"""Attention substrate: blockwise (flash-style) attention with static
triangular scheduling, GQA/MQA, local-window attention, MLA (DeepSeek
latent attention) with the absorb-trick decode path, and KV caches.

Blockwise attention computes online-softmax over KV chunks; q chunks are
unrolled in Python so each one scans only the KV blocks it can actually
see (causal lower-triangle / local window) -- the compiled HLO contains
the triangular FLOP count statically instead of masking a full S^2.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.quant import NF4_LEVELS
from repro.kernels.paged_attention import (paged_gqa_attention,
                                           paged_mla_attention,
                                           paged_nf4_gqa_attention,
                                           paged_quant_gqa_attention)
from repro.kernels.ring_attention import (ring_nf4_gqa_attention,
                                          ring_quant_gqa_attention)
from repro.models.layers import (apply_linear, apply_rmsnorm, apply_rope,
                                 init_linear, init_rmsnorm)

NEG_INF = -1e30


# ---------------------------------------------------------------- caches

@partial(jax.tree_util.register_dataclass, data_fields=("k", "v"),
         meta_fields=())
@dataclasses.dataclass
class KVCache:
    """Full-context cache; slot i holds position i."""
    k: jax.Array   # (B, W, KH, dk)
    v: jax.Array   # (B, W, KH, dv)


@partial(jax.tree_util.register_dataclass, data_fields=("k", "v", "ring_pos"),
         meta_fields=())
@dataclasses.dataclass
class RingKVCache:
    """Rolling window cache; ring_pos[b, i] = absolute position in slot i.

    ring_pos is per batch row so continuous-batching slots can sit at
    different absolute positions (-1 when empty)."""
    k: jax.Array          # (B, W, KH, dk)
    v: jax.Array          # (B, W, KH, dv)
    ring_pos: jax.Array   # (B, W) int32, -1 when empty


@partial(jax.tree_util.register_dataclass, data_fields=("ckv", "krope"),
         meta_fields=())
@dataclasses.dataclass
class LatentCache:
    """MLA compressed cache: latent c_kv + shared rope key."""
    ckv: jax.Array     # (B, W, kv_rank)
    krope: jax.Array   # (B, W, rope_dim)


@partial(jax.tree_util.register_dataclass,
         data_fields=("k", "v", "k_scale", "v_scale"), meta_fields=())
@dataclasses.dataclass
class QuantKVCache:
    """int8 KV cache with per-(position, kv-head) absmax scales -- halves
    decode-phase cache bandwidth vs bf16 (beyond-paper optimization;
    EXPERIMENTS.md §Perf hillclimb 3)."""
    k: jax.Array        # (B, W, KH, dk) int8
    v: jax.Array        # (B, W, KH, dv) int8
    k_scale: jax.Array  # (B, W, KH) f32
    v_scale: jax.Array  # (B, W, KH) f32


@partial(jax.tree_util.register_dataclass,
         data_fields=("k", "v", "k_scale", "v_scale"), meta_fields=())
@dataclasses.dataclass
class NF4KVCache:
    """NF4 KV cache: 4-bit codes (split nibble packing, ``_qnf4``) with
    per-(position, kv-head) absmax scales -- quarter the bf16 cache
    bandwidth; the ring decode kernel dequantizes in-kernel."""
    k: jax.Array        # (B, W, KH, dk/2) uint8
    v: jax.Array        # (B, W, KH, dv/2) uint8
    k_scale: jax.Array  # (B, W, KH) f32
    v_scale: jax.Array  # (B, W, KH) f32


@partial(jax.tree_util.register_dataclass, data_fields=("k", "v"),
         meta_fields=())
@dataclasses.dataclass
class PagedKVCache:
    """Block-paged full-context cache: a global page pool with NO batch
    axis.  Pool page ``page_table[slot, j]`` holds the slot's positions
    ``[j*page_size, (j+1)*page_size)``; pool page 0 is the reserved null
    page -- dead page-table entries point there, so stale slots stream
    and scatter into scratch the position mask zeroes exactly."""
    k: jax.Array   # (P, page_size, KH, dk)
    v: jax.Array   # (P, page_size, KH, dv)


@partial(jax.tree_util.register_dataclass,
         data_fields=("k", "v", "k_scale", "v_scale"), meta_fields=())
@dataclasses.dataclass
class PagedQuantKVCache:
    """Paged int8 pools + per-(position, kv-head) scales; the paged
    decode kernel dequantizes in-kernel, mirroring ``_dq8`` exactly."""
    k: jax.Array        # (P, page_size, KH, dk) int8
    v: jax.Array        # (P, page_size, KH, dv) int8
    k_scale: jax.Array  # (P, page_size, KH) f32
    v_scale: jax.Array  # (P, page_size, KH) f32


@partial(jax.tree_util.register_dataclass,
         data_fields=("k", "v", "k_scale", "v_scale"), meta_fields=())
@dataclasses.dataclass
class PagedNF4KVCache:
    """Paged NF4 code pools + per-(position, kv-head) scales; the paged
    NF4 decode kernel dequantizes in-kernel."""
    k: jax.Array        # (P, page_size, KH, dk/2) uint8
    v: jax.Array        # (P, page_size, KH, dv/2) uint8
    k_scale: jax.Array  # (P, page_size, KH) f32
    v_scale: jax.Array  # (P, page_size, KH) f32


@partial(jax.tree_util.register_dataclass, data_fields=("ckv", "krope"),
         meta_fields=())
@dataclasses.dataclass
class PagedLatentCache:
    """Paged MLA latent pools (c_kv + shared rope key)."""
    ckv: jax.Array     # (P, page_size, kv_rank)
    krope: jax.Array   # (P, page_size, rope_dim)


def _q8(x):
    """x: (B, S, KH, hd) -> (int8, scale (B,S,KH))."""
    scale = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1),
                        1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _dq8(q, scale, dtype):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def _qnf4(x):
    """x: (..., d) -> (codes (..., d/2) uint8, scale (...,) f32).

    NF4 with one absmax block per (position, head) row, packed in the
    SPLIT nibble convention: byte i holds element ``i`` in its low
    nibble and element ``i + d/2`` in its high nibble, so the decode
    kernels dequantize the two head-dim halves without any nibble
    interleave (kernels/ring_attention.py)."""
    d = x.shape[-1]
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), 1e-8)
    levels = jnp.asarray(NF4_LEVELS)
    normed = xf / scale[..., None]
    idx = jnp.argmin(jnp.abs(normed[..., None] - levels),
                     axis=-1).astype(jnp.uint8)
    lo, hi = idx[..., :d // 2], idx[..., d // 2:]
    return (lo | (hi << 4)).astype(jnp.uint8), scale


def _dqnf4(codes, scale, dtype):
    """Inverse of ``_qnf4`` (split packing): low nibbles decode head
    dims [0, d/2), high nibbles [d/2, d)."""
    levels = jnp.asarray(NF4_LEVELS)
    lo = levels[(codes & jnp.uint8(0x0F)).astype(jnp.int32)]
    hi = levels[(codes >> 4).astype(jnp.int32)]
    return (jnp.concatenate([lo, hi], axis=-1)
            * scale[..., None]).astype(dtype)


def pos_vector(pos, batch: int) -> jax.Array:
    """Decode position(s) as a (B,) int32 vector.

    ``pos`` may be a scalar (the classic uniform-batch decode step) or a
    (B,) vector (continuous batching: every slot sits at its own
    absolute position)."""
    return jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (batch,))


# ------------------------------------------------- blockwise attention

def _chunks(n: int, c: int) -> int:
    assert n % c == 0, (n, c)
    return n // c


def _pick_chunk(n: int, pref: int) -> int:
    """Largest chunk <= pref that divides n (frontend prefixes make the
    total sequence non-power-of-two, e.g. 4096 + 256 patches)."""
    c = max(1, min(pref, n))
    while n % c:
        c -= 1
    return c


def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: int = 0,
                        q_offset: int = 0,
                        q_chunk: int = 512, kv_chunk: int = 1024) -> jax.Array:
    """Online-softmax attention.

    q: (B, Sq, H, dk); k: (B, Skv, KH, dk); v: (B, Skv, KH, dv).
    H % KH == 0 (GQA groups).  ``q_offset``: absolute position of q[0]
    (prefill continuation); causal masking compares absolute positions.
    Returns (B, Sq, H, dv).
    """
    from repro.distributed.sharding import constrain_heads
    q, k, v = constrain_heads(q), constrain_heads(k), constrain_heads(v)
    b, sq, h, dk = q.shape
    _, skv, kh, _ = k.shape
    dv = v.shape[-1]
    g = h // kh
    q_chunk = _pick_chunk(sq, q_chunk)
    kv_chunk = _pick_chunk(skv, kv_chunk)
    n_q = _chunks(sq, q_chunk)
    n_kv = _chunks(skv, kv_chunk)
    scale = 1.0 / jnp.sqrt(jnp.float32(dk))

    qg = q.reshape(b, sq, kh, g, dk)
    # Block K/V ONCE per call; each q chunk scans a slice of the blocked
    # stack (a view), instead of materializing its own sliced+transposed
    # copy -- the per-chunk copies cost O(S^2 / chunk) HBM traffic
    # (measured: EXPERIMENTS.md §Perf iteration 1).
    kb_all = k.reshape(b, n_kv, kv_chunk, kh, dk).transpose(1, 0, 2, 3, 4)
    vb_all = v.reshape(b, n_kv, kv_chunk, kh, dv).transpose(1, 0, 2, 3, 4)

    outs = []
    for qi in range(n_q):
        q_lo = qi * q_chunk
        q_hi_abs = q_offset + q_lo + q_chunk - 1    # last abs q position
        # static KV block range visible to this q chunk
        if causal:
            blk_hi = min(n_kv, (q_hi_abs // kv_chunk) + 1)
        else:
            blk_hi = n_kv
        if window > 0:
            lo_abs = max(0, q_offset + q_lo - window + 1)
            blk_lo = lo_abs // kv_chunk
        else:
            blk_lo = 0
        blk_lo = min(blk_lo, blk_hi - 1) if blk_hi > 0 else 0

        qc = qg[:, q_lo:q_lo + q_chunk]
        q_pos = q_offset + q_lo + jnp.arange(q_chunk)

        def body(carry, blk, q_pos=q_pos, qc=qc, blk_lo=blk_lo):
            m, l, acc, bi = carry
            kc, vc = blk
            # bf16 inputs, f32 accumulation (MXU-native contraction)
            s = jax.lax.dot_general(
                qc, kc, (((4,), (3,)), ((0, 2), (0, 2))),
                preferred_element_type=jnp.float32)   # (b,h,q,g,k)
            s = s.transpose(0, 1, 3, 2, 4) * scale    # (b,h,g,q,k)
            k_pos = (blk_lo + bi) * kv_chunk + jnp.arange(kv_chunk)
            mask = jnp.ones((q_chunk, kv_chunk), bool)
            if causal:
                mask &= k_pos[None, :] <= q_pos[:, None]
            if window > 0:
                mask &= k_pos[None, :] > q_pos[:, None] - window
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jax.lax.dot_general(
                p.astype(vc.dtype), vc, (((4,), (1,)), ((0, 1), (0, 2))),
                preferred_element_type=jnp.float32)   # (b,h,g,q,d)
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new, bi + 1), None

        m0 = jnp.full((b, kh, g, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kh, g, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, kh, g, q_chunk, dv), jnp.float32)
        (m, l, acc, _), _ = jax.lax.scan(
            body, (m0, l0, a0, 0), (kb_all[blk_lo:blk_hi],
                                    vb_all[blk_lo:blk_hi]))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        outs.append(out.transpose(0, 3, 1, 2, 4).reshape(b, q_chunk, h, dv))
    return jnp.concatenate(outs, axis=1).astype(q.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     valid: jax.Array) -> jax.Array:
    """One-token attention over a cache.

    q: (B, 1, H, dk); caches: (B, W, KH, d*); valid: (W,) shared or
    (B, W) per-row (continuous batching) bool."""
    b, _, h, dk = q.shape
    _, w, kh, _ = k_cache.shape
    g = h // kh
    if valid.ndim == 1:
        valid = valid[None]
    # f32 dots at full precision (a TPU's default f32 dot rounds the
    # softmax weights to bf16), the precision the decode kernels use
    hi = jax.lax.Precision.HIGHEST
    qg = q.reshape(b, kh, g, dk).astype(jnp.float32)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache.astype(jnp.float32),
                   precision=hi)
    s = s / jnp.sqrt(jnp.float32(dk))
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache.astype(jnp.float32),
                     precision=hi)
    return out.reshape(b, 1, h, -1).astype(q.dtype)


# ------------------------------------------------------------------ GQA

def init_gqa(key: jax.Array, cfg: ArchConfig, cross: bool = False):
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 5)
    p = {"norm": init_rmsnorm(d, cfg),
         "wq": init_linear(ks[0], d, cfg.n_heads * hd, cfg, "attn",
                           transposed=True),
         "wk": init_linear(ks[1], d, cfg.n_kv_heads * hd, cfg, "attn",
                           transposed=True),
         "wv": init_linear(ks[2], d, cfg.n_kv_heads * hd, cfg, "attn",
                           transposed=True),
         "wo": init_linear(ks[3], cfg.n_heads * hd, d, cfg, "attn")}
    return p


def _split_heads(x, n, hd):
    b, s, _ = x.shape
    return x.reshape(b, s, n, hd)


def apply_gqa(p, x: jax.Array, cfg: ArchConfig, *, local: bool = False,
              positions: jax.Array, mode: str,
              cache=None, pos=None, causal: bool = True,
              memory: Optional[jax.Array] = None,
              last_pos: Optional[jax.Array] = None, route=None,
              page_table: Optional[jax.Array] = None,
              prefix=None, q_offset: int = 0, **_):
    """GQA/MQA self-attention (or cross-attention when ``memory`` given).

    mode: train | prefill | decode.  Returns (y, new_cache).
    ``route`` (core.execplan.PhaseRoute): the phase's resolved kernel
    route, threaded into every projection.
    ``last_pos`` ((B,) int32, prefill only): last real position of a
    right-padded prompt -- the rolling-window cache build keeps the last
    ``window`` REAL positions per row instead of the padded tail, so
    bucket padding never evicts prompt tokens (full-context caches
    ignore it; pad entries there are masked/overwritten by decode).
    ``page_table`` ((B, max_pages) int32, decode only): slot -> pool-page
    map when ``cache`` is a Paged* pool.
    ``prefix`` (dense KVCache, prefill only) + ``q_offset`` (STATIC int):
    continuation prefill for radix prefix sharing -- attend over the
    gathered prefix K/V (absolute positions [0, q_offset)) concatenated
    with this call's suffix, but cache only the suffix.  ``positions``
    must already be offset by the caller.
    """
    hd = cfg.resolved_head_dim
    h, kh = cfg.n_heads, cfg.n_kv_heads
    window = cfg.window if local else 0
    xn = apply_rmsnorm(p["norm"], x, cfg.norm_eps)
    q = _split_heads(apply_linear(p["wq"], xn, route), h, hd)

    kv_src = memory if memory is not None else xn
    is_cross = memory is not None

    if mode in ("train", "prefill"):
        k = _split_heads(apply_linear(p["wk"], kv_src, route), kh, hd)
        v = _split_heads(apply_linear(p["wv"], kv_src, route), kh, hd)
        if not is_cross:
            q = apply_rope(q, positions, cfg.rope_theta)
            kpos = positions
            k = apply_rope(k, kpos, cfg.rope_theta)
        if prefix is not None and not is_cross:
            # shared-prefix rows were cached roped at their absolute
            # positions, so concat gives the same K/V stack a full
            # prefill of prefix+suffix would have built (row-wise
            # bitwise: rope and the k/v projections are per-position)
            k_att = jnp.concatenate([prefix.k.astype(k.dtype), k], axis=1)
            v_att = jnp.concatenate([prefix.v.astype(v.dtype), v], axis=1)
        else:
            k_att, v_att = k, v
        y = blockwise_attention(q, k_att, v_att,
                                causal=causal and not is_cross,
                                window=window, q_offset=q_offset)
        new_cache = None
        if mode == "prefill":
            new_cache = _build_cache(k, v, cfg, local, is_cross,
                                     last_pos=last_pos,
                                     kv_dtype=getattr(route, "kv_dtype",
                                                      None))
        y = apply_linear(p["wo"], y.reshape(*y.shape[:2], h * hd), route)
        return x + y, new_cache

    # decode (``pos`` scalar, or (B,) per-slot for continuous batching)
    assert cache is not None and pos is not None
    if is_cross:  # cross K/V precomputed at prefill
        w = cache.k.shape[1]
        valid = jnp.ones((w,), bool)
        q = q  # no rope on cross queries
        y = decode_attention(q, cache.k, cache.v, valid)
        new_cache = cache
    else:
        b = x.shape[0]
        pv = pos_vector(pos, b)
        rows = jnp.arange(b)
        posb = pv[:, None]
        q = apply_rope(q, posb, cfg.rope_theta)
        k = _split_heads(apply_linear(p["wk"], xn, route), kh, hd)
        v = _split_heads(apply_linear(p["wv"], xn, route), kh, hd)
        k = apply_rope(k, posb, cfg.rope_theta)
        if local:
            w = cache.k.shape[1]
            slots = pv % w
            kc = cache.k.at[rows, slots].set(k[:, 0])
            vc = cache.v.at[rows, slots].set(v[:, 0])
            ring = cache.ring_pos.at[rows, slots].set(pv)
            valid = ((ring >= 0) & (ring <= posb)
                     & (ring > posb - window))          # (B, W)
            new_cache = RingKVCache(k=kc, v=vc, ring_pos=ring)
            y = decode_attention(q, kc, vc, valid)
        elif isinstance(cache, PagedQuantKVCache):
            with jax.named_scope("kv_write"):
                ps = cache.k.shape[1]
                pages = page_table[rows, pv // ps]
                off = pv % ps
                kq, ks = _q8(k)
                vq, vs = _q8(v)
                kc = cache.k.at[pages, off].set(kq[:, 0])
                vc = cache.v.at[pages, off].set(vq[:, 0])
                ksc = cache.k_scale.at[pages, off].set(ks[:, 0])
                vsc = cache.v_scale.at[pages, off].set(vs[:, 0])
            new_cache = PagedQuantKVCache(k=kc, v=vc, k_scale=ksc,
                                          v_scale=vsc)
            with jax.named_scope("paged_gqa"):
                y = paged_quant_gqa_attention(q, kc, vc, ksc, vsc,
                                              page_table, pv)
        elif isinstance(cache, PagedNF4KVCache):
            with jax.named_scope("kv_write"):
                ps = cache.k.shape[1]
                pages = page_table[rows, pv // ps]
                off = pv % ps
                kq, ks = _qnf4(k)
                vq, vs = _qnf4(v)
                kc = cache.k.at[pages, off].set(kq[:, 0])
                vc = cache.v.at[pages, off].set(vq[:, 0])
                ksc = cache.k_scale.at[pages, off].set(ks[:, 0])
                vsc = cache.v_scale.at[pages, off].set(vs[:, 0])
            new_cache = PagedNF4KVCache(k=kc, v=vc, k_scale=ksc,
                                        v_scale=vsc)
            with jax.named_scope("paged_gqa"):
                y = paged_nf4_gqa_attention(q, kc, vc, ksc, vsc,
                                            page_table, pv)
        elif isinstance(cache, PagedKVCache):
            with jax.named_scope("kv_write"):
                ps = cache.k.shape[1]
                pages = page_table[rows, pv // ps]
                off = pv % ps
                kc = cache.k.at[pages, off].set(k[:, 0])
                vc = cache.v.at[pages, off].set(v[:, 0])
            new_cache = PagedKVCache(k=kc, v=vc)
            with jax.named_scope("paged_gqa"):
                y = paged_gqa_attention(q, kc, vc, page_table, pv)
        elif isinstance(cache, QuantKVCache):
            kq, ks = _q8(k)
            vq, vs = _q8(v)
            kc = cache.k.at[rows, pv].set(kq[:, 0])
            vc = cache.v.at[rows, pv].set(vq[:, 0])
            ksc = cache.k_scale.at[rows, pv].set(ks[:, 0])
            vsc = cache.v_scale.at[rows, pv].set(vs[:, 0])
            new_cache = QuantKVCache(k=kc, v=vc, k_scale=ksc, v_scale=vsc)
            # in-kernel dequant (mirrors _dq8 bit-for-bit; the historical
            # out-of-kernel path was decode_attention over _dq8(kc, ...))
            y = ring_quant_gqa_attention(q, kc, vc, ksc, vsc, pv)
        elif isinstance(cache, NF4KVCache):
            kq, ks = _qnf4(k)
            vq, vs = _qnf4(v)
            kc = cache.k.at[rows, pv].set(kq[:, 0])
            vc = cache.v.at[rows, pv].set(vq[:, 0])
            ksc = cache.k_scale.at[rows, pv].set(ks[:, 0])
            vsc = cache.v_scale.at[rows, pv].set(vs[:, 0])
            new_cache = NF4KVCache(k=kc, v=vc, k_scale=ksc, v_scale=vsc)
            y = ring_nf4_gqa_attention(q, kc, vc, ksc, vsc, pv)
        else:
            kc = cache.k.at[rows, pv].set(k[:, 0])
            vc = cache.v.at[rows, pv].set(v[:, 0])
            valid = jnp.arange(cache.k.shape[1])[None, :] <= posb
            new_cache = KVCache(k=kc, v=vc)
            y = decode_attention(q, kc, vc, valid)
    y = apply_linear(p["wo"], y.reshape(*y.shape[:2], h * hd), route)
    return x + y, new_cache


def _build_cache(k, v, cfg: ArchConfig, local: bool, is_cross: bool,
                 last_pos=None, kv_dtype=None):
    """``kv_dtype`` is the cache precision to BUILD (the prefill route's
    ``kv_dtype`` when a plan is threaded; None falls back to
    ``cfg.kv_cache``, the historical model-wide setting)."""
    if kv_dtype is None:
        kv_dtype = cfg.kv_cache
    if is_cross:
        return KVCache(k=k, v=v)
    if kv_dtype == "int8" and not local:
        kq, ks = _q8(k)
        vq, vs = _q8(v)
        return QuantKVCache(k=kq, v=vq, k_scale=ks, v_scale=vs)
    if kv_dtype == "nf4" and not local:
        kq, ks = _qnf4(k)
        vq, vs = _qnf4(v)
        return NF4KVCache(k=kq, v=vq, k_scale=ks, v_scale=vs)
    if local:
        # Ring slot i holds the latest REAL position p <= last_pos with
        # p % w == i (per row: continuous batching right-pads prompts,
        # so rows end at different real positions).  Slots whose p would
        # be negative (prompt shorter than the window) stay empty (-1).
        w = cfg.window
        b, s = k.shape[0], k.shape[1]
        if last_pos is None:
            last_pos = jnp.full((b,), s - 1, jnp.int32)
        slots = jnp.arange(w, dtype=jnp.int32)
        p = last_pos[:, None] - ((last_pos[:, None] - slots[None, :]) % w)
        ok = p >= 0                                           # (B, w)
        idx = jnp.clip(p, 0, s - 1)
        kc = jnp.take_along_axis(k, idx[..., None, None], axis=1)
        vc = jnp.take_along_axis(v, idx[..., None, None], axis=1)
        kc = jnp.where(ok[..., None, None], kc, 0)
        vc = jnp.where(ok[..., None, None], vc, 0)
        return RingKVCache(k=kc, v=vc, ring_pos=jnp.where(ok, p, -1))
    return KVCache(k=k, v=v)


def init_gqa_cache(cfg: ArchConfig, batch: int, ctx: int, local: bool,
                   dtype, kv_dtype=None):
    """``kv_dtype`` overrides ``cfg.kv_cache`` (the decode route's
    ``kv_dtype`` when the caller holds a resolved plan)."""
    if kv_dtype is None:
        kv_dtype = cfg.kv_cache
    hd = cfg.resolved_head_dim
    kh = cfg.n_kv_heads
    w = min(cfg.window, ctx) if local else ctx
    if local:
        k = jnp.zeros((batch, w, kh, hd), dtype)
        v = jnp.zeros((batch, w, kh, hd), dtype)
        return RingKVCache(k=k, v=v,
                           ring_pos=jnp.full((batch, w), -1, jnp.int32))
    if kv_dtype == "int8":
        return QuantKVCache(
            k=jnp.zeros((batch, w, kh, hd), jnp.int8),
            v=jnp.zeros((batch, w, kh, hd), jnp.int8),
            k_scale=jnp.zeros((batch, w, kh), jnp.float32),
            v_scale=jnp.zeros((batch, w, kh), jnp.float32))
    if kv_dtype == "nf4":
        return NF4KVCache(
            k=jnp.zeros((batch, w, kh, hd // 2), jnp.uint8),
            v=jnp.zeros((batch, w, kh, hd // 2), jnp.uint8),
            k_scale=jnp.zeros((batch, w, kh), jnp.float32),
            v_scale=jnp.zeros((batch, w, kh), jnp.float32))
    k = jnp.zeros((batch, w, kh, hd), dtype)
    v = jnp.zeros((batch, w, kh, hd), dtype)
    return KVCache(k=k, v=v)


def init_paged_gqa_cache(cfg: ArchConfig, n_pages: int, page_size: int,
                         dtype, kv_dtype=None):
    """Global K/V page pool (page 0 = reserved null page).  ``kv_dtype``
    overrides ``cfg.kv_cache`` (the decode route's ``kv_dtype``)."""
    if kv_dtype is None:
        kv_dtype = cfg.kv_cache
    hd = cfg.resolved_head_dim
    kh = cfg.n_kv_heads
    if kv_dtype == "int8":
        return PagedQuantKVCache(
            k=jnp.zeros((n_pages, page_size, kh, hd), jnp.int8),
            v=jnp.zeros((n_pages, page_size, kh, hd), jnp.int8),
            k_scale=jnp.zeros((n_pages, page_size, kh), jnp.float32),
            v_scale=jnp.zeros((n_pages, page_size, kh), jnp.float32))
    if kv_dtype == "nf4":
        return PagedNF4KVCache(
            k=jnp.zeros((n_pages, page_size, kh, hd // 2), jnp.uint8),
            v=jnp.zeros((n_pages, page_size, kh, hd // 2), jnp.uint8),
            k_scale=jnp.zeros((n_pages, page_size, kh), jnp.float32),
            v_scale=jnp.zeros((n_pages, page_size, kh), jnp.float32))
    return PagedKVCache(k=jnp.zeros((n_pages, page_size, kh, hd), dtype),
                        v=jnp.zeros((n_pages, page_size, kh, hd), dtype))


# ------------------------------------------------------------------ MLA

def init_mla(key: jax.Array, cfg: ArchConfig):
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 8)
    return {
        "norm": init_rmsnorm(d, cfg),
        "dq": init_linear(ks[0], d, m.q_lora_rank, cfg, "attn", transposed=True),
        "qnorm": init_rmsnorm(m.q_lora_rank, cfg),
        "uq": init_linear(ks[1], m.q_lora_rank, h * qk, cfg, "attn",
                          transposed=True),
        "dkv": init_linear(ks[2], d, m.kv_lora_rank + m.qk_rope_head_dim,
                           cfg, "attn", transposed=True),
        "kvnorm": init_rmsnorm(m.kv_lora_rank, cfg),
        "uk": init_linear(ks[3], m.kv_lora_rank, h * m.qk_nope_head_dim,
                          cfg, "attn", transposed=True),
        "uv": init_linear(ks[4], m.kv_lora_rank, h * m.v_head_dim, cfg,
                          "attn", transposed=True),
        "wo": init_linear(ks[5], h * m.v_head_dim, d, cfg, "attn"),
    }


def _mla_qkv(p, xn, cfg, positions, route=None):
    """Decompressed q, k, v for train/prefill plus the latent (for cache)."""
    m = cfg.mla
    h = cfg.n_heads
    b, s, _ = xn.shape
    cq = apply_rmsnorm(p["qnorm"], apply_linear(p["dq"], xn, route),
                       cfg.norm_eps)
    q = apply_linear(p["uq"], cq, route).reshape(b, s, h, -1)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    dkv = apply_linear(p["dkv"], xn, route)
    ckv, krope = jnp.split(dkv, [m.kv_lora_rank], axis=-1)
    ckv = apply_rmsnorm(p["kvnorm"], ckv, cfg.norm_eps)
    krope = apply_rope(krope[:, :, None, :], positions, cfg.rope_theta)

    k_nope = apply_linear(p["uk"], ckv, route).reshape(b, s, h,
                                                       m.qk_nope_head_dim)
    v = apply_linear(p["uv"], ckv, route).reshape(b, s, h, m.v_head_dim)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(krope, (b, s, h, m.qk_rope_head_dim))],
        axis=-1)
    return q_full, k_full, v, ckv, krope[:, :, 0, :]


def apply_mla(p, x: jax.Array, cfg: ArchConfig, *, positions, mode: str,
              cache=None, pos=None, route=None,
              page_table: Optional[jax.Array] = None,
              prefix=None, q_offset: int = 0, **_):
    """MLA attention.  Prefill caches only (c_kv, k_rope); decode uses the
    absorb trick (q projected into latent space) so per-step work is
    O(ctx * kv_rank), not O(ctx * heads * head_dim).

    ``page_table``/``prefix``/``q_offset``: see ``apply_gqa``.  A shared
    prefix arrives as a dense LatentCache; its K/V are re-decompressed
    through W_uk/W_uv here -- per-row linears, so bitwise what a full
    prefill over prefix+suffix computes for those rows."""
    m = cfg.mla
    h = cfg.n_heads
    xn = apply_rmsnorm(p["norm"], x, cfg.norm_eps)

    if mode in ("train", "prefill"):
        q, k, v, ckv, krope = _mla_qkv(p, xn, cfg, positions, route)
        if prefix is not None:
            b, lp = prefix.ckv.shape[0], prefix.ckv.shape[1]
            k_nope_p = apply_linear(p["uk"], prefix.ckv, route).reshape(
                b, lp, h, m.qk_nope_head_dim)
            v_p = apply_linear(p["uv"], prefix.ckv, route).reshape(
                b, lp, h, m.v_head_dim)
            k_p = jnp.concatenate(
                [k_nope_p,
                 jnp.broadcast_to(prefix.krope[:, :, None, :],
                                  (b, lp, h, m.qk_rope_head_dim))], axis=-1)
            k = jnp.concatenate([k_p.astype(k.dtype), k], axis=1)
            v = jnp.concatenate([v_p.astype(v.dtype), v], axis=1)
        y = blockwise_attention(q, k, v, causal=True, q_offset=q_offset)
        new_cache = LatentCache(ckv=ckv, krope=krope) if mode == "prefill" else None
        y = apply_linear(p["wo"], y.reshape(*y.shape[:2], h * m.v_head_dim),
                         route)
        return x + y, new_cache

    # decode with absorbed projections (``pos`` scalar or (B,) per-slot)
    b = x.shape[0]
    pv = pos_vector(pos, b)
    rows = jnp.arange(b)
    posb = pv[:, None]
    cq = apply_rmsnorm(p["qnorm"], apply_linear(p["dq"], xn, route),
                       cfg.norm_eps)
    q = apply_linear(p["uq"], cq, route).reshape(b, 1, h, -1)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, posb, cfg.rope_theta)

    dkv = apply_linear(p["dkv"], xn, route)
    ckv_new, krope_new = jnp.split(dkv, [m.kv_lora_rank], axis=-1)
    ckv_new = apply_rmsnorm(p["kvnorm"], ckv_new, cfg.norm_eps)
    krope_new = apply_rope(krope_new[:, :, None, :], posb,
                           cfg.rope_theta)[:, :, 0, :]

    paged = isinstance(cache, PagedLatentCache)
    if paged:
        ps = cache.ckv.shape[1]
        pages = page_table[rows, pv // ps]
        off = pv % ps
        ckv = cache.ckv.at[pages, off].set(ckv_new[:, 0])
        krope = cache.krope.at[pages, off].set(krope_new[:, 0])
        new_cache = PagedLatentCache(ckv=ckv, krope=krope)
    else:
        ckv = cache.ckv.at[rows, pv].set(ckv_new[:, 0])
        krope = cache.krope.at[rows, pv].set(krope_new[:, 0])
        new_cache = LatentCache(ckv=ckv, krope=krope)

    # absorb: q_lat[h] = q_nope[h] @ W_uk[h]^T  -> score against latent
    wuk = _dense_weight(p["uk"])                     # (kv_rank, h*nope)
    wuk = wuk.reshape(m.kv_lora_rank, h, m.qk_nope_head_dim)
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0].astype(jnp.float32),
                       wuk.astype(jnp.float32))
    if paged:
        o_lat = paged_mla_attention(
            q_lat, q_rope[:, 0].astype(jnp.float32), ckv, krope,
            page_table, pv,
            qk_dim=m.qk_nope_head_dim + m.qk_rope_head_dim)
    else:
        s = jnp.einsum("bhr,bkr->bhk", q_lat, ckv.astype(jnp.float32))
        s = s + jnp.einsum("bhd,bkd->bhk", q_rope[:, 0].astype(jnp.float32),
                           krope.astype(jnp.float32))
        s = s / jnp.sqrt(jnp.float32(m.qk_nope_head_dim
                                     + m.qk_rope_head_dim))
        valid = jnp.arange(ckv.shape[1])[None, :] <= posb    # (B, W)
        s = jnp.where(valid[:, None, :], s, NEG_INF)
        pr = jax.nn.softmax(s, axis=-1)
        o_lat = jnp.einsum("bhk,bkr->bhr", pr, ckv.astype(jnp.float32))
    wuv = _dense_weight(p["uv"]).reshape(m.kv_lora_rank, h, m.v_head_dim)
    o = jnp.einsum("bhr,rhv->bhv", o_lat, wuv.astype(jnp.float32))
    y = o.reshape(b, 1, h * m.v_head_dim).astype(x.dtype)
    y = apply_linear(p["wo"], y, route)
    return x + y, new_cache


def _dense_weight(lin) -> jax.Array:
    """Effective dense weight of a (possibly SALR) linear -- used by the
    MLA absorb path, which needs the matrix itself, not its action."""
    from repro.core.salr import SALRLinear, effective_weight
    if isinstance(lin, SALRLinear):
        return effective_weight(lin)
    return lin["w"]


def init_mla_cache(cfg: ArchConfig, batch: int, ctx: int, dtype):
    m = cfg.mla
    return LatentCache(
        ckv=jnp.zeros((batch, ctx, m.kv_lora_rank), dtype),
        krope=jnp.zeros((batch, ctx, m.qk_rope_head_dim), dtype))


def init_paged_mla_cache(cfg: ArchConfig, n_pages: int, page_size: int,
                         dtype):
    m = cfg.mla
    return PagedLatentCache(
        ckv=jnp.zeros((n_pages, page_size, m.kv_lora_rank), dtype),
        krope=jnp.zeros((n_pages, page_size, m.qk_rope_head_dim), dtype))
