"""Model assembly: layer groups (scan + remat over stacked params),
mixer dispatch, encoder-decoder wiring, modality-frontend stubs, caches,
and the three entry points: train forward, prefill, decode step.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, LayerGroup
from repro.core import execplan
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import recurrent as rec
from repro.models import xlstm as xl
from repro.models import layers as L
from repro.models.layers import (apply_embedding, apply_lm_head, apply_mlp,
                                 apply_rmsnorm, init_embedding, init_lm_head,
                                 init_mlp, init_rmsnorm)

MIXER_INIT = {
    "attn": lambda k, cfg: attn.init_gqa(k, cfg),
    "attn_local": lambda k, cfg: attn.init_gqa(k, cfg),
    "mla": lambda k, cfg: attn.init_mla(k, cfg),
    "rglru": lambda k, cfg: rec.init_rglru(k, cfg),
    "mlstm": lambda k, cfg: xl.init_mlstm(k, cfg),
    "slstm": lambda k, cfg: xl.init_slstm(k, cfg),
}

MIXER_APPLY = {
    "attn": partial(attn.apply_gqa, local=False),
    "attn_local": partial(attn.apply_gqa, local=True),
    "mla": attn.apply_mla,
    "rglru": rec.apply_rglru,
    "mlstm": xl.apply_mlstm,
    "slstm": xl.apply_slstm,
}

# mixers whose named scope is ``attn``; a recurrent mixer's scope is its kind
ATTN_KINDS = ("attn", "attn_local", "mla")


def _group_mlp(cfg: ArchConfig, group: LayerGroup) -> str:
    return group.mlp if group.mlp is not None else cfg.mlp


# ------------------------------------------------------------------ init

def init_layer(key: jax.Array, cfg: ArchConfig, kind: str, mlp_kind: str,
               cross: bool = False):
    ks = jax.random.split(key, 4)
    p = {"mixer": MIXER_INIT[kind](ks[0], cfg)}
    if cross:
        p["cross"] = attn.init_gqa(ks[1], cfg)
    if mlp_kind == "moe":
        p["moe"] = moe_mod.init_moe(ks[2], cfg)
    elif mlp_kind != "none":
        p["mlp_norm"] = init_rmsnorm(cfg.d_model, cfg)
        p["mlp"] = init_mlp(ks[3], cfg, mlp_kind)
    return p


def init_group(key: jax.Array, cfg: ArchConfig, group: LayerGroup,
               cross: bool = False):
    """Per pattern position: params stacked over ``repeats`` (scan axis).

    Under an active allocation scope (budgeted compression), per-repeat
    static shapes may differ before padding, so the repeats are
    initialized in a Python loop from the SAME per-repeat keys the vmap
    would use and tree-stacked afterwards — the allocator's per-stack
    rank padding and capacity pinning guarantee uniform leaf shapes."""
    mlp_kind = _group_mlp(cfg, group)
    out = []
    alloc = L.current_allocation() is not None
    for pi, kind in enumerate(group.pattern):
        keys = jax.random.split(jax.random.fold_in(key, pi), group.repeats)
        if alloc:
            tag = L.new_stack_tag()
            per = []
            for ri in range(group.repeats):
                L.begin_repeat((tag, pi))
                per.append(init_layer(keys[ri], cfg, kind, mlp_kind,
                                      cross))
            out.append(jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *per))
        else:
            out.append(jax.vmap(
                lambda k: init_layer(k, cfg, kind, mlp_kind, cross))(keys))
    return out


def init_params(key: jax.Array, cfg: ArchConfig):
    if cfg.salr.budget is not None and L.current_allocation() is None:
        return init_params_allocated(key, cfg)
    ks = jax.random.split(key, 8)
    is_encdec = bool(cfg.encoder_groups)
    params = {
        "embed": init_embedding(ks[0], cfg),
        "groups": [init_group(jax.random.fold_in(ks[1], gi), cfg, g,
                              cross=is_encdec)
                   for gi, g in enumerate(cfg.layer_groups)],
        "final_norm": init_rmsnorm(cfg.d_model, cfg),
        "lm_head": init_lm_head(ks[2], cfg),
    }
    if is_encdec:
        params["encoder"] = {
            "groups": [init_group(jax.random.fold_in(ks[3], gi), cfg, g)
                       for gi, g in enumerate(cfg.encoder_groups)],
            "final_norm": init_rmsnorm(cfg.d_model, cfg),
        }
    return params


def init_params_allocated(key: jax.Array, cfg: ArchConfig):
    """Budget-allocated model compression (cfg.salr.budget; DESIGN.md §8).

    Two passes over the IDENTICAL init traversal with the identical PRNG
    keys: a survey pass records every compressible weight (placeholder
    params, discarded), ``core.allocate`` resolves per-layer
    (sparsity, rank) decisions under the global budget, and a commit
    pass re-initializes consuming the decisions in traversal order.
    MoE expert stacks compress inside ``init_moe``'s own vmap and keep
    the global config (uniform within the expert stack)."""
    from repro.core import allocate
    from repro.models.layers import salr_cfg_for

    survey = L.AllocationSurvey()
    with L.allocation_scope(survey):
        init_params(key, cfg)                  # placeholders, discarded
    decisions = allocate.plan_linear_allocation(
        survey.entries, salr_cfg_for(cfg), cfg.salr.budget)
    feed = L.AllocationFeed(decisions)
    with L.allocation_scope(feed):
        return init_params(key, cfg)


# ----------------------------------------------------------------- apply

def apply_layer(p, x, cfg: ArchConfig, kind: str, mlp_kind: str, *,
                mode: str, positions=None, cache=None, pos=None,
                memory=None, causal=True, last_pos=None, route=None,
                page_table=None, prefix_cache=None, q_offset: int = 0):
    """One block: mixer (+cross-attn) (+mlp).  Returns (x, new_cache).
    ``last_pos`` ((B,) int32, prefill only): last real position of a
    right-padded prompt, consumed by stateful mixers (masked-state
    prefill) and the rolling-window cache build.  ``route``
    (core.execplan.PhaseRoute): the entry point's resolved kernel route,
    threaded into every projection and the MoE dispatch.
    ``page_table`` (decode) / ``prefix_cache`` + ``q_offset`` (prefill
    continuation) reach only the self-attention mixer; cross-attention
    K/V stay slot-dense and are never prefix-shared."""
    mixer_cache = cache.get("mixer") if cache else None
    with jax.named_scope("attn" if kind in ATTN_KINDS else kind):
        x, new_mixer = MIXER_APPLY[kind](
            p["mixer"], x, cfg, positions=positions, mode=mode,
            cache=mixer_cache, pos=pos, causal=causal, last_pos=last_pos,
            route=route, page_table=page_table,
            prefix=prefix_cache.get("mixer") if prefix_cache else None,
            q_offset=q_offset)
        new_cache = {"mixer": new_mixer}
        if "cross" in p:
            cross_cache = cache.get("cross") if cache else None
            x, new_cross = attn.apply_gqa(
                p["cross"], x, cfg, local=False, positions=positions,
                mode=mode, cache=cross_cache, pos=pos, memory=memory,
                causal=False, route=route)
            new_cache["cross"] = new_cross
    if mlp_kind == "moe":
        with jax.named_scope("moe"):
            x = moe_mod.apply_moe(p["moe"], x, cfg, route=route)
    elif mlp_kind != "none":
        with jax.named_scope("mlp"):
            x = x + apply_mlp(p["mlp"], apply_rmsnorm(p["mlp_norm"], x,
                                                      cfg.norm_eps),
                              mlp_kind, route=route)
    return x, new_cache


def apply_group(gp, x, cfg: ArchConfig, group: LayerGroup, *, mode: str,
                positions=None, caches=None, pos=None, memory=None,
                causal=True, remat=True, last_pos=None, route=None,
                page_table=None, prefix_caches=None, q_offset: int = 0):
    """Scan over ``repeats``; the pattern is applied inside the body.
    ``page_table`` is scan-invariant (every repeat indexes the same
    slot->page map); ``prefix_caches`` are per-repeat stacked like
    ``caches`` and ride the scan xs."""
    mlp_kind = _group_mlp(cfg, group)

    def body(xc, sl):
        params_sl, cache_sl, prefix_sl = sl
        new_caches = []
        for pi, kind in enumerate(group.pattern):
            c = cache_sl[pi] if cache_sl is not None else None
            pc = prefix_sl[pi] if prefix_sl is not None else None
            xc, nc = apply_layer(params_sl[pi], xc, cfg, kind, mlp_kind,
                                 mode=mode, positions=positions, cache=c,
                                 pos=pos, memory=memory, causal=causal,
                                 last_pos=last_pos, route=route,
                                 page_table=page_table, prefix_cache=pc,
                                 q_offset=q_offset)
            new_caches.append(nc)
        return xc, new_caches

    if mode == "train" and remat:
        body = jax.checkpoint(body, prevent_cse=False)

    def scan_body(xc, sl):
        return body(xc, sl)

    xs = (gp, caches if caches is not None else None,
          prefix_caches if prefix_caches is not None else None)
    x, new_caches = jax.lax.scan(scan_body, x, xs, length=group.repeats)
    return x, new_caches


def _embed_inputs(params, cfg: ArchConfig, tokens, frontend_embeds):
    x = apply_embedding(params["embed"], tokens)
    if cfg.frontend and frontend_embeds is not None and cfg.family != "encdec":
        x = jnp.concatenate([frontend_embeds.astype(x.dtype), x], axis=1)
    return x


def _encode(params, cfg: ArchConfig, frontend_embeds, route=None):
    """Encoder stack over frontend embeddings (enc-dec archs).  ``route``
    is the calling entry point's phase route (the encoder always runs
    full-sequence non-causal, but its kernel routes follow the phase
    that invoked it)."""
    x = frontend_embeds
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    for gi, g in enumerate(cfg.encoder_groups):
        x, _ = apply_group(params["encoder"]["groups"][gi], x, cfg, g,
                           mode="train", positions=positions, causal=False,
                           remat=False, route=route)
    return apply_rmsnorm(params["encoder"]["final_norm"], x, cfg.norm_eps)


def forward_hidden(params, cfg: ArchConfig, tokens: jax.Array,
                   frontend_embeds: Optional[jax.Array] = None,
                   plan: Optional[execplan.ExecutionPlan] = None
                   ) -> jax.Array:
    """Teacher-forced forward up to the final norm (no LM head).
    Runs the ``train`` phase of ``plan`` (default: the model's resolved
    plan — reference formulation, see execplan.resolve_plan)."""
    from repro.distributed.sharding import constrain_activation
    route = (plan or execplan.current_override()
             or execplan.resolve_plan(cfg)).route("train")
    memory = None
    if cfg.family == "encdec":
        memory = _encode(params, cfg, frontend_embeds, route)
    with jax.named_scope("embed"):
        x = _embed_inputs(params, cfg, tokens, frontend_embeds)
        x = constrain_activation(x)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    with jax.named_scope("layers"):
        for gi, g in enumerate(cfg.layer_groups):
            x, _ = apply_group(params["groups"][gi], x, cfg, g, mode="train",
                               positions=positions, memory=memory,
                               route=route)
            x = constrain_activation(x)
    with jax.named_scope("head"):
        return apply_rmsnorm(params["final_norm"], x, cfg.norm_eps)


def forward_train(params, cfg: ArchConfig, tokens: jax.Array,
                  frontend_embeds: Optional[jax.Array] = None,
                  plan: Optional[execplan.ExecutionPlan] = None
                  ) -> jax.Array:
    """Full-sequence teacher-forced forward.  Returns logits
    (B, S_total, padded_vocab); for frontend archs S_total includes the
    prefix positions (caller masks them in the loss)."""
    x = forward_hidden(params, cfg, tokens, frontend_embeds, plan)
    return apply_lm_head(params["lm_head"], x)


# ----------------------------------------------------------------- cache

def init_block_cache(cfg: ArchConfig, kind: str, batch: int, ctx: int,
                     dtype, cross: bool, enc_len: int, kv_dtype=None):
    c = {}
    if kind in ("attn", "attn_local"):
        c["mixer"] = attn.init_gqa_cache(cfg, batch, ctx,
                                         local=(kind == "attn_local"),
                                         dtype=dtype, kv_dtype=kv_dtype)
    elif kind == "mla":
        c["mixer"] = attn.init_mla_cache(cfg, batch, ctx, dtype)
    elif kind == "rglru":
        c["mixer"] = rec.init_rglru_cache(cfg, batch, dtype)
    elif kind == "mlstm":
        c["mixer"] = xl.init_mlstm_cache(cfg, batch, dtype)
    elif kind == "slstm":
        c["mixer"] = xl.init_slstm_cache(cfg, batch, dtype)
    if cross:
        hd = cfg.resolved_head_dim
        c["cross"] = attn.KVCache(
            k=jnp.zeros((batch, enc_len, cfg.n_kv_heads, hd), dtype),
            v=jnp.zeros((batch, enc_len, cfg.n_kv_heads, hd), dtype))
    return c


def init_cache(cfg: ArchConfig, batch: int, ctx: int, kv_dtype=None):
    """Decode cache skeleton: per group, per pattern position, stacked
    over repeats.  For enc-dec also includes the encoder memory.
    ``kv_dtype`` overrides ``cfg.kv_cache`` (pass the DECODE route's
    ``kv_dtype`` -- decode reads this cache)."""
    dtype = jnp.dtype(cfg.dtype)
    is_encdec = bool(cfg.encoder_groups)
    enc_len = cfg.frontend_len if is_encdec else 0
    groups = []
    for g in cfg.layer_groups:
        per_pos = []
        for kind in g.pattern:
            one = init_block_cache(cfg, kind, batch, ctx, dtype,
                                   is_encdec, enc_len, kv_dtype=kv_dtype)
            per_pos.append(jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (g.repeats,) + a.shape), one))
        groups.append(per_pos)
    cache = {"groups": groups}
    if is_encdec:
        cache["memory"] = jnp.zeros((batch, enc_len, cfg.d_model), dtype)
    return cache


def prefill(params, cfg: ArchConfig, tokens: jax.Array,
            frontend_embeds: Optional[jax.Array] = None, *,
            logit_index=None,
            plan: Optional[execplan.ExecutionPlan] = None,
            prefix_cache=None, pos_offset: int = 0):
    """Process the prompt; returns (one-position logits, cache).
    Runs the ``prefill`` phase of ``plan`` (default: the model's
    resolved plan).

    ``prefix_cache`` + ``pos_offset`` (STATIC Python int): continuation
    prefill for radix prefix sharing.  ``tokens`` then hold only the
    prompt SUFFIX; the shared prefix arrives as a dense batch=1 cache
    (``gather_prefix_cache``) covering absolute positions
    [0, pos_offset), and the returned cache covers only the suffix
    (the engine's page table stitches prefix + suffix back together for
    decode).  ``pos_offset`` must be static because blockwise
    attention's triangular schedule consumes it in Python arithmetic.

    By default the logits are taken at the last prompt position.
    ``logit_index`` (scalar or (B,) int32, traced ok) selects another
    position instead — bucketed serving right-pads prompts to a small
    set of JIT shapes and reads the logits at the true last token, while
    the padded tail positions stay causally invisible to every real
    token and are masked out of later decode steps by the per-slot
    position (see launch/engine.py).  ``logit_index`` doubles as the
    last-real-position marker for masked-state prefill: stateful mixers
    (rglru/mlstm/slstm) treat positions beyond it as identity
    transitions and the rolling-window cache keeps only real tokens, so
    padded prefill ends in bitwise the exact-length state."""
    route = (plan or execplan.current_override()
             or execplan.resolve_plan(cfg)).route("prefill")
    memory = None
    if cfg.family == "encdec":
        memory = _encode(params, cfg, frontend_embeds, route)
    with jax.named_scope("embed"):
        x = _embed_inputs(params, cfg, tokens, frontend_embeds)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(
        pos_offset + jnp.arange(s, dtype=jnp.int32), (b, s))
    last_pos = None
    if logit_index is not None:
        last_pos = jnp.broadcast_to(jnp.asarray(logit_index, jnp.int32),
                                    (b,))
    caches = []
    with jax.named_scope("layers"):
        for gi, g in enumerate(cfg.layer_groups):
            pcs = prefix_cache["groups"][gi] if prefix_cache else None
            x, nc = apply_group(params["groups"][gi], x, cfg, g,
                                mode="prefill", positions=positions,
                                memory=memory, last_pos=last_pos,
                                route=route, prefix_caches=pcs,
                                q_offset=pos_offset)
            caches.append(nc)
    with jax.named_scope("head"):
        x = apply_rmsnorm(params["final_norm"], x, cfg.norm_eps)
        if logit_index is None:
            x_last = x[:, -1:]
        else:
            idx = jnp.broadcast_to(jnp.asarray(logit_index, jnp.int32), (b,))
            x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
        logits = apply_lm_head(params["lm_head"], x_last)
    cache = {"groups": caches}
    if memory is not None:
        cache["memory"] = memory
    return logits, cache


def decode_step(params, cfg: ArchConfig, cache, tokens: jax.Array,
                pos: jax.Array,
                plan: Optional[execplan.ExecutionPlan] = None):
    """One token step.  tokens: (B, 1); pos: absolute position of this
    token — a scalar int32 (uniform batch) or a (B,) int32 vector
    (continuous batching: each slot decodes at its own position).
    Runs the ``decode`` phase of ``plan`` (default: the model's resolved
    plan).  Returns (logits, new_cache)."""
    route = (plan or execplan.current_override()
             or execplan.resolve_plan(cfg)).route("decode")
    b = tokens.shape[0]
    pos = attn.pos_vector(pos, b)
    pad = -b % DECODE_ROW_ALIGN if _pages_only(cache) else 0
    if pad:
        # rows beyond b are idle slots: they read and write the null page
        tokens = jnp.pad(tokens, ((0, pad), (0, 0)))
        pos = jnp.pad(pos, (0, pad))
        cache = dict(cache, page_table=jnp.pad(cache["page_table"],
                                               ((0, pad), (0, 0))))
    with jax.named_scope("embed"):
        x = apply_embedding(params["embed"], tokens)
    memory = cache.get("memory")
    page_table = cache.get("page_table")
    positions = pos[:, None]
    new_groups = []
    with jax.named_scope("layers"):
        for gi, g in enumerate(cfg.layer_groups):
            x, nc = apply_group(params["groups"][gi], x, cfg, g,
                                mode="decode", positions=positions,
                                caches=cache["groups"][gi], pos=pos,
                                memory=memory, route=route,
                                page_table=page_table)
            new_groups.append(nc)
    with jax.named_scope("head"):
        x = apply_rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = apply_lm_head(params["lm_head"], x)[:b]
    new_cache = {"groups": new_groups}
    if memory is not None:
        new_cache["memory"] = memory
    if page_table is not None:
        new_cache["page_table"] = page_table[:b]
    return logits, new_cache


# A paged decode step runs a multiple of this many rows.  On a TPU, XLA
# work outside the kernels (the LM-head dot, fusions around them) can
# round a row differently depending on how many rows share the step, so
# a lone request (b = 1) would not get the bits the same request gets in
# an n_slots-row engine step.  Page pools have no batch axis, so padding
# costs only idle rows; slot-indexed caches are left as they are.
DECODE_ROW_ALIGN = 8


def _pages_only(cache) -> bool:
    """Every per-layer cache is a batch-free page pool."""
    return ("page_table" in cache and "memory" not in cache
            and all(set(c) == {"mixer"} and isinstance(
                c["mixer"], (attn.PagedKVCache, attn.PagedQuantKVCache,
                             attn.PagedNF4KVCache, attn.PagedLatentCache))
                    for gcs in cache["groups"] for c in gcs))


# ----------------------------------------------------- slot-indexed cache

def init_slot_cache(cfg: ArchConfig, n_slots: int, ctx: int, kv_dtype=None):
    """Decode cache for a continuous-batching slot batch: row b of every
    leaf belongs to slot b, which serves one request at a time and is
    reused (insert overwrites) when that request finishes.  ``kv_dtype``
    overrides ``cfg.kv_cache`` (the decode route's KV precision)."""
    return init_cache(cfg, n_slots, ctx, kv_dtype=kv_dtype)


def _quantize_request(slot_obj, req_obj):
    """Quantize-at-insert: a native (KVCache) prefill cache headed into a
    quantized slot cache is converted here, so mixed-precision plans can
    prefill at full precision and pay the quantization exactly once per
    position on the way into the decode pool."""
    if isinstance(req_obj, attn.KVCache):
        if isinstance(slot_obj, (attn.QuantKVCache, attn.PagedQuantKVCache)):
            kq, ks = attn._q8(req_obj.k)
            vq, vs = attn._q8(req_obj.v)
            return attn.QuantKVCache(k=kq, v=vq, k_scale=ks, v_scale=vs)
        if isinstance(slot_obj, (attn.NF4KVCache, attn.PagedNF4KVCache)):
            kq, ks = attn._qnf4(req_obj.k)
            vq, vs = attn._qnf4(req_obj.v)
            return attn.NF4KVCache(k=kq, v=vq, k_scale=ks, v_scale=vs)
    return req_obj


def insert_cache_slot(cache, request_cache, slot):
    """Write a batch=1 prefill cache into row ``slot`` of a slot cache.

    Group leaves are stacked (repeats, batch, [time,] ...); the request
    leaves (repeats, 1, [t<=ctx,] ...) land at batch index ``slot``,
    time offset 0.  Positions beyond the request's written extent keep
    whatever the previous occupant left there — decode masks by the
    per-slot position, so stale or pad entries are never attended
    (eviction is therefore free: freeing a slot is pure bookkeeping).
    Recurrent-state leaves (RG-LRU/mLSTM/sLSTM) have no time axis; their
    slot row is overwritten wholesale, which is why stale state from a
    previous occupant can never leak into a new request.
    A native-precision request cache headed into a quantized slot cache
    is quantized at insert (``_quantize_request``).
    ``slot`` may be traced (the insert jits once per prefill bucket).
    """
    slot = jnp.asarray(slot, jnp.int32)

    def place(small, big):
        start = (jnp.int32(0), slot) + (jnp.int32(0),) * (big.ndim - 2)
        return jax.lax.dynamic_update_slice(big, small.astype(big.dtype),
                                            start)

    def place_obj(slot_obj, req_obj):
        return jax.tree_util.tree_map(
            place, _quantize_request(slot_obj, req_obj), slot_obj)

    groups = [[{key: place_obj(c[key], rc[key]) for key in c}
               for c, rc in zip(gcs, rgcs)]
              for gcs, rgcs in zip(cache["groups"],
                                   request_cache["groups"])]
    new = dict(cache, groups=groups)
    if "memory" in cache:
        mem = request_cache["memory"].astype(cache["memory"].dtype)
        start = (slot,) + (jnp.int32(0),) * (cache["memory"].ndim - 1)
        new["memory"] = jax.lax.dynamic_update_slice(cache["memory"], mem,
                                                     start)
    return new


PAGEABLE_KINDS = ("attn", "mla")
PAGE_SIZE = 8        # default cache positions per KV pool page


def init_paged_slot_cache(cfg: ArchConfig, n_slots: int, ctx: int, *,
                          page_size: int, n_pages: int, kv_dtype=None):
    """Paged decode cache: pageable mixers (full-context GQA incl.
    int8/NF4, MLA latents) share global page pools with NO batch axis;
    everything position-bounded (rolling-window rings, recurrent state,
    cross-attn K/V, encoder memory) stays slot-indexed dense.  Adds
    ``page_table`` (n_slots, max_pages) int32 with max_pages =
    ceil(ctx / page_size); pool page 0 is the reserved null page, so the
    all-zero table is the safe "no pages owned" state.  ``kv_dtype``
    overrides ``cfg.kv_cache`` (the decode route's KV precision)."""
    dtype = jnp.dtype(cfg.dtype)
    is_encdec = bool(cfg.encoder_groups)
    enc_len = cfg.frontend_len if is_encdec else 0
    max_pages = -(-ctx // page_size)
    groups = []
    for g in cfg.layer_groups:
        per_pos = []
        for kind in g.pattern:
            if kind == "attn":
                one = {"mixer": attn.init_paged_gqa_cache(
                    cfg, n_pages, page_size, dtype, kv_dtype=kv_dtype)}
            elif kind == "mla":
                one = {"mixer": attn.init_paged_mla_cache(
                    cfg, n_pages, page_size, dtype)}
            else:
                one = {"mixer": init_block_cache(
                    cfg, kind, n_slots, ctx, dtype, False, 0)["mixer"]}
            if is_encdec:
                hd = cfg.resolved_head_dim
                one["cross"] = attn.KVCache(
                    k=jnp.zeros((n_slots, enc_len, cfg.n_kv_heads, hd),
                                dtype),
                    v=jnp.zeros((n_slots, enc_len, cfg.n_kv_heads, hd),
                                dtype))
            per_pos.append(jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (g.repeats,) + a.shape), one))
        groups.append(per_pos)
    cache = {"groups": groups,
             "page_table": jnp.zeros((n_slots, max_pages), jnp.int32)}
    if is_encdec:
        cache["memory"] = jnp.zeros((n_slots, enc_len, cfg.d_model), dtype)
    return cache


def insert_paged_cache_slot(cache, request_cache, slot, start):
    """Paged counterpart of ``insert_cache_slot``: scatter a batch=1
    dense prefill cache into the pool pages slot ``slot`` owns.

    The engine must have written the slot's ``page_table`` row BEFORE
    calling this: request positions ``start + [0, T)`` land at pool page
    ``page_table[slot, pos // ps]``, offset ``pos % ps``.  ``start`` is
    the absolute position of the request cache's first entry (the shared
    prefix length under radix sharing, else 0).  Pad-tail positions
    beyond the slot's allocation map to the null page 0 — scratch the
    position mask keeps invisible.  Non-pageable leaves place dense at
    row ``slot`` exactly as the dense insert does.  ``slot``/``start``
    may be traced (jits once per prefill bucket)."""
    slot = jnp.asarray(slot, jnp.int32)
    start = jnp.asarray(start, jnp.int32)
    page_row = cache["page_table"][slot]             # (max_pages,)

    def place(small, big):
        st = (jnp.int32(0), slot) + (jnp.int32(0),) * (big.ndim - 2)
        return jax.lax.dynamic_update_slice(big, small.astype(big.dtype),
                                            st)

    def scatter(pool, req):
        # pool (repeats, P, ps, ...); req (repeats, 1, T, ...)
        ps, t = pool.shape[2], req.shape[2]
        positions = start + jnp.arange(t, dtype=jnp.int32)
        return pool.at[:, page_row[positions // ps], positions % ps].set(
            req[:, 0].astype(pool.dtype))

    def place_obj(slot_obj, req_obj):
        req_obj = _quantize_request(slot_obj, req_obj)
        if isinstance(slot_obj, (attn.PagedQuantKVCache,
                                 attn.PagedNF4KVCache)):
            return type(slot_obj)(
                k=scatter(slot_obj.k, req_obj.k),
                v=scatter(slot_obj.v, req_obj.v),
                k_scale=scatter(slot_obj.k_scale, req_obj.k_scale),
                v_scale=scatter(slot_obj.v_scale, req_obj.v_scale))
        if isinstance(slot_obj, attn.PagedKVCache):
            return attn.PagedKVCache(k=scatter(slot_obj.k, req_obj.k),
                                     v=scatter(slot_obj.v, req_obj.v))
        if isinstance(slot_obj, attn.PagedLatentCache):
            return attn.PagedLatentCache(
                ckv=scatter(slot_obj.ckv, req_obj.ckv),
                krope=scatter(slot_obj.krope, req_obj.krope))
        return jax.tree_util.tree_map(place, req_obj, slot_obj)

    groups = [[{key: place_obj(c[key], rc[key]) for key in c}
               for c, rc in zip(gcs, rgcs)]
              for gcs, rgcs in zip(cache["groups"],
                                   request_cache["groups"])]
    new = dict(cache, groups=groups)
    if "memory" in cache:
        mem = request_cache["memory"].astype(cache["memory"].dtype)
        st = (slot,) + (jnp.int32(0),) * (cache["memory"].ndim - 1)
        new["memory"] = jax.lax.dynamic_update_slice(cache["memory"], mem,
                                                     st)
    return new


def gather_prefix_cache(cache, cfg: ArchConfig, page_row):
    """Gather the pool pages listed in ``page_row`` ((n_hit,) int32)
    into a dense batch=1 prefix cache for continuation prefill.

    Only meaningful for archs whose every mixer is pageable (the
    engine's radix-sharing eligibility check); jits once per n_hit."""
    n_hit = page_row.shape[0]

    def dense(pool):
        # (repeats, P, ps, ...) -> (repeats, 1, n_hit*ps, ...)
        g = pool[:, page_row]
        return g.reshape((pool.shape[0], 1, n_hit * pool.shape[2])
                         + pool.shape[3:])

    def gather(obj):
        if isinstance(obj, attn.PagedKVCache):
            return attn.KVCache(k=dense(obj.k), v=dense(obj.v))
        if isinstance(obj, attn.PagedLatentCache):
            return attn.LatentCache(ckv=dense(obj.ckv),
                                    krope=dense(obj.krope))
        raise TypeError(
            f"prefix sharing requires pageable caches, got {type(obj)}")

    groups = [[{"mixer": gather(c["mixer"])} for c in gcs]
              for gcs in cache["groups"]]
    return {"groups": groups}


def clear_cache_slot(cache, slot):
    """Zero row ``slot`` of every cache leaf (ring positions to -1).
    Functionally unnecessary — insert + position masking already hide
    stale state — but useful for tests and debugging."""
    slot = jnp.asarray(slot, jnp.int32)

    def clear(leaf):
        fill = -1 if leaf.dtype == jnp.int32 else 0
        row = jnp.full(leaf.shape[:1] + (1,) + leaf.shape[2:], fill,
                       leaf.dtype)
        start = (jnp.int32(0), slot) + (jnp.int32(0),) * (leaf.ndim - 2)
        return jax.lax.dynamic_update_slice(leaf, row, start)

    new = dict(cache, groups=jax.tree_util.tree_map(clear, cache["groups"]))
    if "memory" in cache:
        mem = cache["memory"]
        row = jnp.zeros((1,) + mem.shape[1:], mem.dtype)
        start = (slot,) + (jnp.int32(0),) * (mem.ndim - 1)
        new["memory"] = jax.lax.dynamic_update_slice(mem, row, start)
    return new


# ------------------------------------------------------------------ loss

def lm_loss_chunked(head, x: jax.Array, labels: jax.Array,
                    prefix_len: int = 0, chunk: int = 512) -> jax.Array:
    """Cross-entropy without materializing full (B, S, V) logits: scan
    over sequence chunks; each chunk projects through the LM head and
    reduces immediately.  Essential at 256k vocab x 1M tokens."""
    if prefix_len:
        x = x[:, prefix_len:]
    b, s, d = x.shape
    chunk = min(chunk, s)
    if s % chunk:
        chunk = s  # fall back (smoke shapes)
    n = s // chunk
    xc = x.reshape(b, n, chunk, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(b, n, chunk).transpose(1, 0, 2)

    def body(carry, sl):
        nll_sum, cnt = carry
        xs, ls = sl
        logits = apply_lm_head(head, xs).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(ls, 0)[..., None], axis=-1)[..., 0]
        mask = (ls >= 0).astype(jnp.float32)
        return (nll_sum + jnp.sum((logz - gold) * mask),
                cnt + jnp.sum(mask)), None

    (nll, cnt), _ = jax.lax.scan(body, (0.0, 0.0), (xc, lc))
    return nll / jnp.maximum(cnt, 1.0)


def lm_loss(logits: jax.Array, labels: jax.Array,
            prefix_len: int = 0) -> jax.Array:
    """Mean next-token cross-entropy; labels < 0 are masked.  For
    frontend archs the first ``prefix_len`` logit positions are the
    modality prefix and carry no labels."""
    if prefix_len:
        logits = logits[:, prefix_len:]
    lf = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(
        lf, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    nll = (logz - gold) * mask
    return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)
