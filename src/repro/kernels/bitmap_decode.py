"""In-kernel decode of the tile-major tiled bitmap (core.bitmap).

Every bitmap-family GEMM kernel (salr_spmm, bitmap_spmm, qsalr_spmm and
the grouped/decode-grid MoE bodies) decodes its weight block here, in
the TRANSPOSED orientation the storage layout gives it: a block holds
``words (tile//32, bk)`` and a ``(cap_t, bk)`` value segment, i.e. one
column-tile cell per lane (matrix row).  The cell's compaction order
runs down the sublanes, so the decode needs only ops the TPU compiler
lowers (no cumsum, no lane gather, no lane-merging reshape):

  1. unpack: ``(words >> b) & 1`` broadcast over a 32-deep sublane axis,
     collapsed into the ``tile`` sublanes of ``bits (tile, bk)``;
  2. slots: the exclusive prefix popcount of each lane, as one MXU
     matmul of a strictly-lower-triangular ones matrix with ``bits``
     (bf16 0/1 operands, f32 accumulation: exact integer counts);
  3. gather: a select chain over the value rows, loaded 8 sublanes at a
     time — row ``s`` lands wherever a lane's slot equals ``s``.

The result is W^T for the block, contracted against x with
:func:`dot_xwt`.  Decoded values are exact copies of the stored ones,
so the tile equals ``core.bitmap.tile_decode`` bit for bit.

:func:`bitmap_decode_pallas` runs the same decode alone, writing the
whole W^T once: the train step's reference route contracts it with
XLA's own GEMM, where the GEMM kernels would redecode each weight block
once per M block.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.nf4_common import dequant_nf4_split

_CHUNK = 8          # value rows per aligned load (one f32 sublane tile)


def _select_rows(acc, slots, rows_ref, first_slot: int):
    """acc[j, k] = rows_ref[s - first_slot, k] where slots[j, k] == s."""
    n = rows_ref.shape[0]

    def chunk(start, size, acc):
        rows = rows_ref[pl.ds(start, size), :].astype(jnp.float32)
        for i in range(size):
            acc = jnp.where(slots == first_slot + start + i,
                            rows[i:i + 1, :], acc)
        return acc

    n_full = n // _CHUNK
    acc = jax.lax.fori_loop(
        0, n_full,
        lambda c, a: chunk(pl.multiple_of(c * _CHUNK, _CHUNK), _CHUNK, a),
        acc)
    if n % _CHUNK:
        acc = chunk(n_full * _CHUNK, n % _CHUNK, acc)
    return acc


def decode_bitmap_t(words, segments, dtype):
    """Decode one block to W^T ``(tile, bk)``.

    ``words``: ``(tile//32, bk)`` uint32 bit words of the block's cells;
    ``segments``: ``((ref, first_slot), ...)`` value refs of shape
    ``(n, bk)`` holding slots ``[first_slot, first_slot + n)`` (one ref
    for plain values; the NF4 path passes its two decoded halves)."""
    wpt, bk = words.shape
    tile = wpt * 32
    w = jax.lax.bitcast_convert_type(words, jnp.int32)
    shift = jax.lax.broadcasted_iota(jnp.int32, (wpt, 32, bk), 1)
    bits = ((w[:, None, :] >> shift) & 1).reshape(tile, bk)
    r = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1)
    lower = (c < r).astype(jnp.bfloat16)
    prefix = jax.lax.dot_general(
        lower, bits.astype(jnp.float32).astype(jnp.bfloat16),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    slots = jnp.where(bits != 0, prefix.astype(jnp.int32), -1)
    acc = jnp.zeros((tile, bk), jnp.float32)
    for ref, first in segments:
        acc = _select_rows(acc, slots, ref, first)
    return acc.astype(dtype)


def _decode_kernel(words_ref, values_ref, o_ref):
    o_ref[...] = decode_bitmap_t(words_ref[...], ((values_ref, 0),),
                                 o_ref.dtype)


def bitmap_decode_pallas(words: jax.Array, values: jax.Array, *,
                         block_k: int, interpret: bool) -> jax.Array:
    """W^T ``(n_tiles * tile, K)`` in the values' dtype, each weight
    decoded once, from tile-major ``words (n_tiles, tile//32, K)`` and
    ``values (n_tiles, cap_t, K)``."""
    n_tiles, wpt, kdim = words.shape
    tile = wpt * 32
    cap_t = values.shape[1]
    assert values.shape == (n_tiles, cap_t, kdim) and kdim % block_k == 0
    return pl.pallas_call(
        _decode_kernel,
        grid=(n_tiles, kdim // block_k),
        in_specs=[
            pl.BlockSpec((None, wpt, block_k), lambda ni, ki: (ni, 0, ki)),
            pl.BlockSpec((None, cap_t, block_k), lambda ni, ki: (ni, 0, ki)),
        ],
        out_specs=pl.BlockSpec((tile, block_k), lambda ni, ki: (ni, ki)),
        out_shape=jax.ShapeDtypeStruct((n_tiles * tile, kdim), values.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(words, values)


def nf4_halves_scratch(half: int, block_k: int) -> tuple:
    """VMEM for the two dequantized NF4 segment halves of
    :func:`decode_nf4_bitmap_t`."""
    return (pltpu.VMEM((half, block_k), jnp.float32),
            pltpu.VMEM((half, block_k), jnp.float32))


def decode_nf4_bitmap_t(words_ref, codes_ref, scales_ref, lo_ref, hi_ref,
                        dtype):
    """NF4 variant of :func:`decode_bitmap_t`: dequantize the block's
    split-packed segment (``codes (cap_t//2, bk)``, ``scales (1, bk)``)
    into the two VMEM halves, then decode to W^T."""
    lo_ref[...], hi_ref[...] = dequant_nf4_split(codes_ref[...],
                                                 scales_ref[...])
    half = lo_ref.shape[0]
    return decode_bitmap_t(words_ref[...], ((lo_ref, 0), (hi_ref, half)),
                           dtype)


def dot_xwt(x, wt):
    """x (Bm, Bk) @ W^T-tile (tile, Bk)^T -> (Bm, tile), f32 accumulate."""
    return jax.lax.dot_general(x, wt, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
