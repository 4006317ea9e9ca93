"""Jit'd public wrappers around the Pallas kernels.

Handles leading batch dims, M-padding to block multiples, block-size
legalization (blocks must divide the padded operand dims and satisfy the
TPU's lane rule), and the interpret-mode switch: :func:`resolve_interpret`
is the ONE place that decides, when a kernel is traced, whether Pallas
compiles it for the TPU (default backend ``tpu``) or runs it in the
interpreter (any other backend, e.g. ``JAX_PLATFORMS=cpu`` test runs).

All wrappers share one decorator (:func:`_batched_matmul`) for the
flatten/pad/unflatten boilerplate; kernel imports are hoisted to module
scope so dispatch never pays a per-trace import.
"""
from __future__ import annotations

import functools
import inspect
import math

import jax
import jax.numpy as jnp

from repro.core import bitmap as bm
from repro.core.quant import quantize_nf4
from repro.kernels.bitmap_decode import bitmap_decode_pallas
from repro.kernels.bitmap_spmm import bitmap_spmm_pallas
from repro.kernels.contract import kernel_contract
from repro.kernels.fused_lora import fused_lora_pallas
from repro.kernels.grouped_spmm import (decode_dense_spmm_pallas,
                                        decode_nm_spmm_pallas,
                                        decode_qsalr_spmm_pallas,
                                        decode_salr_spmm_pallas,
                                        grouped_dense_spmm_pallas,
                                        grouped_nm_spmm_pallas,
                                        grouped_qsalr_spmm_pallas,
                                        grouped_salr_spmm_pallas)
from repro.kernels.nf4_spmm import QBLOCK, nf4_spmm_pallas
from repro.kernels.nm_spmm import nm_spmm_pallas
from repro.kernels.qsalr_spmm import qsalr_spmm_pallas
from repro.kernels.salr_spmm import salr_spmm_pallas

LANES = 128          # TPU lane width: minor block dims divide by it


def resolve_interpret(interpret: bool | None = None) -> bool:
    """The interpret switch every kernel entry point goes through: an
    explicit bool wins (tests compile for a described TPU with
    ``False``, or force the interpreter with ``True``); ``None`` follows
    the default backend at trace time — compiled on a TPU, interpreted
    anywhere else."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _flatten_pad(x: jax.Array, block_m: int):
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    pad = (-m) % block_m
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    return x2, lead, m


def _unflatten(y: jax.Array, lead, m: int):
    return y[:m].reshape(*lead, y.shape[-1])


def _divisor_block(dim: int, block: int, mult: int = 1) -> int:
    """Largest legal block for an operand axis that lands on the lanes
    (block_k of x and of the tile-major bitmap leaves, block_n of plain
    weights): a divisor of ``dim``, a multiple of ``lcm(128, mult)``,
    and no larger than ``block`` — or, when no such block exists, the
    full ``dim`` (always legal: the TPU accepts a block equal to the
    array dim, and kernels require blocks to divide their operand dims
    exactly)."""
    step = math.lcm(LANES, mult)
    d = min(block, dim) // step * step
    while d >= step:
        if dim % d == 0:
            return d
        d -= step
    return dim


def _batched_matmul(*static_argnames, kind: str = "linear",
                    differentiable: bool = True, serves=(),
                    ragged_rank: bool = False):
    """Decorator unifying the wrappers' boilerplate: jit with the given
    static names, flatten leading batch dims of x, pad M up to the block
    multiple (each body's own ``block_m`` default — 128 for the tiled
    GEMMs, 8 for the decode grid's single M tile), run the kernel body
    on the 2D view, unpad.

    ``kind`` / ``differentiable`` / ``serves`` register the wrapper's
    machine-readable :class:`repro.kernels.contract.KernelContract` —
    the dispatch-closure source of truth the static analyzer
    (``repro.analysis``) checks plan routes, custom-VJP pairing, and
    error budgets against.  ``interpret`` (static, default None) is
    resolved by :func:`resolve_interpret` when the wrapper is traced."""

    def deco(body):
        default_m = inspect.signature(body).parameters["block_m"].default

        def op(x, *args, block_m: int = default_m, interpret=None, **kw):
            x2, lead, m = _flatten_pad(x, block_m)
            y = body(x2, *args, block_m=block_m,
                     interpret=resolve_interpret(interpret), **kw)
            return _unflatten(y, lead, m)
        op.__name__ = body.__name__
        op.__qualname__ = body.__qualname__
        op.__doc__ = body.__doc__
        jitted = jax.jit(op, static_argnames=("block_m",) + static_argnames)
        return kernel_contract(kind=kind, differentiable=differentiable,
                               serves=serves,
                               ragged_rank=ragged_rank)(jitted)
    return deco


def _pad_bcat(b_cat: jax.Array, cols: int) -> jax.Array:
    """Zero-pad B_cat's output dim up to the (tile-padded) encoded width;
    padded columns produce zeros the caller slices off."""
    if b_cat.shape[1] < cols:
        b_cat = jnp.pad(b_cat, ((0, 0), (0, cols - b_cat.shape[1])))
    return b_cat


@_batched_matmul("block_k", "interpret",
                 serves=("linear:bitmap/native",))
def bitmap_matmul(x: jax.Array, tbw: bm.TiledBitmapWeight, *,
                  block_m: int = 128, block_k: int = 128,
                  interpret: bool) -> jax.Array:
    """y = x @ W_hat with the fused bitmap-decode GEMM kernel."""
    bk = _divisor_block(tbw.rows, block_k)
    return bitmap_spmm_pallas(x, tbw.words, tbw.values, cols=tbw.cols,
                              cap_t=tbw.cap_t, block_m=block_m, block_k=bk,
                              interpret=interpret)


@kernel_contract(kind="linear", differentiable=False)
@functools.partial(jax.jit, static_argnames=("interpret",))
def bitmap_decode_t(tbw: bm.TiledBitmapWeight, *,
                    interpret=None) -> jax.Array:
    """W_hat^T ``(cols, rows)`` of a tiled bitmap by the in-kernel decode,
    each weight decoded once: ``bm.tile_decode(tbw).T`` bit for bit.  No
    autodiff rule: callers hand it a frozen base."""
    bk = _divisor_block(tbw.rows, LANES)
    return bitmap_decode_pallas(tbw.words, tbw.values, block_k=bk,
                                interpret=resolve_interpret(interpret))


@_batched_matmul("block_n", "block_k", "interpret",
                 serves=("linear:nm/native",))
def nm_matmul(x: jax.Array, nmw: bm.NMWeight, *,
              block_m: int = 128, block_n: int = 128, block_k: int = 128,
              interpret: bool) -> jax.Array:
    """y = x @ W_hat with the 2:4 decode GEMM kernel."""
    bk = _divisor_block(nmw.rows, block_k)
    bn = _divisor_block(nmw.cols, block_n, mult=nmw.m)
    return nm_spmm_pallas(x, nmw.group_bits, nmw.values, n=nmw.n, m=nmw.m,
                          block_m=block_m, block_n=bn, block_k=bk,
                          interpret=interpret)


@_batched_matmul("block_k", "interpret",
                 serves=("linear:bitmap/native",), ragged_rank=True)
def salr_matmul(x: jax.Array, tbw: bm.TiledBitmapWeight,
                a_cat: jax.Array, b_cat: jax.Array, *,
                block_m: int = 128, block_k: int = 128,
                interpret: bool) -> jax.Array:
    """y = x @ W_hat + (x @ A_cat) @ B_cat — the full SALR op, one kernel."""
    bk = _divisor_block(tbw.rows, block_k)
    return salr_spmm_pallas(x, tbw.words, tbw.values, a_cat,
                            _pad_bcat(b_cat, tbw.cols),
                            cols=tbw.cols, cap_t=tbw.cap_t,
                            block_m=block_m, block_k=bk,
                            interpret=interpret)


@_batched_matmul("block_k", "interpret",
                 serves=("linear:bitmap_nf4/native",
                         "linear:bitmap/nf4",
                         "linear:bitmap/bitmap_nf4"),
                 ragged_rank=True)
def qsalr_matmul(x: jax.Array, qtbw: bm.QTiledBitmapWeight,
                 a_cat: jax.Array, b_cat: jax.Array, *,
                 block_m: int = 128, block_k: int = 128,
                 interpret: bool) -> jax.Array:
    """y = x @ dequant(W_hat) + (x @ A_cat) @ B_cat with NF4 dequant,
    bitmap decode, GEMM, and the concat-adapter path fused in-kernel."""
    bk = _divisor_block(qtbw.rows, block_k)
    if a_cat.shape[1] == 0:
        # degenerate base-only layer: the kernel's low-rank pass needs a
        # nonzero rank; a zero adapter contributes exactly nothing.
        a_cat = jnp.zeros((qtbw.rows, 8), x.dtype)
        b_cat = jnp.zeros((8, qtbw.cols), x.dtype)
    return qsalr_spmm_pallas(x, qtbw.words, qtbw.codes, qtbw.scales,
                             a_cat, _pad_bcat(b_cat, qtbw.cols),
                             cols=qtbw.cols, cap_t=qtbw.cap_t,
                             block_m=block_m, block_k=bk,
                             interpret=interpret)


@_batched_matmul("block_n", "block_k", "interpret",
                 serves=("adapter",), ragged_rank=True)
def lora_matmul(x: jax.Array, a_cat: jax.Array, b_cat: jax.Array, *,
                block_m: int = 128, block_n: int = 128, block_k: int = 128,
                interpret: bool) -> jax.Array:
    """y = (x @ A_cat) @ B_cat with the fused concat-adapter kernel."""
    bk = _divisor_block(a_cat.shape[0], block_k)
    bn = _divisor_block(b_cat.shape[1], block_n)
    return fused_lora_pallas(x, a_cat, b_cat, block_m=block_m, block_n=bn,
                             block_k=bk, interpret=interpret)


# ---------------------------------------------------------------------------
# ragged grouped GEMM (MoE expert dispatch, kernels/grouped_spmm.py)
# ---------------------------------------------------------------------------

def _grouped_adapters(a_cat, b_cat, ncols: int):
    """Normalize the stacked adapter pair for the grouped kernels:
    rank-0 (or absent) adapters become None — the kernels then skip the
    low-rank pass entirely — and B_cat's output dim is zero-padded to
    the encoded width."""
    if a_cat is None or a_cat.shape[-1] == 0:
        return None, None
    if b_cat.shape[-1] < ncols:
        b_cat = jnp.pad(b_cat, ((0, 0), (0, 0),
                                (0, ncols - b_cat.shape[-1])))
    return a_cat, b_cat


@_batched_matmul("block_n", "block_k", "interpret", kind="moe",
                 serves=("moe:grouped/dense/native",
                         "moe:grouped/mask/native"),
                 ragged_rank=True)
def grouped_dense_matmul(x, tile_expert: jax.Array, w: jax.Array,
                         a_cat=None, b_cat=None, *,
                         block_m: int = 128, block_n: int = 128,
                         block_k: int = 128,
                         interpret: bool) -> jax.Array:
    """y[t] = x[t] @ w[e(t)] (+ adapters) over expert-grouped rows.
    w: (E, K, N) dense expert stack; tile_expert: (M/block_m,) int32."""
    e, kdim, ncols = w.shape
    bk = _divisor_block(kdim, block_k)
    bn = _divisor_block(ncols, block_n)
    a3, b3 = _grouped_adapters(a_cat, b_cat, ncols)
    return grouped_dense_spmm_pallas(x, tile_expert, w, a3, b3,
                                     block_m=block_m, block_n=bn,
                                     block_k=bk, interpret=interpret)


@_batched_matmul("block_k", "interpret", kind="moe",
                 serves=("moe:grouped/bitmap/native",), ragged_rank=True)
def grouped_salr_matmul(x, tile_expert: jax.Array,
                        tbw: bm.TiledBitmapWeight, a_cat, b_cat, *,
                        block_m: int = 128, block_k: int = 128,
                        interpret: bool) -> jax.Array:
    """Grouped SALR op over an expert-stacked tiled bitmap (4D leaves:
    words (E, n_tiles, tile/32, K), values (E, n_tiles, cap_t, K))."""
    _, n_tiles, wpt, kdim = tbw.words.shape
    cols = n_tiles * wpt * 32
    bk = _divisor_block(kdim, block_k)
    a3, b3 = _grouped_adapters(a_cat, b_cat, cols)
    return grouped_salr_spmm_pallas(x, tile_expert, tbw.words, tbw.values,
                                    a3, b3, cols=cols, cap_t=tbw.cap_t,
                                    block_m=block_m, block_k=bk,
                                    interpret=interpret)


@_batched_matmul("block_k", "interpret", kind="moe",
                 serves=("moe:grouped/bitmap_nf4/native",
                         "moe:grouped/bitmap/nf4",
                         "moe:grouped/bitmap/bitmap_nf4"),
                 ragged_rank=True)
def grouped_qsalr_matmul(x, tile_expert: jax.Array,
                         qtbw: bm.QTiledBitmapWeight, a_cat, b_cat, *,
                         block_m: int = 128, block_k: int = 128,
                         interpret: bool) -> jax.Array:
    """Grouped QSALR op (NF4 dequant in-kernel) over an expert-stacked
    quantized tiled bitmap."""
    _, n_tiles, wpt, kdim = qtbw.words.shape
    cols = n_tiles * wpt * 32
    bk = _divisor_block(kdim, block_k)
    a3, b3 = _grouped_adapters(a_cat, b_cat, cols)
    return grouped_qsalr_spmm_pallas(x, tile_expert, qtbw.words,
                                     qtbw.codes, qtbw.scales, a3, b3,
                                     cols=cols, cap_t=qtbw.cap_t,
                                     block_m=block_m, block_k=bk,
                                     interpret=interpret)


@_batched_matmul("block_n", "block_k", "interpret", kind="moe",
                 serves=("moe:grouped/nm/native",), ragged_rank=True)
def grouped_nm_matmul(x, tile_expert: jax.Array, nmw: bm.NMWeight,
                      a_cat=None, b_cat=None, *,
                      block_m: int = 128, block_n: int = 128,
                      block_k: int = 128,
                      interpret: bool) -> jax.Array:
    """Grouped N:M op over an expert-stacked NMWeight (group_bits
    (E, K, N/m) uint8, values (E, K, N/m*n))."""
    kdim = nmw.group_bits.shape[1]
    ncols = nmw.group_bits.shape[2] * nmw.m
    bk = _divisor_block(kdim, block_k)
    bn = _divisor_block(ncols, block_n, mult=nmw.m)
    a3, b3 = _grouped_adapters(a_cat, b_cat, ncols)
    return grouped_nm_spmm_pallas(x, tile_expert, nmw.group_bits,
                                  nmw.values, a3, b3, n=nmw.n, m=nmw.m,
                                  block_m=block_m, block_n=bn, block_k=bk,
                                  interpret=interpret)


# ---------------------------------------------------------------------------
# decode-specialized grid (small token counts; kernels/grouped_spmm.py)
# ---------------------------------------------------------------------------
# ``row_expert`` maps every assignment row to its expert (-1 on padding
# rows); the decode grid keeps rows in plain assignment order (no
# grouping).  The decorator pads x's rows to the block_m multiple, and
# _pad_row_expert grows the map to match (-1 rows never match an expert
# step, so pad rows emit exact zeros).

def _pad_row_expert(row_expert: jax.Array, mrows: int) -> jax.Array:
    pad = mrows - row_expert.shape[0]
    assert pad >= 0, (
        f"row_expert has {row_expert.shape[0]} rows but x only {mrows}")
    if pad:
        row_expert = jnp.pad(row_expert, (0, pad), constant_values=-1)
    return row_expert


@_batched_matmul("block_n", "block_k", "interpret", kind="moe",
                 serves=("moe:decode_grid/dense/native",
                         "moe:decode_grid/mask/native"),
                 ragged_rank=True)
def decode_dense_matmul(x, row_expert: jax.Array, w: jax.Array,
                        a_cat=None, b_cat=None, *,
                        block_m: int = 8, block_n: int = 128,
                        block_k: int = 128,
                        interpret: bool) -> jax.Array:
    """Decode-grid y[t] = x[t] @ w[e(t)] (+ adapters) over assignment
    rows.  w: (E, K, N) dense expert stack; row_expert: (M,) int32."""
    e, kdim, ncols = w.shape
    bk = _divisor_block(kdim, block_k)
    bn = _divisor_block(ncols, block_n)
    a3, b3 = _grouped_adapters(a_cat, b_cat, ncols)
    return decode_dense_spmm_pallas(x, _pad_row_expert(row_expert,
                                                       x.shape[0]),
                                    w, a3, b3, block_n=bn, block_k=bk,
                                    interpret=interpret)


@_batched_matmul("block_k", "interpret", kind="moe",
                 serves=("moe:decode_grid/bitmap/native",),
                 ragged_rank=True)
def decode_salr_matmul(x, row_expert: jax.Array,
                       tbw: bm.TiledBitmapWeight, a_cat, b_cat, *,
                       block_m: int = 8, block_k: int = 128,
                       interpret: bool) -> jax.Array:
    """Decode-grid SALR op over an expert-stacked tiled bitmap."""
    _, n_tiles, wpt, kdim = tbw.words.shape
    cols = n_tiles * wpt * 32
    bk = _divisor_block(kdim, block_k)
    a3, b3 = _grouped_adapters(a_cat, b_cat, cols)
    return decode_salr_spmm_pallas(x, _pad_row_expert(row_expert,
                                                      x.shape[0]),
                                   tbw.words, tbw.values,
                                   a3, b3, cols=cols, cap_t=tbw.cap_t,
                                   block_k=bk, interpret=interpret)


@_batched_matmul("block_k", "interpret", kind="moe",
                 serves=("moe:decode_grid/bitmap_nf4/native",
                         "moe:decode_grid/bitmap/nf4",
                         "moe:decode_grid/bitmap/bitmap_nf4"),
                 ragged_rank=True)
def decode_qsalr_matmul(x, row_expert: jax.Array,
                        qtbw: bm.QTiledBitmapWeight, a_cat, b_cat, *,
                        block_m: int = 8, block_k: int = 128,
                        interpret: bool) -> jax.Array:
    """Decode-grid QSALR op (NF4 dequant in-kernel)."""
    _, n_tiles, wpt, kdim = qtbw.words.shape
    cols = n_tiles * wpt * 32
    bk = _divisor_block(kdim, block_k)
    a3, b3 = _grouped_adapters(a_cat, b_cat, cols)
    return decode_qsalr_spmm_pallas(x, _pad_row_expert(row_expert,
                                                       x.shape[0]),
                                    qtbw.words, qtbw.codes,
                                    qtbw.scales, a3, b3, cols=cols,
                                    cap_t=qtbw.cap_t, block_k=bk,
                                    interpret=interpret)


@_batched_matmul("block_n", "block_k", "interpret", kind="moe",
                 serves=("moe:decode_grid/nm/native",), ragged_rank=True)
def decode_nm_matmul(x, row_expert: jax.Array, nmw: bm.NMWeight,
                     a_cat=None, b_cat=None, *,
                     block_m: int = 8, block_n: int = 128,
                     block_k: int = 128,
                     interpret: bool) -> jax.Array:
    """Decode-grid N:M op over an expert-stacked NMWeight."""
    kdim = nmw.group_bits.shape[1]
    ncols = nmw.group_bits.shape[2] * nmw.m
    bk = _divisor_block(kdim, block_k)
    bn = _divisor_block(ncols, block_n, mult=nmw.m)
    a3, b3 = _grouped_adapters(a_cat, b_cat, ncols)
    return decode_nm_spmm_pallas(x, _pad_row_expert(row_expert,
                                                    x.shape[0]),
                                 nmw.group_bits, nmw.values,
                                 a3, b3, n=nmw.n, m=nmw.m, block_n=bn,
                                 block_k=bk, interpret=interpret)


def nf4_encode_2d(w: jax.Array):
    """Quantize a (K, N) weight into the kernel layout:
    codes (K, N/2) uint8 + scales (K, N/QBLOCK) f32.  N % QBLOCK == 0."""
    kdim, n = w.shape
    assert n % QBLOCK == 0
    q = quantize_nf4(w, block=QBLOCK)
    return q.codes.reshape(kdim, n // 2), q.scales.reshape(kdim, n // QBLOCK)


@_batched_matmul("block_n", "block_k", "interpret",
                 serves=("linear:dense/nf4",
                         "linear:dense/bitmap_nf4",
                         "linear:mask/nf4",
                         "linear:mask/bitmap_nf4"))
def nf4_matmul(x: jax.Array, codes: jax.Array, scales: jax.Array, *,
               block_m: int = 128, block_n: int = 128, block_k: int = 128,
               interpret: bool) -> jax.Array:
    """y = x @ dequant(codes, scales) with the NF4 GEMM kernel."""
    bk = _divisor_block(codes.shape[0], block_k)
    # the kernel requires block_n to cover whole scale blocks
    bn = _divisor_block(codes.shape[1] * 2, block_n, mult=QBLOCK)
    return nf4_spmm_pallas(x, codes, scales, block_m=block_m, block_n=bn,
                           block_k=bk, interpret=interpret)
