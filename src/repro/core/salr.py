"""SALRLinear: the paper's contribution as one composable JAX module.

A SALR linear layer is
    y = x @ W_hat  +  (x @ A_cat) @ B_cat  (+ bias)
where W_hat is the statically-pruned frozen base (stored dense, bitmap,
N:M, or NF4-quantized bitmap) and A_cat/B_cat fuse the task LoRA adapter
with the sparsity-preservation residual adapter into a single GEMM pair.

Only ``lora`` and ``res`` fields are trainable (see repro.core.pytree).

Execution plans (DESIGN.md §2): every layer carries a ``backend`` tag
and, when kernel-ready, stores its base in the kernel-native tiled
layout (``TiledBitmapWeight`` / ``QTiledBitmapWeight``, always in the
logical (d_in, d_out) orientation).  ``apply_salr`` dispatches on the
base representation:

    TiledBitmapWeight   -> ops.salr_matmul   (fused decode+GEMM+adapters)
    QTiledBitmapWeight  -> ops.qsalr_matmul  (NF4 dequant in-kernel)
    NMWeight            -> ops.nm_matmul + ops.lora_matmul
    dense / mask / flat -> reference decode + dense GEMM

``backend="reference"`` (per-call, per-layer, or via a plan route —
see ``repro.core.execplan``) always takes the dense decode path;
gradients always do — the kernel forward carries a custom VJP whose
backward is the reference path, so adapters-only fine-tuning works
unchanged on kernel-planned layers.  Phase-aware route selection
(prefill vs decode vs train) lives in ``core/execplan.py``:
``resolve_plan`` is the only reader of ``cfg.salr.backend``, and the
resolved ``PhaseRoute`` is threaded explicitly through the model apply
paths down to the ``backend`` argument here.
"""
from __future__ import annotations

import contextlib
import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import bitmap as bm
from repro.core import prune
from repro.core.adapters import LoRAAdapter, init_lora, pad_rank
from repro.core.quant import (NF4_LEVELS, NF4Tensor, dequantize_nf4,
                              quantize_nf4)
from repro.core.residual import truncated_svd_adapter


@partial(jax.tree_util.register_dataclass,
         data_fields=("words", "qvalues"), meta_fields=("cols", "cap"))
@dataclasses.dataclass(frozen=True)
class QBitmapWeight:
    """Bitmap sparse matrix whose compact values are NF4-quantized (QSALR)."""
    words: jax.Array
    qvalues: NF4Tensor
    cols: int
    cap: int

    @property
    def rows(self) -> int:
        return self.words.shape[0]

    def nbytes(self) -> int:
        return self.words.size * 4 + self.qvalues.nbytes()


@dataclasses.dataclass(frozen=True)
class SALRConfig:
    """Static compression configuration for one family of linear layers."""
    sparsity: float = 0.5
    method: str = "bitmap"        # dense | mask | bitmap | nm | bitmap_nf4
    lora_rank: int = 64
    res_rank: int = 64
    nm: tuple = (2, 4)
    cap_align: int = 128
    dtype: str = "float32"
    backend: str = "kernel"       # kernel | reference (execution plan)
    # dual-representation emission: additionally store a requantized NF4
    # twin of the base (SALRLinear.qbase) sharing the sparse structure
    # and the adapters, so a mixed-precision plan can serve decode from
    # fewer bytes (PhaseRoute.repr) while prefill/train stay native.
    dual_repr: bool = False

    def capacity(self, cols: int) -> int:
        return bm.default_capacity(cols, self.sparsity, self.cap_align)


@partial(jax.tree_util.register_dataclass,
         data_fields=("codes", "scales"), meta_fields=("shape", "block"))
@dataclasses.dataclass(frozen=True)
class QDenseWeight:
    """Dense base NF4-requantized into the kernel 2D layout
    (ops.nf4_matmul): codes (K, Np/2) uint8 + per-block scales
    (K, Np/block) f32, where Np is the logical column count padded up to
    the block multiple (padded columns quantize to exact zeros and are
    sliced off after the GEMM)."""
    codes: jax.Array
    scales: jax.Array
    shape: tuple                   # logical (K, N) (static)
    block: int                     # scale block size (static)

    def nbytes(self) -> int:
        return (self.codes.size
                + self.scales.size * self.scales.dtype.itemsize)


@partial(jax.tree_util.register_dataclass,
         data_fields=("base", "lora", "res", "bias", "qbase"),
         meta_fields=("d_in", "d_out", "transposed", "backend"))
@dataclasses.dataclass(frozen=True)
class SALRLinear:
    """Frozen sparse base + trainable fused adapters.

    ``transposed=True`` means the (flat) base stores W^T so the encoded
    row axis equals the TP-sharded output dimension.  Kernel-native tiled
    bases are ALWAYS stored in the logical (d_in, d_out) orientation
    (the fused kernels contract over storage rows), so ``transposed`` is
    False whenever ``base`` is Tiled/QTiledBitmapWeight — DESIGN.md §3.
    ``backend`` records the layer's default execution path.

    ``qbase`` (optional, frozen like ``base``) is the dual-representation
    twin: the SAME sparse structure with an NF4-requantized payload
    (QTiledBitmapWeight sharing ``base.words``, or QDenseWeight for
    dense bases).  A mixed-precision plan route (``PhaseRoute.repr`` in
    {"nf4", "bitmap_nf4"}) streams it instead of the native base; the
    adapters are shared untouched, so the route's error is exactly the
    requantization error (core/quant.ERROR_BUDGETS).
    """
    base: object                   # Array | BitmapWeight | NMWeight |
    #                                QBitmapWeight | TiledBitmapWeight |
    #                                QTiledBitmapWeight
    lora: LoRAAdapter
    res: Optional[LoRAAdapter]
    bias: Optional[jax.Array]
    d_in: int
    d_out: int
    transposed: bool
    backend: str = "reference"
    qbase: object = None           # QTiledBitmapWeight | QDenseWeight | None


def _is_tiled(base) -> bool:
    return isinstance(base, (bm.TiledBitmapWeight, bm.QTiledBitmapWeight))


_KERNEL_DECODE: list = []           # stack of active kernel_decode scopes


@contextlib.contextmanager
def kernel_decode():
    """Trace-time scope of the train step (``train/step.py``): inside it
    a reference-route decode of a tiled bitmap base may run the Pallas
    decode kernel (see ``_decode_in_kernel``).  Outside it the reference
    route keeps the jnp ``tile_decode``, so the oracles that compare
    against the kernels share no decode code with them."""
    _KERNEL_DECODE.append(True)
    try:
        yield
    finally:
        _KERNEL_DECODE.pop()


def _decode_in_kernel(base) -> bool:
    """Whether a base decodes through the Pallas decode kernel: a tiled
    bitmap inside ``kernel_decode``, on a single TPU chip, where the jnp
    decode's lane gather is the slow part of a train step.  Elsewhere
    the jnp decode stays: on other backends the kernel would run
    interpreted, and across chips a base may be sharded, which a Pallas
    call outside ``shard_map`` cannot be."""
    return (bool(_KERNEL_DECODE)
            and isinstance(base, bm.TiledBitmapWeight)
            and jax.default_backend() == "tpu"
            and jax.device_count() == 1)


def materialize_base(base) -> jax.Array:
    """Dense W_hat from any base representation (reference decode path).

    Tiled bases decode in the logical orientation with zero-padded
    columns up to the tile multiple; callers slice to ``layer.d_out``.
    """
    if _decode_in_kernel(base):
        from repro.kernels import ops  # deferred: kernels import core.bitmap
        # the base is frozen: no tangent may reach the kernel
        return ops.bitmap_decode_t(jax.lax.stop_gradient(base)).T
    if isinstance(base, bm.BitmapWeight):
        return bm.decode(base)
    if isinstance(base, bm.NMWeight):
        return bm.nm_decode(base)
    if isinstance(base, bm.TiledBitmapWeight):
        return bm.tile_decode(base)
    if isinstance(base, bm.QTiledBitmapWeight):
        return bm.qtile_decode(base)
    if isinstance(base, QBitmapWeight):
        vals = dequantize_nf4(base.qvalues)
        return bm.decode(bm.BitmapWeight(words=base.words,
                                         values=vals,
                                         cols=base.cols, cap=base.cap))
    if isinstance(base, QDenseWeight):
        kdim, n = base.shape
        lo = (base.codes & jnp.uint8(0x0F)).astype(jnp.int32)
        hi = (base.codes >> 4).astype(jnp.int32)
        idx = jnp.stack([lo, hi], axis=-1).reshape(kdim, -1)
        levels = jnp.asarray(NF4_LEVELS)
        np_cols = idx.shape[1]
        vals = (levels[idx].reshape(kdim, -1, base.block)
                * base.scales[..., None]).reshape(kdim, np_cols)
        return vals[:, :n]
    return base  # dense / masked-dense array


def adapter_cat(layer: SALRLinear) -> tuple[jax.Array, jax.Array]:
    """A_cat/B_cat fusing the LoRA and residual adapters (paper §Concat)."""
    if layer.res is None:
        return layer.lora.a, layer.lora.b * layer.lora.scale
    a_cat = jnp.concatenate([layer.lora.a, layer.res.a], axis=1)
    b_cat = jnp.concatenate([layer.lora.b * layer.lora.scale,
                             layer.res.b * layer.res.scale], axis=0)
    return a_cat, b_cat


# ---------------------------------------------------------------------------
# backend dispatch
# ---------------------------------------------------------------------------

def force_backend(backend: str):
    """Scoped backend override consulted (at TRACE time) by ``apply_salr``
    and ``apply_moe`` calls that were not handed an explicit route.

    This is compatibility sugar over the execution-plan subsystem: the
    scope maps to a phase-uniform plan override pushed on the
    ``core.execplan`` stack (``plan_scope(uniform_plan(backend))``), which the
    resolvers consult AFTER any explicitly threaded plan route — resolve
    a plan and thread it instead for phase-aware dispatch."""
    from repro.core import execplan as plan_mod
    return plan_mod.plan_scope(plan_mod.uniform_plan(backend))


def _resolve_backend(layer: SALRLinear, backend: Optional[str]) -> str:
    b = backend
    if b is None:
        from repro.core import execplan as plan_mod
        override = plan_mod.current_override()
        if override is not None:
            # a DIRECT apply_salr call carries no phase context, so a
            # scope plan resolves as prefill here (force_backend pushes
            # phase-uniform plans, where this is immaterial; model entry
            # points resolve their own phase from the scope instead)
            b = override.linear_backend("prefill")
    if b is None:
        b = layer.backend
    if b not in ("kernel", "reference"):
        raise ValueError(f"unknown SALR backend {b!r}")
    return b


def _apply_reference(x: jax.Array, layer: SALRLinear,
                     precision=None, constrain_fn=None,
                     base=None) -> jax.Array:
    """Dense decode + GEMM (the differentiable oracle path).  ``base``
    (optional) substitutes another representation of the frozen base —
    the quantized-repr oracle dequantizes ``layer.qbase`` here."""
    if base is None:
        base = layer.base
    with jax.named_scope("salr_base_decode"):
        w = materialize_base(base)
        if _is_tiled(base) or isinstance(base, QDenseWeight):
            w = w[:, :layer.d_out]        # drop tile/block zero-padding
        if w.dtype != x.dtype:
            w = w.astype(x.dtype)
        if constrain_fn is not None and not _is_tiled(base) \
                and not isinstance(base, QDenseWeight):
            # the storage-rows sharding convention only applies to flat bases
            w = constrain_fn(w)
    with jax.named_scope("salr_base"):
        if layer.transposed:
            y = jax.lax.dot_general(
                x, w, (((x.ndim - 1,), (1,)), ((), ())), precision=precision)
        else:
            y = jax.lax.dot_general(
                x, w, (((x.ndim - 1,), (0,)), ((), ())), precision=precision)
    with jax.named_scope("salr_adapter"):
        a_cat, b_cat = adapter_cat(layer)
        y = y + (x @ a_cat) @ b_cat
    if layer.bias is not None:
        y = y + layer.bias
    return y


def _kernel_capable(layer: SALRLinear) -> bool:
    """Whether a fused Pallas op exists for this base layout.  Dense /
    mask / flat (unplanned) storage has none: the reference GEMM is that
    representation's execution plan — see plan() to convert."""
    return (_is_tiled(layer.base)
            or (isinstance(layer.base, bm.NMWeight) and not layer.transposed))


def _kernel_dispatch(x: jax.Array, layer: SALRLinear) -> jax.Array:
    """Route the forward to the fused Pallas op for this base layout."""
    from repro.kernels import ops  # deferred: kernels import core.bitmap
    base = layer.base
    a_cat, b_cat = adapter_cat(layer)
    if isinstance(base, bm.TiledBitmapWeight):
        if a_cat.shape[1] == 0:
            y = ops.bitmap_matmul(x, base)[..., :layer.d_out]
        else:
            y = ops.salr_matmul(x, base, a_cat, b_cat)[..., :layer.d_out]
    elif isinstance(base, bm.QTiledBitmapWeight):
        y = ops.qsalr_matmul(x, base, a_cat, b_cat)[..., :layer.d_out]
    elif isinstance(base, bm.NMWeight) and not layer.transposed:
        y = ops.nm_matmul(x, base)
        if a_cat.shape[1]:
            y = y + ops.lora_matmul(x, a_cat, b_cat)
    else:
        raise TypeError(f"no fused kernel for base {type(base).__name__} "
                        f"(transposed={layer.transposed})")
    if layer.bias is not None:
        y = y + layer.bias
    return y


@jax.custom_vjp
def _kernel_forward(x: jax.Array, layer: SALRLinear) -> jax.Array:
    return _kernel_dispatch(x, layer)


def _kernel_forward_fwd(x, layer):
    return _kernel_dispatch(x, layer), (x, layer)


def _kernel_forward_bwd(res, g):
    # Pallas kernels carry no AD rules; the backward pass runs the exact
    # reference formulation (ISSUE: reference path for grads, kernel path
    # keeps the frozen base un-differentiated).
    x, layer = res
    _, vjp = jax.vjp(lambda xx, ll: _apply_reference(xx, ll), x, layer)
    return vjp(g)


_kernel_forward.defvjp(_kernel_forward_fwd, _kernel_forward_bwd)


def _qkernel_dispatch(x: jax.Array, layer: SALRLinear) -> jax.Array:
    """Fused op over the dual-representation twin (layer.qbase): the
    base product streams the requantized payload, the adapters/bias are
    the SAME as the native path."""
    from repro.kernels import ops  # deferred: kernels import core.bitmap
    qb = layer.qbase
    a_cat, b_cat = adapter_cat(layer)
    if isinstance(qb, bm.QTiledBitmapWeight):
        y = ops.qsalr_matmul(x, qb, a_cat, b_cat)[..., :layer.d_out]
    elif isinstance(qb, QDenseWeight):
        y = ops.nf4_matmul(x, qb.codes, qb.scales)[..., :layer.d_out]
        if a_cat.shape[1]:
            y = y + ops.lora_matmul(x, a_cat, b_cat)
    else:
        raise TypeError(f"no fused kernel for qbase {type(qb).__name__}")
    if layer.bias is not None:
        y = y + layer.bias
    return y


@jax.custom_vjp
def _qkernel_forward(x: jax.Array, layer: SALRLinear) -> jax.Array:
    return _qkernel_dispatch(x, layer)


def _qkernel_forward_fwd(x, layer):
    return _qkernel_dispatch(x, layer), (x, layer)


def _qkernel_forward_bwd(res, g):
    # backward replays the reference formulation over the dequantized
    # twin (quantized routes are serving routes; grads here only matter
    # for trace-through completeness and match what was computed)
    x, layer = res
    _, vjp = jax.vjp(
        lambda xx, ll: _apply_reference(xx, ll, base=ll.qbase), x, layer)
    return vjp(g)


_qkernel_forward.defvjp(_qkernel_forward_fwd, _qkernel_forward_bwd)


def _resolve_repr(base_repr: Optional[str]) -> str:
    if base_repr is None:
        from repro.core import execplan as plan_mod
        override = plan_mod.current_override()
        if override is not None:
            # same phase convention as _resolve_backend: a direct
            # phase-less call reads the scope plan's prefill route
            base_repr = override.base_repr("prefill")
    return base_repr or "native"


def apply_salr(x: jax.Array, layer: SALRLinear,
               precision=None, constrain_fn=None,
               backend: Optional[str] = None,
               base_repr: Optional[str] = None) -> jax.Array:
    """y = x @ W_hat + (x @ A_cat) @ B_cat (+ bias).  x: (..., d_in).

    ``backend`` selects the execution path (explicit arg — usually the
    threaded plan route's ``linear`` — then any active plan-scope
    override, then ``layer.backend``): ``"kernel"`` routes to the fused
    Pallas op for the layer's base representation, ``"reference"``
    decodes dense and runs plain GEMMs.

    ``base_repr`` selects the base REPRESENTATION (the threaded plan
    route's ``repr``, then any plan-scope override, then ``"native"``):
    a quantized repr ("nf4"/"bitmap_nf4") streams the layer's
    dual-representation twin (``layer.qbase``) — through the in-kernel
    NF4 ops under the kernel backend, or dequantized under the reference
    backend (the budgeted-error oracle).  Layers without a ``qbase``
    fall back to the native base, the usual capability rule.

    ``constrain_fn`` (optional) pins the decoded dense W_hat (rows, cols)
    to the storage-row sharding under pjit (repro.distributed.sharding);
    it applies to flat-storage reference decodes only — tiled plans keep
    the sparse representation live and never materialize W_hat.  Bases
    without a fused kernel (dense / mask / unplanned flat) always take
    the reference path with the caller's precision/constrain semantics
    intact, whatever the requested backend."""
    b = _resolve_backend(layer, backend)
    r = _resolve_repr(base_repr)
    if r != "native" and layer.qbase is not None:
        if b == "kernel":
            return _qkernel_forward(x, layer)
        return _apply_reference(x, layer, precision, constrain_fn,
                                base=layer.qbase)
    if b == "kernel" and _kernel_capable(layer):
        return _kernel_forward(x, layer)
    return _apply_reference(x, layer, precision, constrain_fn)


def delta_w(layer: SALRLinear) -> jax.Array:
    """Effective dense update contributed by the fused adapters."""
    a_cat, b_cat = adapter_cat(layer)
    return a_cat @ b_cat


def effective_weight(layer: SALRLinear) -> jax.Array:
    """Dense W_hat + A_cat B_cat (for analysis only; defeats compression)."""
    w = materialize_base(layer.base)
    if _is_tiled(layer.base):
        w = w[:, :layer.d_out]
    if layer.transposed:
        w = w.T
    return w + delta_w(layer)


# ---------------------------------------------------------------------------
# compression entry point
# ---------------------------------------------------------------------------

def compress_linear(key: jax.Array, w: jax.Array, cfg: SALRConfig,
                    bias: Optional[jax.Array] = None,
                    transposed: bool = False, *,
                    mask: Optional[jax.Array] = None,
                    cap_t: Optional[int] = None,
                    pad_rank_to: Optional[int] = None) -> SALRLinear:
    """Compress a dense weight W (d_in, d_out) into a SALRLinear.

    Pipeline (paper Fig. 2a): magnitude-prune -> encode base (bitmap/NM/
    NF4) -> truncated-SVD the total residual (pruned entries + capacity
    spill [+ quantization error]) into the trainable ``res`` adapter ->
    fresh LoRA adapter.

    The keyword-only overrides are the budget allocator's hooks
    (core/allocate.py), all defaulting to today's behavior:

    - ``mask``: pruning mask in the LOGICAL (d_in, d_out) orientation
      (e.g. one slice of ``prune.global_masks``), replacing the
      per-matrix magnitude mask for the maskable methods (mask /
      bitmap / bitmap_nf4; N:M masks are structural and dense takes
      none).  Capacity spill past static capacities folds into the
      residual adapter exactly, as always.
    - ``cap_t``: tiled-capacity override so every member of a scan
      stack encodes with the stack's (maximum) capacity and stacked
      leaves stay shape-uniform.
    - ``pad_rank_to``: physical residual-adapter rank; the trainable
      rank-``cfg.res_rank`` adapter is zero-padded to this width
      (``adapters.pad_rank`` — exact in the GEMM, gradient-frozen).
      Layers allocated rank 0 inside a rank>0 stack store an all-zero
      adapter of this width.

    With ``cfg.backend == "kernel"`` the bitmap-family bases are emitted
    directly in the kernel-native tiled layout (logical orientation, so
    the resulting layer reports ``transposed=False``); transposed N:M
    storage — whose kernel contracts over logical rows — is converted to
    a tiled bitmap as well.  With ``cfg.backend == "reference"``, or if
    ``transposed`` flat storage is requested, the historical flat layout
    is kept: storage is W^T so the encoded row axis equals the sharded
    output dimension (DESIGN.md §3 sharding-aware encoding).  Fully
    traceable (runs under the model-init vmaps).
    """
    d_in, d_out = w.shape
    store = w.T if transposed else w
    # override masks arrive in the logical orientation; flat store-
    # orientation paths encode the transposed view
    store_mask = None if mask is None else (mask.T if transposed else mask)
    dtype = jnp.dtype(cfg.dtype)
    kernel_ready = cfg.backend == "kernel"
    res_ad = None
    out_transposed = transposed

    if cfg.method == "dense":
        base = store.astype(dtype)
    elif cfg.method == "mask":
        m_ = (store_mask if store_mask is not None
              else prune.magnitude_mask(store, cfg.sparsity))
        base = prune.apply_mask(store, m_).astype(dtype)
        e = prune.residual(store, m_)
        res_ad = _res_adapter(e, cfg, transposed, dtype)
    elif cfg.method == "bitmap":
        if kernel_ready:
            base, e = _tiled_bitmap_base(w, cfg, dtype, mask=mask,
                                         cap_t=cap_t)
            res_ad = _res_adapter(e, cfg, False, dtype)
            out_transposed = False
        else:
            bw, e = bm.encode_from_dense(store.astype(dtype), cfg.sparsity,
                                         cap=cfg.capacity(store.shape[1]),
                                         mask=store_mask)
            base = bw
            res_ad = _res_adapter(e, cfg, transposed, dtype)
    elif cfg.method == "nm":
        n, m = cfg.nm
        if kernel_ready and transposed:
            base, e = _tiled_nm_base(w, cfg, dtype)
            res_ad = _res_adapter(e, cfg, False, dtype)
            out_transposed = False
        else:
            nmw, e = bm.nm_encode(store.astype(dtype), n=n, m=m)
            base = nmw
            res_ad = _res_adapter(e, cfg, transposed, dtype)
    elif cfg.method == "bitmap_nf4":
        if kernel_ready:
            tbw, e = _tiled_encode(w.astype(jnp.float32), cfg, mask=mask,
                                   cap_t=cap_t)
            q, qerr = bm.tile_quantize_nf4(tbw)
            e = e + qerr[:, :d_out]
            base = q
            res_ad = _res_adapter(e, cfg, False, dtype)
            out_transposed = False
        else:
            bw, e = bm.encode_from_dense(store.astype(jnp.float32),
                                         cfg.sparsity,
                                         cap=cfg.capacity(store.shape[1]),
                                         mask=store_mask)
            q = quantize_nf4(bw.values)
            # quantization error of kept values joins the residual too
            qerr_vals = bw.values - dequantize_nf4(q)
            e = e + bm.decode(bm.BitmapWeight(words=bw.words,
                                              values=qerr_vals,
                                              cols=bw.cols, cap=bw.cap))
            base = QBitmapWeight(words=bw.words, qvalues=q,
                                 cols=bw.cols, cap=bw.cap)
            res_ad = _res_adapter(e, cfg, transposed, dtype)
    else:
        raise ValueError(f"unknown SALR method {cfg.method!r}")

    if pad_rank_to is not None and pad_rank_to > 0:
        if res_ad is None:
            res_ad = LoRAAdapter(a=jnp.zeros((d_in, pad_rank_to), dtype),
                                 b=jnp.zeros((pad_rank_to, d_out), dtype),
                                 scale=1.0)
        else:
            res_ad = pad_rank(res_ad, pad_rank_to)

    lora = init_lora(key, d_in, d_out, cfg.lora_rank, dtype=dtype)
    layer = SALRLinear(base=base, lora=lora, res=res_ad,
                       bias=None if bias is None else bias.astype(dtype),
                       d_in=d_in, d_out=d_out, transposed=out_transposed,
                       backend=cfg.backend)
    if cfg.dual_repr:
        layer = dataclasses.replace(layer, qbase=attach_qbase(layer))
    return layer


def attach_qbase(layer: SALRLinear):
    """Dual-representation twin of ``layer.base`` for mixed-precision
    plan routes: the SAME sparse structure, NF4-requantized payload.

    TiledBitmapWeight bases requantize per tile cell (QTiledBitmapWeight
    aliasing ``base.words``); non-transposed dense/mask bases requantize
    into the ``ops.nf4_matmul`` 2D layout (QDenseWeight, columns padded
    to the QBLOCK multiple — padded zeros quantize exactly).  Bases that
    are already quantized (QTiledBitmapWeight, QBitmapWeight) or have no
    fused quantized op (NM, transposed flat) return None: the route
    falls back to the native base, the usual capability rule.  The
    requantization error is NOT folded into the residual adapter — the
    adapters are shared with the native base, so the quantized route's
    error is exactly the NF4 roundtrip (core/quant.ERROR_BUDGETS).
    Traceable (pure jnp)."""
    base = layer.base
    if isinstance(base, bm.TiledBitmapWeight):
        return bm.tile_quantize_nf4(base)[0]
    if isinstance(base, jax.Array) and base.ndim == 2 \
            and not layer.transposed:
        from repro.kernels import ops  # deferred: kernels import core.bitmap
        from repro.kernels.nf4_spmm import QBLOCK
        kdim, n = base.shape
        pad = (-n) % QBLOCK
        w = jnp.pad(base.astype(jnp.float32), ((0, 0), (0, pad)))
        codes, scales = ops.nf4_encode_2d(w)
        return QDenseWeight(codes=codes, scales=scales,
                            shape=(kdim, n), block=QBLOCK)
    return None


def _tiled_encode(w: jax.Array, cfg: SALRConfig,
                  mask: Optional[jax.Array] = None,
                  cap_t: Optional[int] = None):
    """Tile-encode a logical (d_in, d_out) weight with static capacity
    (traceable).  Returns (TiledBitmapWeight, residual incl. spill)."""
    d_in, d_out = w.shape
    tile = bm.default_tile(d_out)
    if mask is None:
        mask = prune.magnitude_mask(w, cfg.sparsity)
    if cap_t is None:
        cap_t = bm.tiled_capacity(tile, cfg.sparsity)
    w_hat = prune.apply_mask(w, mask)
    pad = bm.round_up(d_out, tile) - d_out
    if pad:
        w_hat = jnp.pad(w_hat, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    tbw, spill = bm.tile_encode(w_hat, mask, tile, cap_t)
    e = prune.residual(w, mask[:, :d_out] if pad else mask)
    return tbw, e + spill[:, :d_out]


def _tiled_bitmap_base(w: jax.Array, cfg: SALRConfig, dtype,
                       mask: Optional[jax.Array] = None,
                       cap_t: Optional[int] = None):
    return _tiled_encode(w.astype(dtype), cfg, mask=mask, cap_t=cap_t)


def _tiled_nm_base(w: jax.Array, cfg: SALRConfig, dtype):
    """Transposed N:M storage, kernel-ready: the N:M mask is computed in
    the storage orientation (groups along d_in, the sharding/encoding
    convention), then the masked weight is re-encoded as a logical tiled
    bitmap the fused kernel can contract over."""
    n, m = cfg.nm
    mask_store = prune.nm_mask(w.astype(dtype).T, n=n, m=m)
    cap_t = bm.tiled_capacity(bm.default_tile(w.shape[1]), 1.0 - n / m)
    return _tiled_encode(w.astype(dtype), cfg, mask=mask_store.T,
                         cap_t=cap_t)


def _res_adapter(e_store: jax.Array, cfg: SALRConfig, transposed: bool,
                 dtype) -> Optional[LoRAAdapter]:
    if cfg.res_rank <= 0:
        return None
    e = e_store.T if transposed else e_store   # back to (d_in, d_out)
    return truncated_svd_adapter(e, cfg.res_rank, dtype=dtype)


# ---------------------------------------------------------------------------
# execution-plan conversion for existing layers
# ---------------------------------------------------------------------------

def plan(layer: SALRLinear, mode: str = "kernel") -> SALRLinear:
    """Convert a layer's base storage to the given execution plan.

    ``mode="kernel"`` re-encodes flat bitmap-family bases into the
    kernel-native tiled layout (exact — decode is unchanged; flat
    NF4-quantized values are dequantized and re-quantized per tile cell,
    a one-time drift comparable to the original quantization error).
    ``mode="reference"`` converts tiled bases back to flat row encodings.
    Dense / mask bases are untouched by either mode.

    Runs on concrete arrays (it sizes capacities from the actual
    populations); call it outside jit — ``compress_linear`` already
    emits kernel-ready storage when ``cfg.backend == "kernel"``.
    """
    if mode not in ("kernel", "reference"):
        raise ValueError(f"unknown plan mode {mode!r}")
    base, transposed = layer.base, layer.transposed

    if mode == "kernel":
        if isinstance(base, bm.BitmapWeight):
            base = bm.to_tiled(base, transpose=transposed)
            transposed = False
        elif isinstance(base, QBitmapWeight):
            flat = bm.BitmapWeight(words=base.words,
                                   values=dequantize_nf4(base.qvalues),
                                   cols=base.cols, cap=base.cap)
            tbw = bm.to_tiled(flat, transpose=transposed)
            base, _ = bm.tile_quantize_nf4(tbw)
            transposed = False
        elif isinstance(base, bm.NMWeight) and transposed:
            dense = bm.nm_decode(base).T            # logical (d_in, d_out)
            flat, _ = bm.encode_from_dense(dense, 0.0,
                                           mask=dense != 0,
                                           cap=dense.shape[1])
            base = bm.to_tiled(flat)
            transposed = False
    else:  # reference
        if isinstance(base, bm.QTiledBitmapWeight):
            base = bm.tile_dequantize_nf4(base)
        if isinstance(base, bm.TiledBitmapWeight):
            base = bm.from_tiled(base, cols=layer.d_out)
            transposed = False

    return dataclasses.replace(layer, base=base, transposed=transposed,
                               backend=mode)


def base_nbytes(layer: SALRLinear, base_repr: str = "native") -> int:
    """Bytes STREAMED for the base product under ``base_repr`` — a
    quantized repr with an emitted twin reads ``qbase``'s bytes, which
    is what the decode roofline should charge."""
    base = layer.base
    if base_repr != "native" and layer.qbase is not None:
        base = layer.qbase
    if hasattr(base, "nbytes") and callable(base.nbytes):
        return base.nbytes()
    return base.size * base.dtype.itemsize


def layer_nbytes(layer: SALRLinear) -> int:
    n = base_nbytes(layer)
    for ad in (layer.lora, layer.res):
        if ad is not None:
            n += ad.a.size * ad.a.dtype.itemsize + ad.b.size * ad.b.dtype.itemsize
    if layer.bias is not None:
        n += layer.bias.size * layer.bias.dtype.itemsize
    return n
