"""Tests for prune methods, adapters (concat fusion), residual SVD,
NF4 quantization and the composed SALRLinear module."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core import adapters as ad
from repro.core import prune, residual
from repro.core.pytree import combine, split_trainable
from repro.core.quant import dequantize_nf4, quantize_nf4
from repro.core.salr import (SALRConfig, apply_salr, compress_linear,
                             effective_weight, force_backend, kernel_decode,
                             layer_nbytes)


# ------------------------------------------------------------------ prune

def test_magnitude_mask_exact_count():
    w = jax.random.normal(jax.random.PRNGKey(0), (32, 32))
    for p in (0.0, 0.25, 0.5, 0.9, 1.0):
        m = prune.magnitude_mask(w, p)
        assert int(jnp.sum(~m)) == round(p * w.size)


def test_magnitude_mask_keeps_largest():
    w = jnp.array([[1.0, -5.0, 0.1, 3.0]])
    m = prune.magnitude_mask(w, 0.5)
    np.testing.assert_array_equal(np.asarray(m), [[False, True, False, True]])


def test_global_masks_share_threshold():
    k = jax.random.PRNGKey(1)
    w1 = jax.random.normal(k, (16, 16)) * 10.0   # big magnitudes
    w2 = jax.random.normal(k, (16, 16)) * 0.01   # tiny magnitudes
    m1, m2 = prune.global_masks([w1, w2], 0.5)
    # global threshold prunes nearly all of w2, keeps nearly all of w1
    assert float(prune.sparsity(m2)) > 0.9
    assert float(prune.sparsity(m1)) < 0.1


# ---------------------------------------------------------------- adapters

@settings(max_examples=15, deadline=None)
@given(n_adapters=st.integers(1, 4), r=st.integers(1, 8),
       seed=st.integers(0, 10_000))
def test_concat_equals_sequential(n_adapters, r, seed):
    key = jax.random.PRNGKey(seed)
    keys = jax.random.split(key, 2 * n_adapters + 1)
    d_in, d_out = 24, 20
    adapters = []
    for i in range(n_adapters):
        a = jax.random.normal(keys[2 * i], (d_in, r))
        b = jax.random.normal(keys[2 * i + 1], (r, d_out))
        adapters.append(ad.LoRAAdapter(a=a, b=b, scale=0.5 + 0.25 * i))
    x = jax.random.normal(keys[-1], (7, d_in))
    seq = ad.apply_adapters_sequential(x, adapters)
    fused = ad.apply_adapters_fused(x, adapters)
    np.testing.assert_allclose(np.asarray(seq), np.asarray(fused),
                               rtol=1e-5, atol=1e-5)


def test_lora_init_zero_update():
    lora = ad.init_lora(jax.random.PRNGKey(0), 16, 8, rank=4)
    x = jnp.ones((3, 16))
    np.testing.assert_allclose(np.asarray(ad.apply_adapter(x, lora)), 0.0)


# ---------------------------------------------------------------- residual

def test_truncated_svd_adapter_is_best_rank_r():
    e = jax.random.normal(jax.random.PRNGKey(2), (40, 30))
    r = 5
    adp = residual.truncated_svd_adapter(e, r)
    err = float(residual.approximation_error(e, adp))
    s = jnp.linalg.svd(e, compute_uv=False)
    eckart_young = float(jnp.sum(s[r:] ** 2) / e.size)
    assert err == pytest.approx(eckart_young, rel=1e-4)


def test_svd_adapter_rank_padding():
    e = jax.random.normal(jax.random.PRNGKey(3), (6, 4))
    adp = residual.truncated_svd_adapter(e, rank=10)  # > min(d,k)
    assert adp.a.shape == (6, 10) and adp.b.shape == (10, 4)
    # still reconstructs E exactly (full rank captured)
    np.testing.assert_allclose(np.asarray(adp.delta_w()), np.asarray(e),
                               rtol=1e-4, atol=1e-5)


# -------------------------------------------------------------------- nf4

def test_nf4_roundtrip_error_small():
    x = jax.random.normal(jax.random.PRNGKey(4), (128, 64))
    q = quantize_nf4(x, block=64)
    xq = dequantize_nf4(q)
    assert xq.shape == x.shape
    rel = float(jnp.linalg.norm(x - xq) / jnp.linalg.norm(x))
    assert rel < 0.12  # 4-bit normal-float on gaussian data
    # ~4.5 bits/elem incl. scales => ~7x smaller than f32
    assert q.nbytes() < x.size * 4 / 6


def test_nf4_exact_on_levels():
    from repro.core.quant import NF4_LEVELS
    x = jnp.asarray(NF4_LEVELS).reshape(1, -1) * 3.0
    q = quantize_nf4(x, block=16)
    np.testing.assert_allclose(np.asarray(dequantize_nf4(q)), np.asarray(x),
                               rtol=1e-6, atol=1e-6)


# -------------------------------------------------------------- SALRLinear

@pytest.mark.parametrize("method", ["dense", "mask", "bitmap", "nm", "bitmap_nf4"])
def test_salr_linear_forward(method):
    key = jax.random.PRNGKey(5)
    d_in, d_out = 64, 48
    w = jax.random.normal(key, (d_in, d_out)) / 8.0
    cfg = SALRConfig(sparsity=0.5, method=method, lora_rank=8, res_rank=8,
                     cap_align=8)
    layer = compress_linear(key, w, cfg)
    x = jax.random.normal(jax.random.PRNGKey(6), (5, d_in))
    y = apply_salr(x, layer)
    assert y.shape == (5, d_out)
    assert bool(jnp.all(jnp.isfinite(y)))
    if method == "dense":
        np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("method", ["mask", "bitmap"])
def test_salr_recovery_quality(method):
    """Ŵ + SVD-residual + (zero-init LoRA) should approximate the original
    matmul much better than pruning alone (Theorem 3 in action)."""
    key = jax.random.PRNGKey(8)
    d = 96
    w = jax.random.normal(key, (d, d)) / jnp.sqrt(d)
    x = jax.random.normal(jax.random.PRNGKey(9), (16, d))
    y_ref = x @ w

    cfg = SALRConfig(sparsity=0.5, method=method, lora_rank=4, res_rank=48,
                     cap_align=8)
    layer = compress_linear(key, w, cfg)
    y_salr = apply_salr(x, layer)

    cfg0 = SALRConfig(sparsity=0.5, method=method, lora_rank=4, res_rank=0,
                      cap_align=8)
    layer0 = compress_linear(key, w, cfg0)
    y_prune = apply_salr(x, layer0)

    err_salr = float(jnp.linalg.norm(y_salr - y_ref))
    err_prune = float(jnp.linalg.norm(y_prune - y_ref))
    assert err_salr < 0.55 * err_prune  # rank=d/2 must cut error a lot


def test_salr_transposed_storage_equivalence():
    key = jax.random.PRNGKey(10)
    w = jax.random.normal(key, (32, 48))
    x = jax.random.normal(jax.random.PRNGKey(11), (4, 32))
    # full-rank residual => W_hat + E == W exactly in either layout, so the
    # two storages must agree.  (At truncated rank they differ slightly
    # because capacity spill depends on the storage layout.)
    cfg = SALRConfig(sparsity=0.5, method="bitmap", lora_rank=4, res_rank=32,
                     cap_align=8)
    l_n = compress_linear(key, w, cfg, transposed=False)
    l_t = compress_linear(key, w, cfg, transposed=True)
    y_ref = x @ w
    np.testing.assert_allclose(np.asarray(apply_salr(x, l_n)),
                               np.asarray(y_ref), rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(apply_salr(x, l_t)),
                               np.asarray(y_ref), rtol=1e-3, atol=1e-3)


def test_effective_weight_identity_dense():
    key = jax.random.PRNGKey(12)
    w = jax.random.normal(key, (16, 16))
    cfg = SALRConfig(method="bitmap", sparsity=0.5, lora_rank=2,
                     res_rank=16, cap_align=8)
    layer = compress_linear(key, w, cfg)
    # rank = full => W_hat + residual adapter == W (LoRA starts at zero)
    np.testing.assert_allclose(np.asarray(effective_weight(layer)),
                               np.asarray(w), rtol=1e-4, atol=1e-4)


def test_split_trainable_partition():
    key = jax.random.PRNGKey(13)
    w = jax.random.normal(key, (16, 16))
    cfg = SALRConfig(method="bitmap", sparsity=0.5, lora_rank=2, res_rank=2,
                     cap_align=8)
    layer = compress_linear(key, w, cfg)
    train, frozen = split_trainable({"proj": layer})
    tleaves = jax.tree_util.tree_leaves(train)
    fleaves = jax.tree_util.tree_leaves(frozen)
    # trainable = lora.a, lora.b, res.a, res.b
    assert len(tleaves) == 4
    # frozen = bitmap words + values
    assert len(fleaves) == 2
    merged = combine(train, frozen)
    y0 = apply_salr(jnp.ones((1, 16)), layer)
    y1 = apply_salr(jnp.ones((1, 16)), merged["proj"])
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1))


def test_qsalr_size_reduction():
    key = jax.random.PRNGKey(14)
    d = 256
    w = jax.random.normal(key, (d, d))
    cfg = SALRConfig(sparsity=0.2, method="bitmap_nf4", lora_rank=0,
                     res_rank=0, cap_align=8)
    layer = compress_linear(key, w, cfg)
    dense_bytes = d * d * 2  # bf16 reference deployment
    # paper Table 6: ~5x vs bf16 at 20% sparsity + NF4
    ratio = dense_bytes / layer_nbytes(layer)
    assert ratio > 2.7  # vs f32 it is ~2x more
    y = apply_salr(jnp.ones((2, d)), layer)
    assert bool(jnp.all(jnp.isfinite(y)))


def test_salr_grad_only_flows_to_adapters():
    key = jax.random.PRNGKey(15)
    w = jax.random.normal(key, (24, 24))
    cfg = SALRConfig(method="bitmap", sparsity=0.5, lora_rank=4, res_rank=4,
                     cap_align=8)
    layer = compress_linear(key, w, cfg)
    x = jax.random.normal(jax.random.PRNGKey(16), (8, 24))
    train, frozen = split_trainable(layer)

    def loss(train_params):
        full = combine(train_params, frozen)
        return jnp.sum(apply_salr(x, full) ** 2)

    g = jax.grad(loss)(train)
    gl = jax.tree_util.tree_leaves(g)
    assert len(gl) == 4
    assert any(float(jnp.abs(x).sum()) > 0 for x in gl)


@pytest.mark.parametrize("backend", ["reference", "kernel"])
def test_kernel_decode_grads_equal_jnp_decode(monkeypatch, backend):
    """Adapter gradients through a two-layer remat'd scan of tiled SALR
    linears are the same whether the frozen base decodes by the Pallas
    decode kernel (interpreted here, as it runs on a TPU) or by the jnp
    decode: no tangent reaches the kernel, and remat traces it.  The
    kernel backend's backward replays the reference route over the whole
    layer, base included."""
    from repro.core import bitmap as bm
    from repro.core import salr
    d = 64
    cfg = SALRConfig(sparsity=0.5, method="bitmap", lora_rank=4, res_rank=4,
                     cap_align=8)
    keys = jax.random.split(jax.random.PRNGKey(21), 4)
    layers = [compress_linear(k, jax.random.normal(k, (d, d)) / 8.0, cfg,
                              cap_t=bm.tiled_capacity(d, 0.5))
              for k in keys[:2]]
    assert isinstance(layers[0].base, bm.TiledBitmapWeight)
    stack = jax.tree.map(lambda *a: jnp.stack(a), *layers)
    trainable, frozen = split_trainable(stack)
    x = jax.random.normal(keys[2], (8, d))

    def loss(trainable, frozen, x):
        def body(h, layer):
            return jnp.tanh(apply_salr(h, layer, backend=backend)), None
        h, _ = jax.lax.scan(jax.checkpoint(body, prevent_cse=False), x,
                            combine(trainable, frozen))
        return jnp.mean(jnp.square(h))

    def run():
        fn = jax.value_and_grad(loss)
        jaxpr = str(jax.make_jaxpr(fn)(trainable, frozen, x))
        return jax.jit(fn)(trainable, frozen, x), "bitmap_decode_t" in jaxpr

    want, decoded_in_kernel = run()
    assert not decoded_in_kernel
    monkeypatch.setattr(
        salr, "_decode_in_kernel",
        lambda base: isinstance(base, bm.TiledBitmapWeight))
    got, decoded_in_kernel = run()
    assert decoded_in_kernel
    leaves = jax.tree.leaves(want)
    assert any(float(jnp.max(jnp.abs(g))) > 0 for g in leaves[1:])
    for a, b in zip(jax.tree.leaves(got), leaves):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_kernel_decode_is_the_train_steps_choice(monkeypatch):
    """On one TPU chip the train step decodes its tiled bases in the
    Pallas kernel; a reference-route forward outside the train step (the
    oracles the kernels are checked against) keeps the jnp decode.
    Traced only: what the code observes on a TPU, from this CPU host."""
    from repro import configs
    from repro.models import model as M
    from repro.optim.adamw import AdamW
    from repro.train.state import make_train_state
    from repro.train.step import make_train_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda *a: 1)
    cfg = configs.get("smollm_135m", smoke=True)
    opt = AdamW(lr=1e-3)
    state = jax.eval_shape(
        lambda: make_train_state(jax.random.PRNGKey(0), cfg, opt))
    tokens = jnp.zeros((2, 16), jnp.int32)
    batch = {"tokens": tokens, "labels": tokens}
    step = str(jax.make_jaxpr(make_train_step(cfg, opt))(state, batch))
    assert "bitmap_decode_t" in step
    params = combine(state.trainable, state.frozen)
    with force_backend("reference"):
        oracle = str(jax.make_jaxpr(
            lambda p: M.forward_hidden(p, cfg, tokens))(params))
    assert "cumsum" in oracle                 # the jnp tile_decode
    assert "bitmap_decode_t" not in oracle
    # across chips a base may be sharded: the jnp decode stays
    monkeypatch.setattr(jax, "device_count", lambda *a: 4)
    layer = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:],
                                                        a.dtype),
                         params["groups"][0][0]["mlp"]["down"])
    with kernel_decode():
        one = str(jax.make_jaxpr(lambda x, lin: apply_salr(
            x, lin, backend="reference"))(jnp.ones((2, layer.d_in)), layer))
    assert "cumsum" in one and "bitmap_decode_t" not in one
