"""Per-kernel allclose tests against the ref.py pure-jnp oracles,
sweeping shapes and dtypes (interpret=True executes the kernel bodies on
CPU; real-TPU execution uses the same code with interpret=False)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitmap as bm
from repro.kernels import ops, ref

jax.config.update("jax_traceback_filtering", "off")


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-4, atol=2e-4)


SHAPES = [
    # (M, K, N, block_m, block_k)
    (8, 64, 64, 8, 32),
    (16, 128, 128, 16, 64),
    (32, 128, 256, 32, 128),
    (7, 64, 128, 8, 64),      # M padding path
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mm,kk,nn,bm_,bk", SHAPES)
def test_bitmap_spmm_vs_ref(mm, kk, nn, bm_, bk, dtype):
    key = jax.random.PRNGKey(mm * 1000 + nn)
    k1, k2 = jax.random.split(key)
    w = (jax.random.normal(k1, (kk, nn)) / np.sqrt(kk)).astype(dtype)
    x = (jax.random.normal(k2, (mm, kk)) / 4).astype(dtype)
    tile = min(nn, 64)
    tbw, _ = bm.tile_encode_from_dense(w, 0.5, tile=tile)
    y_ref = ref.bitmap_spmm_ref(x, tbw)
    y = ops.bitmap_matmul(x, tbw, block_m=bm_, block_k=bk, interpret=True)
    assert y.shape == y_ref.shape and y.dtype == dtype
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("nm_pat", [(2, 4), (1, 4), (4, 8)])
def test_nm_spmm_vs_ref(nm_pat, dtype):
    n, m = nm_pat
    mm, kk, nn = 16, 64, 128
    key = jax.random.PRNGKey(n * 10 + m)
    k1, k2 = jax.random.split(key)
    w = (jax.random.normal(k1, (kk, nn)) / np.sqrt(kk)).astype(dtype)
    x = (jax.random.normal(k2, (mm, kk)) / 4).astype(dtype)
    nmw, _ = bm.nm_encode(w, n=n, m=m)
    y_ref = ref.nm_spmm_ref(x, nmw)
    y = ops.nm_matmul(x, nmw, block_m=16, block_n=64, block_k=32,
                      interpret=True)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("r", [8, 32])
def test_salr_spmm_vs_ref(r, dtype):
    mm, kk, nn = 16, 128, 128
    key = jax.random.PRNGKey(r)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    w = (jax.random.normal(k1, (kk, nn)) / np.sqrt(kk)).astype(dtype)
    x = (jax.random.normal(k2, (mm, kk)) / 4).astype(dtype)
    a = (jax.random.normal(k3, (kk, r)) / np.sqrt(kk)).astype(dtype)
    b = (jax.random.normal(k4, (r, nn)) / np.sqrt(r)).astype(dtype)
    tbw, _ = bm.tile_encode_from_dense(w, 0.5, tile=64)
    y_ref = ref.salr_spmm_ref(x, tbw, a, b)
    y = ops.salr_matmul(x, tbw, a, b, block_m=16, block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mm,kk,nn,r", [(8, 64, 64, 16), (16, 128, 256, 48)])
def test_fused_lora_vs_ref(mm, kk, nn, r, dtype):
    key = jax.random.PRNGKey(mm + r)
    k1, k2, k3 = jax.random.split(key, 3)
    x = (jax.random.normal(k1, (mm, kk)) / 4).astype(dtype)
    a = (jax.random.normal(k2, (kk, r)) / np.sqrt(kk)).astype(dtype)
    b = (jax.random.normal(k3, (r, nn)) / np.sqrt(r)).astype(dtype)
    y_ref = ref.fused_lora_ref(x, a, b)
    y = ops.lora_matmul(x, a, b, block_m=8, block_n=64, block_k=32,
                        interpret=True)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_nf4_spmm_vs_ref(dtype):
    mm, kk, nn = 16, 64, 128
    key = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    w = jax.random.normal(k1, (kk, nn)) / np.sqrt(kk)
    x = (jax.random.normal(k2, (mm, kk)) / 4).astype(dtype)
    codes, scales = ops.nf4_encode_2d(w)
    y_ref = ref.nf4_spmm_ref(x, codes, scales)
    y = ops.nf4_matmul(x, codes, scales, block_m=16, block_n=64, block_k=32,
                       interpret=True)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), **_tol(dtype))


def test_salr_spmm_multi_adapter_concat():
    """The fused kernel with A_cat/B_cat == sum of per-adapter updates +
    sparse base — the paper's deployment identity."""
    from repro.core.adapters import LoRAAdapter, concat_adapters
    mm, kk, nn = 8, 64, 64
    key = jax.random.PRNGKey(9)
    ks = jax.random.split(key, 6)
    w = jax.random.normal(ks[0], (kk, nn)) / np.sqrt(kk)
    x = jax.random.normal(ks[1], (mm, kk)) / 4
    ad1 = LoRAAdapter(a=jax.random.normal(ks[2], (kk, 8)),
                      b=jax.random.normal(ks[3], (8, nn)) / 8, scale=0.5)
    ad2 = LoRAAdapter(a=jax.random.normal(ks[4], (kk, 16)),
                      b=jax.random.normal(ks[5], (16, nn)) / 8, scale=2.0)
    cat = concat_adapters([ad1, ad2])
    tbw, _ = bm.tile_encode_from_dense(w, 0.5, tile=64)
    y = ops.salr_matmul(x, tbw, cat.a, cat.b, block_m=8, block_k=64,
                        interpret=True)
    y_ref = (x @ bm.tile_decode(tbw)
             + 0.5 * (x @ ad1.a) @ ad1.b + 2.0 * (x @ ad2.a) @ ad2.b)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)


def test_bitmap_matmul_batched_input():
    key = jax.random.PRNGKey(11)
    w = jax.random.normal(key, (64, 128)) / 8
    x = jax.random.normal(key, (2, 3, 64)) / 4
    tbw, _ = bm.tile_encode_from_dense(w, 0.5, tile=64)
    y = ops.bitmap_matmul(x, tbw, block_m=8, block_k=64, interpret=True)
    assert y.shape == (2, 3, 128)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(x @ bm.tile_decode(tbw)),
                               rtol=2e-4, atol=2e-4)


# --------------------------------------- standalone decode (train route)

def _tiled_at(kdim, n, dtype, full_rows=0, seed=0):
    """A tiled bitmap of a (kdim, n) base at p = 0.5, its columns padded
    to the tile multiple; the first tile of the first ``full_rows`` rows
    is set to overflow, so those cells hold exactly ``cap_t`` values."""
    tile = bm.default_tile(n)
    cols = bm.round_up(n, tile)
    cap_t = bm.tiled_capacity(tile, 0.5)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    w = jax.random.normal(k1, (kdim, cols)).astype(dtype)
    mask = jax.random.bernoulli(k2, 0.5, (kdim, cols)) & (jnp.arange(cols) < n)
    mask = mask.at[:full_rows, :tile].set(True)
    tbw, _ = bm.tile_encode(jnp.where(mask, w, 0), mask, tile, cap_t)
    return tbw


@pytest.mark.parametrize("kk,nn,dtype,full_rows", [
    (576, 576, jnp.bfloat16, 0),      # N padded to 768
    (576, 192, jnp.bfloat16, 0),
    (576, 1536, jnp.bfloat16, 0),
    (1536, 576, jnp.bfloat16, 0),     # block_k 128
    (576, 576, jnp.bfloat16, 8),      # full cells: the last value row
    (64, 64, jnp.float32, 4),
], ids=["576x576", "576x192", "576x1536", "1536x576", "576x576-full",
        "64x64-f32-full"])
def test_bitmap_decode_t_bit_exact(kk, nn, dtype, full_rows):
    tbw = _tiled_at(kk, nn, dtype, full_rows)
    if full_rows:
        per_cell = jnp.sum(jax.lax.population_count(tbw.words), axis=1)
        assert int(jnp.max(per_cell)) == tbw.cap_t
    wt = ops.bitmap_decode_t(tbw, interpret=True)
    want = bm.tile_decode(tbw).T
    assert wt.shape == want.shape == (tbw.cols, kk) and wt.dtype == dtype
    np.testing.assert_array_equal(np.asarray(wt).view(np.uint8),
                                  np.asarray(want).view(np.uint8))


# ------------------------------------------- paged decode vs dense decode

def _paged_pool(rows, pos, ps, n_row_pages, fill):
    """Scatter per-row context ``rows`` (B, W, ...) into a page pool
    (1 + B * n_row_pages, ps, ...) whose page 0 is the null page; only
    positions <= pos[b] are written, the rest keep ``fill``.  Returns
    (pool, page_table)."""
    b = rows.shape[0]
    pool = np.full((1 + b * n_row_pages, ps) + rows.shape[2:], fill,
                   np.asarray(rows).dtype)
    table = np.zeros((b, n_row_pages), np.int32)
    for i in range(b):
        for p in range(n_row_pages):
            idx = 1 + i * n_row_pages + p
            live = max(0, min(ps, int(pos[i]) + 1 - p * ps))
            pool[idx, :live] = np.asarray(rows[i, p * ps:p * ps + live])
            table[i, p] = idx
    return jnp.asarray(pool), jnp.asarray(table)


@pytest.mark.parametrize("kind", ["native", "int8", "nf4", "mla"])
def test_paged_kernel_vs_dense_decode(kind):
    """Each paged decode kernel against the dense decode the serving
    engine's oracle runs: ``decode_attention`` over the same rows
    (dequantized by ``_dq8`` / ``_dqnf4`` for the quantized pools), and
    the absorbed-MLA einsums of ``apply_mla``'s dense decode.  f32
    operands, so the two paths differ only in f32 summation order."""
    from repro.kernels import paged_attention as pa
    from repro.models import attention as A
    key = jax.random.PRNGKey(7)
    b, kh, g, d, ps, n_row_pages = 3, 2, 2, 16, 8, 3
    h, w = kh * g, ps * n_row_pages
    pos = np.array([0, 13, w - 1], np.int32)
    valid = jnp.arange(w)[None, :] <= pos[:, None]
    rnd = lambda i, shape: jax.random.normal(jax.random.fold_in(key, i),
                                             shape, jnp.float32)
    if kind == "mla":
        r, rd = 32, 8
        q_lat, q_rope = rnd(0, (b, h, r)), rnd(1, (b, h, rd))
        ckv, krope = rnd(2, (b, w, r)), rnd(3, (b, w, rd))
        cp, table = _paged_pool(ckv, pos, ps, n_row_pages, 5.0)
        kp, _ = _paged_pool(krope, pos, ps, n_row_pages, -5.0)
        got = pa.paged_mla_attention(q_lat, q_rope, cp, kp, table,
                                     jnp.asarray(pos), qk_dim=d + rd)
        s = (jnp.einsum("bhr,bkr->bhk", q_lat, ckv)
             + jnp.einsum("bhd,bkd->bhk", q_rope, krope))
        s = jnp.where(valid[:, None, :], s / jnp.sqrt(jnp.float32(d + rd)),
                      A.NEG_INF)
        want = jnp.einsum("bhk,bkr->bhr", jax.nn.softmax(s, axis=-1), ckv)
    else:
        q = rnd(0, (b, 1, h, d))
        k, v = rnd(1, (b, w, kh, d)), rnd(2, (b, w, kh, d))
        if kind == "native":
            kp, table = _paged_pool(k, pos, ps, n_row_pages, 5.0)
            vp, _ = _paged_pool(v, pos, ps, n_row_pages, -5.0)
            got = pa.paged_gqa_attention(q, kp, vp, table, jnp.asarray(pos))
        else:
            quant, dequant, paged = {
                "int8": (A._q8, A._dq8, pa.paged_quant_gqa_attention),
                "nf4": (A._qnf4, A._dqnf4, pa.paged_nf4_gqa_attention),
            }[kind]
            (kq, ks), (vq, vs) = quant(k), quant(v)
            k, v = dequant(kq, ks, q.dtype), dequant(vq, vs, q.dtype)
            pools = [_paged_pool(x, pos, ps, n_row_pages, 0)[0]
                     for x in (kq, vq, ks, vs)]
            table = _paged_pool(ks, pos, ps, n_row_pages, 0)[1]
            got = paged(q, *pools, table, jnp.asarray(pos))
        want = A.decode_attention(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-6, atol=2e-6)
