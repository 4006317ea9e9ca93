"""Real-size TPU compiles of the serving path's kernels, at no chip time.

The TPU compiler is installed even where no chip is attached: it
compiles for a DESCRIBED v5e chip, and refuses what the chip would
refuse (misaligned blocks, unsupported in-kernel ops, VMEM overruns) —
none of which interpret mode can see.  Shapes are ``smollm_135m``'s
published widths: every SALR linear (K x N) at prefill M=128 and decode
M=8 (``n_slots``), the paged GQA decode kernel at its heads, and the
standalone bitmap decode of the train step, alone, over an expert stack
and inside a reference-route linear at the fine-tuning cell's 4096 rows.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and pytest-xdist workers all import
this file.  The worker that runs these tests loads it; elsewhere the
fixture skips.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.core import bitmap as bm
from repro.core import salr
from repro.core.adapters import LoRAAdapter
from repro.kernels import ops
from repro.kernels.paged_attention import paged_gqa_attention

CFG = configs.get("smollm_135m")
D, HD = CFG.d_model, CFG.resolved_head_dim
H, KH, F = CFG.n_heads, CFG.n_kv_heads, CFG.d_ff
RANK = CFG.salr.lora_rank + CFG.salr.res_rank
LINEARS = {"wq/wo": (D, H * HD), "wk/wv": (D, KH * HD),
           "gate/up": (D, F), "down": (F, D)}
N_SLOTS, PAGE, MAX_PAGES = 8, 8, 20          # the chip smoke's engine


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # such compiles cannot be read back from a persistent cache without
    # a chip; keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _tiled_spec(spec, kdim, n):
    tile = bm.default_tile(n)
    cols = bm.round_up(n, tile)
    cap_t = bm.tiled_capacity(tile, CFG.salr.sparsity)
    return bm.TiledBitmapWeight(
        words=spec((cols // tile, tile // 32, kdim), jnp.uint32),
        values=spec((cols // tile, cap_t, kdim), jnp.bfloat16),
        cols=cols, tile=tile, cap_t=cap_t)


@pytest.mark.parametrize("m", [128, N_SLOTS], ids=["prefill", "decode"])
@pytest.mark.parametrize("name", list(LINEARS))
def test_salr_matmul_compiles_for_v5e(one_chip, name, m):
    kdim, n = LINEARS[name]

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    tbw = _tiled_spec(spec, kdim, n)
    text = _compiled_text(
        lambda x, w, a, b: ops.salr_matmul(x, w, a, b, interpret=False),
        spec((m, kdim), jnp.bfloat16), tbw,
        spec((kdim, RANK), jnp.bfloat16), spec((RANK, n), jnp.bfloat16))
    assert "tpu_custom_call" in text


def test_paged_gqa_attention_compiles_for_v5e(one_chip):
    n_pool = N_SLOTS * MAX_PAGES + 1

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = _compiled_text(
        lambda *a: paged_gqa_attention(*a, interpret=False),
        spec((N_SLOTS, 1, H, HD), jnp.bfloat16),
        spec((n_pool, PAGE, KH, HD), jnp.bfloat16),
        spec((n_pool, PAGE, KH, HD), jnp.bfloat16),
        spec((N_SLOTS, MAX_PAGES), jnp.int32),
        spec((N_SLOTS,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("name", list(LINEARS))
def test_bitmap_decode_compiles_for_v5e(one_chip, name):
    kdim, n = LINEARS[name]

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = _compiled_text(lambda w: ops.bitmap_decode_t(w, interpret=False),
                          _tiled_spec(spec, kdim, n))
    assert "tpu_custom_call" in text


def test_train_route_decodes_in_kernel_for_v5e(one_chip, monkeypatch):
    """The reference route of a tiled linear, as the train step runs it
    on one TPU chip: the base decodes in the Pallas kernel, with no
    gather."""
    kdim, n = LINEARS["wq/wo"]

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    half = RANK // 2
    layer = salr.SALRLinear(
        base=_tiled_spec(spec, kdim, n),
        lora=LoRAAdapter(a=spec((kdim, half), jnp.bfloat16),
                         b=spec((half, n), jnp.bfloat16), scale=2.0),
        res=LoRAAdapter(a=spec((kdim, half), jnp.bfloat16),
                        b=spec((half, n), jnp.bfloat16)),
        bias=None, d_in=kdim, d_out=n, transposed=False)
    # what the code observes on one TPU chip, from this CPU host
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda *a: 1)
    with salr.kernel_decode():
        text = _compiled_text(salr._apply_reference,
                              spec((4096, kdim), jnp.bfloat16), layer)
    assert "tpu_custom_call" in text
    assert "gather" not in text


def test_bitmap_decode_vmapped_compiles_for_v5e(one_chip):
    """An expert stack's bases decode under ``vmap`` in the MoE
    reference route of a train step; the batched kernel compiles."""
    kdim, n = LINEARS["gate/up"]

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct((4, *shape), dtype, sharding=one_chip)

    text = _compiled_text(
        jax.vmap(lambda w: ops.bitmap_decode_t(w, interpret=False)),
        _tiled_spec(spec, kdim, n))
    assert "tpu_custom_call" in text
