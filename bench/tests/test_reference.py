"""The benchmark's weights reach the program intact, and its plain
reference computes what the program computes (float32, CPU)."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

import reference
import weights

DATA = os.path.join(os.path.dirname(__file__), "data")


def _tiny(dtype="float32"):
    with open(os.path.join(DATA, "tiny_model.json")) as f:
        return dict(json.load(f)["model"], dtype=dtype)


def test_loader_builds_the_programs_parameter_tree():
    from repro.models import model as M
    m = _tiny("bfloat16")
    cfg = weights.arch_config(m)
    want = jax.eval_shape(lambda k: M.init_params(k, cfg),
                          jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda s: weights.to_program(
        m, weights.generate(m, s)), jnp.zeros((2,), jnp.uint32))
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype
    # the adapters' ranks are the configuration's, not the program's init
    lin = got["groups"][0][0]["mixer"]["wq"]
    assert lin.lora.a.shape[-1] == m["lora_rank"]
    assert lin.res.a.shape[-1] == m["res_rank"]


def test_mask_keeps_exactly_the_configured_share():
    m = _tiny("bfloat16")
    w = weights.bench_weights(m, 2 ** 40 + 3)
    mask = np.asarray(w["layers"]["up"]["mask"])
    groups = mask.reshape(*mask.shape[:-1], -1, weights.MASK_GROUP)
    assert (groups.sum(-1) == weights.MASK_GROUP // 2).all()
    assert (np.asarray(w["layers"]["up"]["w"])[~mask] == 0).all()


def test_reference_matches_the_program_in_float32():
    from repro.models import model as M
    m = _tiny()
    cfg = weights.arch_config(m)
    params = weights.program_params(m, 3)
    w = weights.bench_weights(m, 3)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 40), 0,
                              m["vocab_size"])
    with jax.default_matmul_precision("highest"):
        prog = M.forward_train(params, cfg, toks)[0, :, :m["vocab_size"]]
    ref = reference.logits(m, w, toks[0], jnp.arange(40))
    np.testing.assert_allclose(np.asarray(prog), np.asarray(ref),
                               atol=2e-5, rtol=0)


def test_seed_beyond_32_bits():
    a = weights.seed_words(2 ** 31 + 5)
    assert a.tolist() == [0, 2 ** 31 + 5]
    assert weights.seed_words(2 ** 33).tolist() == [2, 0]
