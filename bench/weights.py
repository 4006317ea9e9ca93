"""The benchmark's own model weights, made from the seed on the device.

The benchmark, not the program, decides every weight, so the plain
reference (``reference.py``) can use the very same numbers without
taking anything the program made.  Per linear layer it draws a dense
weight, a pruning mask that keeps exactly ``1 - sparsity`` of every
group of ``MASK_GROUP`` columns in each row, a task adapter and a
residual adapter, all in the configuration's dtype.  ``to_program``
then hands them to the program through its own compression entry
(``compress_linear`` with the mask given and no SVD), which packs the
base into whatever storage the program uses, and sets the two adapters.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

# Exact-count masks per group of this many columns: every width of the
# benchmark's configurations is a multiple of it, and no 256-column tile
# of the program can then hold more than half its columns, so nothing
# spills out of its static capacity.
MASK_GROUP = 64
ADAPTER_AMPLITUDE = 0.3
LINEARS = ("q", "k", "v", "o", "gate", "up", "down")


def seed_words(seed: int) -> np.ndarray:
    """A seed of up to 64 bits as two uint32 words (a jit argument)."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is not a whole number below 2**64")
    return np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)


def root_key(words, stream: int) -> jax.Array:
    key = jax.random.fold_in(jax.random.PRNGKey(stream), words[0])
    return jax.random.fold_in(key, words[1])


def linear_shapes(m: dict) -> dict:
    d, hd = m["d_model"], m["head_dim"]
    q, kv, f = m["n_heads"] * hd, m["n_kv_heads"] * hd, m["d_ff"]
    return {"q": (d, q), "k": (d, kv), "v": (d, kv), "o": (q, d),
            "gate": (d, f), "up": (d, f), "down": (f, d)}


def _mask(key, shape, keep: float):
    """Exactly round(keep * MASK_GROUP) kept entries in every group."""
    *lead, n = shape
    assert n % MASK_GROUP == 0, (shape, MASK_GROUP)
    n_keep = int(round(keep * MASK_GROUP))
    bits = jax.random.bits(key, (*lead, n // MASK_GROUP, MASK_GROUP),
                           jnp.uint32)
    # low bits hold the column index, so the keys of a group are distinct
    keys = (bits & ~jnp.uint32(MASK_GROUP - 1)) | jnp.arange(
        MASK_GROUP, dtype=jnp.uint32)
    kth = jnp.sort(keys, axis=-1)[..., n_keep - 1:n_keep]
    return (keys <= kth).reshape(shape)


def _linear(key, n_layers, k, n, m, dt):
    ks = jax.random.split(key, 6)
    keep = 1.0 - m["sparsity"]
    r, rr = m["lora_rank"], m["res_rank"]
    mask = _mask(ks[0], (n_layers, k, n), keep)
    w = jax.random.normal(ks[1], (n_layers, k, n), jnp.float32)
    w = (w / np.sqrt(k * keep) * mask).astype(dt)

    def pair(ka, kb, rank):
        a = jax.random.normal(ka, (n_layers, k, rank), jnp.float32)
        b = jax.random.normal(kb, (n_layers, rank, n), jnp.float32)
        return ((a / np.sqrt(k)).astype(dt),
                (b * (ADAPTER_AMPLITUDE / np.sqrt(rank))).astype(dt))

    la, lb = pair(ks[2], ks[3], r)
    ra, rb = pair(ks[4], ks[5], rr)
    return {"w": w, "mask": mask, "la": la, "lb": lb, "ra": ra, "rb": rb}


def generate(m: dict, words) -> dict:
    """All weights of configuration ``m`` (its ``model`` block) from the
    seed words.  Traceable; ``generate_jit`` runs it as one program."""
    dt = jnp.dtype(m["dtype"])
    d, v, n_layers = m["d_model"], m["vocab_size"], m["num_hidden_layers"]
    key = root_key(words, 1)
    ks = jax.random.split(key, 4 + len(LINEARS))
    embed = jax.random.normal(ks[0], (v, d), jnp.float32) / np.sqrt(d)
    if m["tie_word_embeddings"]:
        head = embed.T
    else:
        head = jax.random.normal(ks[1], (d, v), jnp.float32) / np.sqrt(d)
    norms = 1.0 + 0.1 * jax.random.normal(ks[2], (2 * n_layers + 1, d))
    layers = {"attn_norm": norms[:n_layers].astype(dt),
              "mlp_norm": norms[n_layers:2 * n_layers].astype(dt)}
    for i, (name, (k, n)) in enumerate(linear_shapes(m).items()):
        layers[name] = _linear(ks[4 + i], n_layers, k, n, m, dt)
    return {"embed": embed.astype(dt), "head": head.astype(dt),
            "final_norm": norms[-1].astype(dt), "layers": layers}


# -------------------------------------------------- into the program


def arch_config(m: dict):
    """The program's ``ArchConfig`` for configuration block ``m``."""
    from repro.configs.base import ArchConfig, LayerGroup, SALRModelConfig
    return ArchConfig(
        name=m["name"], family="dense", d_model=m["d_model"],
        n_heads=m["n_heads"], n_kv_heads=m["n_kv_heads"],
        head_dim=m["head_dim"], d_ff=m["d_ff"], vocab_size=m["vocab_size"],
        mlp="swiglu", rope_theta=float(m["rope_theta"]),
        norm_eps=float(m["rms_norm_eps"]), dtype=m["dtype"],
        layer_groups=(LayerGroup(("attn",), m["num_hidden_layers"]),),
        salr=SALRModelConfig(sparsity=m["sparsity"], method="bitmap",
                             lora_rank=m["lora_rank"],
                             res_rank=m["res_rank"], backend="kernel"))


# program parameter path of each linear, and whether its init stores it
# transposed (the kernel layout ignores that flag, compress_linear takes it)
_PROGRAM_LINEARS = {"q": ("mixer", "wq", True), "k": ("mixer", "wk", True),
                    "v": ("mixer", "wv", True), "o": ("mixer", "wo", False),
                    "gate": ("mlp", "gate", True), "up": ("mlp", "up", True),
                    "down": ("mlp", "down", False)}


def to_program(m: dict, w: dict):
    """The program's parameter tree holding weights ``w``."""
    from repro.core.adapters import LoRAAdapter
    from repro.core.salr import SALRConfig, compress_linear
    from repro.models.layers import padded_vocab

    cfg = arch_config(m)
    scfg = SALRConfig(sparsity=m["sparsity"], method="bitmap",
                      lora_rank=m["lora_rank"], res_rank=0,
                      dtype=m["dtype"], backend="kernel")
    pad = padded_vocab(cfg) - m["vocab_size"]
    layer = {"mixer": {"norm": {"scale": w["layers"]["attn_norm"]}},
             "mlp_norm": {"scale": w["layers"]["mlp_norm"]}, "mlp": {}}
    for name, (block, key, transposed) in _PROGRAM_LINEARS.items():
        lw = w["layers"][name]

        def one(wl, mask, la, lb, ra, rb, transposed=transposed):
            lin = compress_linear(jax.random.PRNGKey(0), wl, scfg,
                                  transposed=transposed, mask=mask)
            return dataclasses.replace(
                lin, lora=LoRAAdapter(a=la, b=lb, scale=1.0),
                res=LoRAAdapter(a=ra, b=rb, scale=1.0))

        layer[block][key] = jax.vmap(one)(lw["w"], lw["mask"], lw["la"],
                                          lw["lb"], lw["ra"], lw["rb"])
    return {"embed": {"table": jnp.pad(w["embed"], ((0, pad), (0, 0)))},
            "groups": [[layer]],
            "final_norm": {"scale": w["final_norm"]},
            "lm_head": {"w": jnp.pad(w["head"], ((0, 0), (0, pad)))}}


def adapters_from_program(trainable) -> dict:
    """{linear: {la, lb, ra, rb}} of a program parameter (or gradient or
    optimizer-moment) tree shaped like ``to_program``'s output."""
    layer = trainable["groups"][0][0]
    out = {}
    for name, (block, key, _) in _PROGRAM_LINEARS.items():
        lin = layer[block][key]
        out[name] = {"la": lin.lora.a, "lb": lin.lora.b,
                     "ra": lin.res.a, "rb": lin.res.b}
    return out


def program_params(m: dict, seed: int):
    """One jitted call on the default device: seed -> program params."""
    fn = jax.jit(lambda words: to_program(m, generate(m, words)))
    return fn(seed_words(seed))


def bench_weights(m: dict, seed: int):
    return jax.jit(lambda words: generate(m, words))(seed_words(seed))
