"""Plain reference of the benchmark's dense GQA models (Llama layout:
RMSNorm, rotary position embedding on the two halves of each head,
grouped-query causal attention, SwiGLU), written from the published
description in straightforward ``jax.numpy``.  It imports nothing of the
program and reads only the benchmark's own weights (``weights.py``).

Every linear is ``x @ (mask * W) + (x @ A) @ B + (x @ A_res) @ B_res``.
``mode="f32"`` computes in float32 at the highest matmul precision.
``mode="fp8"`` is the lower-precision control, the step below the
bfloat16 the configurations state: the inputs of every matmul are
rounded to float8 e4m3 and the gradients flowing back through them to
e5m2, each tensor scaled to its format's range first (the usual fp8
training recipe).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _scaled_round(x, dtype, top):
    s = jnp.max(jnp.abs(x)) / top
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def _fp8(x):
    return _scaled_round(x, jnp.float8_e4m3fn, 448.0)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, g):
    return (_scaled_round(g, jnp.float8_e5m2, 57344.0),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _cast(x, mode):
    x = x.astype(jnp.float32)
    return _fp8(x) if mode == "fp8" else x


def mm(x, w, mode):
    return jnp.matmul(_cast(x, mode), _cast(w, mode), precision=HIGHEST)


def linear(x, lw, mode, adapters=None):
    """``adapters`` (la, lb, ra, rb) override the layer's own."""
    la, lb, ra, rb = adapters if adapters is not None else (
        lw["la"], lw["lb"], lw["ra"], lw["rb"])
    return (mm(x, lw["w"], mode) + mm(mm(x, la, mode), lb, mode)
            + mm(mm(x, ra, mode), rb, mode))


def rmsnorm(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def rope(x, pos, theta):
    """x (S, H, hd): rotate (first half, second half) pairs."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, mode):
    """Causal GQA; q (S, H, hd), k/v (S, KH, hd); query head h reads
    key/value head h // (H // KH)."""
    s, h, hd = q.shape
    kh = k.shape[1]
    qg = _cast(q, mode).reshape(s, kh, h // kh, hd)
    scores = jnp.einsum("qkgd,pkd->kgqp", qg, _cast(k, mode),
                        precision=HIGHEST) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("kgqp,pkd->qkgd", _cast(p, mode), _cast(v, mode),
                     precision=HIGHEST)
    return out.reshape(s, h * hd)


def hidden(m, w, tokens, mode="f32", adapters=None):
    """Final-normed hidden states (S, d) of one sequence."""
    eps, theta = float(m["rms_norm_eps"]), float(m["rope_theta"])
    h, kh, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    s = tokens.shape[0]
    pos = jnp.arange(s)
    x = w["embed"][tokens].astype(jnp.float32)

    def layer(x, sl):
        lw, ad = sl
        get = (lambda n: None) if ad is None else (lambda n: ad[n])
        y = rmsnorm(x, lw["attn_norm"], eps)
        q = rope(linear(y, lw["q"], mode, get("q")).reshape(s, h, hd),
                 pos, theta)
        k = rope(linear(y, lw["k"], mode, get("k")).reshape(s, kh, hd),
                 pos, theta)
        v = linear(y, lw["v"], mode, get("v")).reshape(s, kh, hd)
        x = x + linear(attention(q, k, v, mode), lw["o"], mode, get("o"))
        y = rmsnorm(x, lw["mlp_norm"], eps)
        g = jax.nn.silu(linear(y, lw["gate"], mode, get("gate")))
        x = x + linear(g * linear(y, lw["up"], mode, get("up")),
                       lw["down"], mode, get("down"))
        return x, None

    x, _ = jax.lax.scan(layer, x, (w["layers"], adapters))
    return rmsnorm(x, w["final_norm"], eps)


def logits(m, w, tokens, positions, mode="f32"):
    """Logits (len(positions), vocab) of one sequence at ``positions``."""
    x = hidden(m, w, tokens, mode)[positions]
    return mm(x, w["head"], mode)


# ------------------------------------------------------------ training


def adapter_tree(w):
    """The trainable part of the weights: (la, lb, ra, rb) per linear,
    stacked over layers like ``w["layers"]``."""
    return {n: (lw["la"], lw["lb"], lw["ra"], lw["rb"])
            for n, lw in w["layers"].items() if isinstance(lw, dict)}


def loss(m, w, adapters, tokens, labels, mode="f32"):
    """Mean next-token cross-entropy over a (B, S) batch."""
    def one(t, lab):
        lg = mm(hidden(m, w, t, mode, adapters), w["head"], mode)
        logz = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, lab[:, None], axis=-1)[:, 0]
        return jnp.sum(logz - gold)
    total = jnp.sum(jax.lax.map(lambda tl: one(*tl), (tokens, labels)))
    return total / tokens.size


def adamw_steps(m, w, batches, opt, mode="f32"):
    """Run ``len(batches)`` AdamW steps on the adapters, as configured in
    ``opt`` (peak lr, warmup, total steps, betas, eps, weight decay,
    global-norm clip, residual-adapter lr scale, lr floor of the cosine).
    Returns (losses, clipped first gradient, adapters after the steps)."""
    params = adapter_tree(w)
    mu = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                params)
    nu = mu
    grad_fn = jax.value_and_grad(
        lambda p, t, lab: loss(m, w, p, t, lab, mode))
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    losses, first = [], None
    for step, (tokens, labels) in enumerate(batches, start=1):
        value, g = grad_fn(params, tokens, labels)
        leaves = jax.tree_util.tree_leaves(g)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in leaves))
        g = jax.tree_util.tree_map(
            lambda x: x * jnp.minimum(1.0, opt["clip_norm"] / (gnorm + 1e-9)),
            g)
        if first is None:
            first = g
        lr = _lr(opt, step)
        mu = jax.tree_util.tree_map(lambda a, x: b1 * a + (1 - b1) * x, mu, g)
        nu = jax.tree_util.tree_map(lambda a, x: b2 * a + (1 - b2) * x * x,
                                    nu, g)
        c1, c2 = 1 - b1 ** step, 1 - b2 ** step

        def upd(p, a, b, scale):
            pf = p.astype(jnp.float32)
            u = (a / c1) / (jnp.sqrt(b / c2) + eps) + opt["weight_decay"] * pf
            return (pf - lr * scale * u).astype(p.dtype)

        params = {n: tuple(upd(p, a, b, 1.0 if i < 2 else opt["res_lr_scale"])
                           for i, (p, a, b) in enumerate(zip(*t)))
                  for n, t in ((n, (params[n], mu[n], nu[n]))
                               for n in params)}
        losses.append(value)
    return losses, first, params


def _lr(opt, step):
    """AdamW's warm-up then cosine learning rate at ``step`` (from 1)."""
    peak, warm, total = opt["lr"], opt["warmup"], opt["total_steps"]
    if step < warm:
        return peak * step / max(warm, 1)
    frac = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    floor = opt["lr_floor"]
    return peak * (floor + (1 - floor) * 0.5 * (1 + math.cos(math.pi * frac)))
