"""Fine-tuning driver: the program's jitted train step
(``train/step.make_train_step`` under its ``AdamW``, over the state of
``train/state.train_state_from_params``, assembled as
``launch/train.build_trainer`` assembles them), fed the benchmark's own
batches.  ``build_trainer`` itself is not called: it also builds the
program's synthetic data stream, whose bigram table costs about a minute
at a 49,152-token vocabulary and serves no batch of this cell.  The
residual adapters' learning-rate scale is the configuration's
(``res_lr_scale``, what ``build_trainer``'s Theorem-4 rule gives at
these widths once clipped to 10).

Set-up builds the one train state and step, and drives them through
their first three steps with the window's own call and feed (step 1
compiles).  Those three steps are what the reference follows: each
step's loss, the first gradient as the optimizer got it (its first
moment after one step, over 1 - beta1), and the adapters' change after
three steps, each by the worst adapter leaf.  The window then runs whole
steps on the same state until ``--seconds`` have passed; ``train_tok_s``
is their tokens over their time.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

import reference
import traffic
import weights

CLOCK = time.perf_counter
CHECKED_STEPS = 3
POOL = 64


def _flat(tree: dict) -> dict:
    """{linear.part: array} of an adapters tree."""
    return {f"{n}.{p}": v for n, parts in tree.items()
            for p, v in (parts.items() if isinstance(parts, dict)
                         else zip(("la", "lb", "ra", "rb"), parts))}


@jax.jit
def _norms(a: dict, b: dict, scale) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(
        a[k].astype(jnp.float32) * scale - b[k].astype(jnp.float32))))
        for k in a}


def leaf_norms(tree: dict, minus: dict | None = None,
               scale: float = 1.0) -> dict:
    """{linear.part: float32 norm} of an adapters tree times ``scale``,
    or of its difference from ``minus``."""
    a = _flat(tree)
    b = _flat(minus) if minus is not None else {
        k: jnp.zeros((), v.dtype) for k, v in a.items()}
    return {k: float(v) for k, v in _norms(a, b, scale).items()}


def build(m: dict, opt: dict, seed: int):
    """(state, jitted step) of the program, as configured."""
    from repro.optim.adamw import AdamW, warmup_cosine
    from repro.train.state import train_state_from_params
    from repro.train.step import make_train_step
    cfg = weights.arch_config(m)
    adamw = AdamW(lr=warmup_cosine(opt["lr"], opt["warmup"],
                                   opt["total_steps"], opt["lr_floor"]),
                  b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                  weight_decay=opt["weight_decay"],
                  clip_norm=opt["clip_norm"])
    state = train_state_from_params(weights.program_params(m, seed), adamw)
    step = jax.jit(make_train_step(cfg, adamw,
                                   res_lr_scale=opt["res_lr_scale"]))
    return state, step


def run(ctx) -> dict:
    m, t, seed, seconds = ctx.model, ctx.traffic, ctx.seed, ctx.seconds
    opt = t["optimizer"]
    state, step_fn = build(m, opt, seed)
    ctx.stamp("weights and train state")
    pool = traffic.train_batches(t, seed, m["vocab_size"], POOL)
    feeds = jax.jit(lambda b: [{"tokens": b[i, :, :-1],
                                "labels": b[i, :, 1:]}
                               for i in range(POOL)])(pool)
    ctx.stamp("trainer and batches")

    start = weights.adapters_from_program(state.trainable)
    losses = []
    for i in range(CHECKED_STEPS):
        state, met = step_fn(state, feeds[i])
        losses.append(float(met["loss"]))
        ctx.stamp(f"checked step {i + 1}")
        if i == 0:
            mu1 = weights.adapters_from_program(state.opt.mu)
            grad1 = leaf_norms(mu1, scale=1.0 / (1.0 - opt["b1"]))
    change = leaf_norms(weights.adapters_from_program(state.trainable),
                        start)
    del start
    ctx.mark_setup()

    trace_from = seconds - t["trace_seconds"]
    tracing = None
    n_steps, step_times, bad = 0, [], 0
    i = CHECKED_STEPS
    t0 = CLOCK()
    while CLOCK() - t0 < seconds:
        if ctx.trace and tracing is None and CLOCK() - t0 >= trace_from:
            tracing = ctx.start_trace()
        s0, cpu = CLOCK(), time.thread_time()
        with jax.profiler.TraceAnnotation("bench.train_step"):
            state, met = step_fn(state, feeds[i % POOL])
            loss = float(met["loss"])
        step_times.append((CLOCK() - s0, bool(tracing), s0 - ctx.t_start,
                           time.thread_time() - cpu))
        bad += not np.isfinite(loss)
        n_steps += 1
        i += 1
    elapsed = CLOCK() - t0
    if tracing:
        ctx.stop_trace(tracing)
    stats = ctx.device_stats()
    tokens = t["batch"] * t["seq"]
    times = sorted((t, at, c) for t, _, at, c in step_times)
    ctx.note(f"{n_steps} steps in {elapsed:.3f} s (step median "
             f"{times[len(times) // 2][0]:.3f} s, slowest {times[-1][0]:.3f}"
             f" s at {times[-1][1]:.3f} s, {times[-1][2]:.3f} s of it on "
             f"this thread's CPU); "
             f"checked losses {losses}")
    ctx.layer_inputs.update(train_tokens=tokens)
    del state, feeds, step_fn
    gc.collect()

    batches = [(pool[j, :, :-1], pool[j, :, 1:]) for j in range(CHECKED_STEPS)]
    ref = reference_readings(m, seed, batches, opt)
    numbers = compare(losses, grad1, change, ref, leaf_norms(
        mu1, ref["first"], 1.0 / (1.0 - opt["b1"])))
    k = max(change, key=lambda k: abs(change[k] - ref["change"][k])
            / ref["change"][k])
    ctx.note(f"change of leaf {k}: {change[k]!r} against the reference's "
             f"{ref['change'][k]!r}; first gradient {grad1[k]!r} against "
             f"{ref['grad1'][k]!r}")
    for mode in ctx.controls:
        if mode == "half_batch":
            half = t["batch"] // 2
            c = reference_readings(m, seed, [(a[:half], b[:half])
                                             for a, b in batches], opt)
        else:
            c = reference_readings(m, seed, batches, opt, mode)
        for k, v in compare(c["losses"], c["grad1"], c["change"], ref,
                            leaf_norms(c["first"], ref["first"])).items():
            numbers[f"{k}.{mode}"] = v
    return {"metrics": {"train_tok_s": (n_steps * tokens / elapsed,
                                        "tokens/s")},
            "attempted": n_steps, "failed": bad, "numbers": numbers,
            "stats": stats}


def reference_readings(m, seed, batches, opt, mode="f32") -> dict:
    w = weights.bench_weights(m, seed)
    run3 = jax.jit(lambda w, b: reference.adamw_steps(m, w, b, opt, mode))
    losses, first, after = run3(w, batches)
    return {"losses": [float(x) for x in losses], "first": first,
            "grad1": leaf_norms(first),
            "change": leaf_norms(after, reference.adapter_tree(w))}


def compare(losses, grad1, change, ref, grad_diff) -> dict:
    """The numbers compared, each the worst over steps or leaves.

    ``loss_gap``: the largest relative gap of a checked step's loss.
    ``grad_gap`` and ``change_gap``: the largest gap between a leaf's
    norm and the reference leaf's, of the first gradient and of the
    change after the checked steps.  ``grad_diff``: the largest norm of
    a leaf's first gradient minus the reference's (``grad_diff`` holds
    those norms per leaf).  Each is measured against the larger of the
    reference leaf's norm and the median leaf's.  Leaves whose reference
    gradient is under a thousandth of the median leaf's move by
    round-off alone and are left out of the change."""
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(losses,
                                                       ref["losses"]))

    def worst(prog, refn, keep):
        med = float(np.median([refn[k] for k in keep]))
        return max(abs(prog[k] - refn[k]) / max(refn[k], med) for k in keep)

    leaves = sorted(ref["grad1"])
    gmed = float(np.median([ref["grad1"][k] for k in leaves]))
    moving = [k for k in leaves if ref["grad1"][k] >= 1e-3 * gmed]
    return {"loss_gap": loss_gap,
            "grad_gap": worst(grad1, ref["grad1"], leaves),
            "grad_diff": max(grad_diff[k] / max(ref["grad1"][k], gmed)
                             for k in leaves),
            "change_gap": worst(change, ref["change"], moving)}
