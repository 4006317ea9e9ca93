"""Serving driver: the program's continuous-batching engine under load.

The harness keeps every clock itself.  It submits each request when it
is due (``arrival=0.0`` and increasing ``rid``, so the engine admits it
at its next ``step()`` in due order) and reads each request's tokens as
``step()`` returns.  Two arrival modes, named by the traffic file:

- ``open_loop``: Poisson arrivals at a fixed rate over the window; after
  the window the run drains until every request due in it has its first
  token.  End to end: ``ttft_p95_s`` (from each request's due time) and
  ``itl_p95_ms`` (every gap between consecutive tokens of a request).
- ``backlog``: more requests waiting than there are slots, all the
  window.  End to end: ``tok_s``, tokens delivered in the window over
  the window.

Afterwards the benchmark's plain reference (``reference.py``) re-runs a
sample of finished requests, drawn from the seed with the longest among
them, and the widest gap by which a served token's reference logit lies
below the reference's best is compared with its limit.
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


class Recorder:
    """Tokens of every request as the host sees them after ``step()``."""

    def __init__(self, eng, prompt_len: dict):
        self.eng = eng
        self.prompt_len = prompt_len
        self.seen: dict = {}
        self.first: dict = {}
        self.admitted: dict = {}
        self.last: dict = {}
        self.gaps: list = []
        self.done: set = set()
        self.delivered = 0
        self.steps: list = []     # (prefill live lengths, decode contexts)

    def observe(self, step_start: float, now: float, record: bool) -> None:
        counts = {a.req.rid: len(a.result.tokens)
                  for a in self.eng.slots if a is not None}
        if len(self.done) != len(self.eng.results):
            for rid, res in self.eng.results.items():
                if rid not in self.done:
                    self.done.add(rid)
                    counts[rid] = len(res.tokens)
        prefills, ctxs = [], []
        for rid, c in counts.items():
            c0 = self.seen.get(rid, 0)
            if c <= c0:
                continue
            p = self.prompt_len[rid]
            if c0 == 0:
                self.first[rid] = now
                self.admitted[rid] = step_start
                prefills.append(p)
            else:
                self.gaps.append(now - self.last[rid])
            self.gaps.extend([0.0] * (c - c0 - 1))
            if c >= 2:
                ctxs.append(p + c - 1)
            self.last[rid] = now
            self.seen[rid] = c
            self.delivered += c - c0
        if record:
            self.steps.append((prefills, ctxs))


def build(m: dict, t: dict, seed: int):
    from repro.launch.engine import ContinuousBatchingEngine, EngineConfig
    cfg = weights.arch_config(m)
    params = weights.program_params(m, seed)
    e = t["engine"]
    eng = ContinuousBatchingEngine(cfg, params, EngineConfig(
        n_slots=e["n_slots"], max_ctx=e["max_ctx"],
        page_size=e["page_size"], backend="kernel"))
    routes = {ph: eng.plan.route(ph).linear for ph in ("prefill", "decode")}
    if set(routes.values()) != {"kernel"} or not eng.paged:
        raise RuntimeError(f"engine is not as configured: linear routes "
                           f"{routes}, paged KV {eng.paged}")
    return eng


def warm(eng, t: dict, vocab: int, seconds: float) -> list:
    """Compile every shape the mix can use, then clear the engine."""
    from repro.launch.engine import Request
    prompts = traffic.warm_prompts(t, vocab, eng.buckets, seconds)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=2, arrival=0.0))
    while eng.step():
        pass
    eng.reset()
    return [len(p) for p in prompts]


def run(ctx) -> dict:
    m, t, seed, seconds = ctx.model, ctx.traffic, ctx.seed, ctx.seconds
    eng = build(m, t, seed)
    ctx.stamp("weights and engine")
    buckets = warm(eng, t, m["vocab_size"], seconds)
    ctx.stamp(f"warmed prefill buckets {buckets} and the decode step")
    ctx.mark_setup()
    w = window(ctx, eng, t, seed, seconds)
    stats = ctx.device_stats()
    sample = pick_sample(w["finished"], w["requests"], t["check_requests"],
                         seed)
    pairs = [(w["requests"][r].prompt, w["finished"][r]) for r in sample]
    del eng
    gc.collect()
    numbers = {"widest_gap": widest_gap(m, seed, pairs,
                                        t["engine"]["max_ctx"],
                                        t["output"]["max"])}
    for mode in ctx.controls:
        numbers[f"widest_gap.{mode}"] = widest_gap(
            m, seed, pairs, t["engine"]["max_ctx"], t["output"]["max"],
            control=mode)
    ctx.note(f"checked {len(sample)} requests, "
             f"{sum(len(s) for _, s in pairs)} served tokens")
    return {"metrics": w["metrics"], "attempted": w["attempted"],
            "failed": w["failed"], "numbers": numbers, "stats": stats}


def window(ctx, eng, t: dict, seed: int, seconds: float) -> dict:
    """Offer the mix to the warmed engine for ``seconds``."""
    from repro.launch.engine import Request
    vocab = ctx.model["vocab_size"]
    backlog = t["arrivals"] == "backlog"
    blocks = 0
    pending_reqs = [] if backlog else traffic.open_loop(t, seed, seconds,
                                                        vocab)
    prompt_len: dict = {}
    due: dict = {}
    requests: dict = {}
    rec = Recorder(eng, prompt_len)
    late, depth, step_s = [], [], []
    trace_from = seconds - t["trace_seconds"]
    tracing = None
    i = 0
    t0 = CLOCK()

    def submit(r, now):
        prompt_len[r.rid] = len(r.prompt)
        due[r.rid] = r.due
        requests[r.rid] = r
        late.append(now - r.due)
        eng.submit(Request(rid=r.rid, prompt=r.prompt, max_new_tokens=r.out,
                           arrival=0.0))

    while True:
        now = CLOCK() - t0
        if ctx.trace and tracing is None and trace_from <= now < seconds:
            tracing = ctx.start_trace()
        if tracing and now >= seconds:
            ctx.stop_trace(tracing)
            tracing = False
        with jax.profiler.TraceAnnotation("bench.submit"):
            if backlog:
                if now >= seconds:
                    break
                while len(eng.pending) < t["backlog"]:
                    for r in traffic.backlog_block(t, seed, blocks, vocab,
                                                   blocks * t["block"]):
                        submit(r, 0.0)
                    blocks += 1
            else:
                while i < len(pending_reqs) and pending_reqs[i].due <= now:
                    submit(pending_reqs[i], now)
                    i += 1
                if i == len(pending_reqs) and len(rec.first) == i:
                    break
        depth.append((now, len(eng.pending), eng.n_active))
        if eng.pending or eng.n_active:
            start = CLOCK() - t0
            cpu = time.thread_time()
            with jax.profiler.TraceAnnotation("bench.step"):
                eng.step()
            after = CLOCK() - t0
            step_s.append((after - start, t0 + start - ctx.t_start,
                           time.thread_time() - cpu))
            with jax.profiler.TraceAnnotation("bench.observe"):
                before = rec.delivered
                rec.observe(start, after, bool(tracing))
                if backlog and after > seconds:
                    rec.delivered = before
        else:
            with jax.profiler.TraceAnnotation("bench.sleep"):
                time.sleep(max(0.0, pending_reqs[i].due - now))
    if tracing:
        ctx.stop_trace(tracing)

    step_s.sort()
    ctx.note(f"{len(step_s)} steps, median {step_s[len(step_s) // 2][0]:.4f}"
             f" s, slowest {step_s[-1][0]:.4f} s at {step_s[-1][1]:.3f} s, "
             f"{step_s[-1][2]:.4f} s of it on this thread's CPU")
    ctx.note(f"requests {len(requests)}, started {len(rec.first)}, "
             f"finished {len(eng.results)}, generator late p50 "
             f"{1e3 * float(np.median(late)):.3f} ms max "
             f"{1e3 * max(late):.3f} ms")
    metrics = {}
    if backlog:
        metrics["tok_s"] = (rec.delivered / seconds, "tokens/s")
        attempted = len(rec.first)
    else:
        ttft = [rec.first[r] - due[r] for r in requests]
        metrics["ttft_p95_s"] = (float(np.percentile(ttft, 95)), "s")
        metrics["itl_p95_ms"] = (float(np.percentile(rec.gaps, 95)) * 1e3,
                                 "ms")
        ctx.note(f"ttft p50 {np.median(ttft):.4f} s over "
                 f"{len(ttft)} requests, itl p50 "
                 f"{1e3 * np.median(rec.gaps):.3f} ms over {len(rec.gaps)} "
                 f"gaps")
        attempted = len(requests)
    waits = [rec.admitted[r] - due[r] for r in rec.admitted]
    ctx.layer_inputs.update(steps=rec.steps, queue_waits=waits)
    return {"metrics": metrics, "attempted": attempted,
            "failed": attempted - len(rec.first), "requests": requests,
            "finished": {rid: list(r.tokens)
                         for rid, r in eng.results.items()},
            "admitted": rec.admitted, "first": rec.first, "depth": depth,
            "due": due}


def pick_sample(finished: dict, requests: dict, k: int, seed: int) -> list:
    """The longest finished request and k - 1 others drawn from the seed."""
    rids = sorted(finished)
    if not rids:
        return []
    longest = max(rids, key=lambda r: (len(requests[r].prompt)
                                       + len(finished[r]), r))
    rest = [r for r in rids if r != longest]
    draw = traffic.rng(seed, 7).permutation(len(rest))[:k - 1]
    return [longest] + sorted(rest[j] for j in draw)


def reference_logits_fn(m: dict, mode: str):
    return jax.jit(lambda w, toks, pos: reference.logits(m, w, toks, pos,
                                                         mode))


def teacher_inputs(prompt, served, max_ctx: int, max_out: int):
    """Token ids (max_ctx,) of prompt + served tokens, and the positions
    (max_out,) whose logits predict each served token (padded)."""
    seq = list(prompt) + list(served[:-1])
    toks = np.zeros((max_ctx,), np.int32)
    toks[:len(seq)] = seq
    pos = np.full((max_out,), len(prompt) - 1, np.int32)
    pos[:len(served)] = len(prompt) - 1 + np.arange(len(served))
    return jnp.asarray(toks), jnp.asarray(pos)


def widest_gap(m: dict, seed: int, pairs: list, max_ctx: int,
               max_out: int, control: str | None = None) -> float:
    """Widest gap between the reference's best logit and its logit of a
    served token.  ``control`` ("fp8") reads instead the gap of the token
    that the reference at that precision puts first."""
    if not pairs:
        return float("inf")
    w = weights.bench_weights(m, seed)
    ref = reference_logits_fn(m, "f32")
    low = reference_logits_fn(m, control) if control else None
    worst = 0.0
    for prompt, served in pairs:
        toks, pos = teacher_inputs(prompt, served, max_ctx, max_out)
        lg = np.asarray(ref(w, toks, pos))[:len(served)]
        if low is not None:
            chosen = np.asarray(low(w, toks, pos))[:len(served)].argmax(-1)
        else:
            chosen = np.asarray(served)
        if np.any(chosen >= lg.shape[1]) or np.any(chosen < 0):
            return float("inf")
        gap = lg.max(-1) - lg[np.arange(len(chosen)), chosen]
        worst = max(worst, float(gap.max()))
    return worst
