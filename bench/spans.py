"""The program's own spans and named scopes in a profiler trace.

    python3 bench/spans.py <cell> <seed> <seconds> [<trace_seconds>]

runs a serving or fine-tuning cell once, traced, keeps its trace and
prints, as one JSON line, the summary ``reduce`` makes of it and the four
per-layer readings of this module.  ``trace_seconds`` (serving only)
replaces the mix's own, to trace a whole window.

An op's scopes are in its HLO metadata name (``op_name``, e.g.
``jit(decode_fn)/layers/while/body/attn/paged_gqa/...``).  A TPU trace's
op events carry no stat with it (on a TPU v5e their stats are
``device_offset_ps``, ``device_duration_ps`` and ``Time Scale
Multiplier``), so ``hlo_names`` reads it from the compiled modules'
text (``compiled.as_text()``), keyed by module and by instruction: an
event names its instruction (``%fusion.42 = s32[64]{...} fusion(...)``),
and of the compiled variants of one module (a prefill per bucket) an
execution is matched to the one whose instructions have its ops'
result shapes.  ``reduce`` works on ``devtrace.load_events``' list and
those names:

- ``spans``: every program span (``engine.*``, ``bench.*``) on the main
  thread, by name (the name before any ``#`` metadata), as intervals;
- ``modules``: per XLA module, its executions, its busy time (the union
  of its ops), its ops' device self time by scope path, and the part of
  it whose ops its compiled text names (``named_s``);
- ``scopes``: device self time per innermost named scope, over all
  modules.  Self time is an op's duration less the ops nested in it on
  its line, so a ``while`` is not counted twice; an op without a scope
  (also after ``hlo_names``' look at its operand) takes that of the op
  it is nested in, and ops under no scope of ``SCOPES`` count as
  ``unscoped``;
- ``idle_spans``: each device idle gap charged to the innermost program
  span that covers its middle;
- ``device_scopes``: the ten scopes of most self time.

``traced_window`` writes the compiled modules' text beside the trace
(``<trace_dir>/hlo/``), which ``load`` reads back.
"""
from __future__ import annotations

import bisect
import collections
import glob
import json
import os
import re
import statistics
import sys

import devtrace

SPAN_PREFIXES = ("bench.", "engine.")
# The program's named scopes (models/model.py, models/attention.py,
# core/salr.py), from the entry point down
SCOPES = ("embed", "layers", "head", "attn", "mlp", "moe", "rglru",
          "mlstm", "slstm", "kv_write", "paged_gqa", "salr_base",
          "salr_base_decode", "salr_adapter")
UNSCOPED = "unscoped"
OUTSIDE = "outside spans"

_WORD = re.compile(r"[A-Za-z_][\w.\-]*")
_INSTR = re.compile(r"^\s*(?:ROOT )?(%[^ ]+) = (.*)$")
_OPCODE = re.compile(r" [a-z][\w\-]*\(")
_SHAPE = re.compile(r"[a-z]\w*\[[\d,]*\]")
_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')


def instruction_key(text: str):
    """(name, result shapes) of an HLO instruction, as a trace event or
    a line of module text prints it; None for anything else."""
    m = _INSTR.match(text)
    if m is None:
        return None
    rest = m.group(2)
    op = _OPCODE.search(rest)
    return m.group(1), tuple(_SHAPE.findall(rest[:op.start()] if op
                                            else rest))


def hlo_names(texts) -> dict:
    """{module name: [{instruction key: op_name}, one per variant]} of
    compiled modules' text.  An instruction the compiler put in (a copy,
    a layout change) has no metadata: it takes its first operand's."""
    out = collections.defaultdict(list)
    for text in texts:
        head = re.match(r"HloModule ([^\s,]+)", text)
        names, operand, by_name = {}, {}, {}
        for line in text.splitlines():
            key = instruction_key(line)
            if key is None:
                continue
            m = _OP_NAME.search(line)
            names[key] = m.group(1) if m else None
            by_name[key[0]] = key
            op = _OPCODE.search(line, line.index(" = "))
            arg = re.match(r"%[^\s,)]+", line[op.end():]) if op else None
            operand[key] = arg.group(0) if arg else None
        for key in [k for k, v in names.items() if v is None]:
            seen, k = set(), key
            while names[k] is None and operand[k] in by_name \
                    and k not in seen:
                seen.add(k)
                k = by_name[operand[k]]
            names[key] = names[k]
        out[head.group(1)].append(names)
    return dict(out)


def load(trace_dir: str):
    """(``devtrace.load_events``' events, the texts of the compiled
    modules kept beside them)."""
    texts = []
    for path in sorted(glob.glob(os.path.join(trace_dir, "hlo", "*.txt"))):
        with open(path) as f:
            texts.append(f.read())
    return devtrace.load_events(trace_dir), texts


def _named(ops, variants):
    """[(start, end, op_name)] of one module execution's ops [(start,
    end, event name)], by the variant that knows their shapes best, and
    for each op whether that variant knows it."""
    keys = [instruction_key(n) for _, _, n in ops]
    best = max(variants or [{}],
               key=lambda v: sum(k in v for k in keys[:200]))
    return ([(s, e, best.get(k)) for (s, e, _), k in zip(ops, keys)],
            [k in best for k in keys])


def scope_path(op_name) -> str:
    """The program's scopes in an op's metadata name, outermost first:
    ``jit(f)/layers/while/body/transpose(jvp(attn))/dot_general`` ->
    ``layers/attn``.  The last part names the primitive, and a name
    without ``/`` (a parameter's) holds no scope.  An instruction that
    merged several ops reads ``a/b/squeeze;attn/reshape``: its first
    name counts."""
    found = []
    for part in (op_name or "").split(";", 1)[0].split("/")[:-1]:
        words = [w for w in _WORD.findall(part) if w in SCOPES]
        found += words[-1:]
    return "/".join(found)


def innermost(path: str) -> str:
    return path.rsplit("/", 1)[-1] if path else UNSCOPED


def _self_times(ops):
    """[(start, end, path)] of one line, by start and then longest first
    -> [(self seconds, path)].  An op without a scope of its own takes
    the path of the op it runs nested in."""
    self_ns = [e - s for s, e, _ in ops]
    paths = [p for _, _, p in ops]
    stack = []
    for i, (s, e, _) in enumerate(ops):
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            parent = stack[-1]
            self_ns[parent] -= min(e, ops[parent][1]) - s
            paths[i] = paths[i] or paths[parent]
        stack.append(i)
    return [(t * 1e-9, p) for t, p in zip(self_ns, paths)]


def _innermost_spans(spans, points):
    """For each of the sorted ``points`` the name of the innermost of
    ``spans`` [(start, end, name)], which nest, that covers it."""
    spans = sorted(spans, key=lambda x: (x[0], -x[1]))
    out, stack, i = [], [], 0
    for p in points:
        while i < len(spans) and spans[i][0] <= p:
            while stack and stack[-1][1] <= spans[i][0]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][1] <= p:
            stack.pop()
        out.append(stack[-1][2] if stack else OUTSIDE)
    return out


def span_name(name: str) -> str:
    return name.split("#", 1)[0]


def reduce(events: list, hlo: dict | None = None) -> dict:
    """The summary of ``devtrace.load_events``' list; ``hlo`` (of
    ``hlo_names``) names the ops' scopes."""
    host = [e for e in events if not devtrace.is_device_plane(e[0])]
    window = [(e[0], e[1], e[3], e[3] + e[4]) for e in host
              if span_name(e[2]) == devtrace.WINDOW_SPAN]
    if not window:
        raise ValueError(f"trace holds no {devtrace.WINDOW_SPAN} span")
    plane0, main, w0, w1 = window[0]
    spans = collections.defaultdict(list)
    flat = []
    for p, line, name, s, d in host:
        name = span_name(name)
        if (p, line) == (plane0, main) and name.startswith(SPAN_PREFIXES) \
                and s < w1 and s + d > w0:
            spans[name].append((s, s + d))
            if name != devtrace.WINDOW_SPAN:
                flat.append((s, s + d, name))

    modules = {}
    scopes = collections.Counter()
    gaps = []
    for plane in sorted({e[0] for e in events
                         if devtrace.is_device_plane(e[0])}):
        runs = sorted((s, s + d, devtrace.base_name(n))
                      for p, line, n, s, d in events
                      if p == plane and line == "XLA Modules"
                      and s < w1 and s + d > w0)
        starts = [r[0] for r in runs]
        ops = sorted(((max(s, w0), min(s + d, w1), n)
                      for p, line, n, s, d in events
                      if p == plane and line == "XLA Ops"
                      and s < w1 and s + d > w0),
                     key=lambda o: (o[0], -o[1]))
        per_run = collections.defaultdict(list)
        for op in ops:
            i = bisect.bisect_right(starts, op[0]) - 1
            per_run[i if i >= 0 and runs[i][1] >= op[0] else None].append(op)
        for s, e, name in runs:
            modules.setdefault(name, {"count": 0, "busy_s": 0.0,
                                      "named_s": 0.0,
                                      "scopes": collections.Counter()})
            modules[name]["count"] += 1
        for i, rops in per_run.items():
            name = runs[i][2] if i is not None else None
            named, known = _named(rops, (hlo or {}).get(name))
            m = modules.get(name, {"busy_s": 0.0, "named_s": 0.0,
                                   "scopes": collections.Counter()})
            m["busy_s"] += sum(e - s for s, e in
                               devtrace._union([o[:2] for o in rops])) * 1e-9
            for (t, path), k in zip(_self_times(
                    [(s, e, scope_path(n)) for s, e, n in named]), known):
                m["scopes"][path] += t
                m["named_s"] += t * k
                scopes[innermost(path)] += t
        merged = devtrace._union([o[:2] for o in ops])
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] + g[1])
    idle = collections.Counter()
    for (s, e), name in zip(gaps, _innermost_spans(
            flat, [(s + e) / 2 for s, e in gaps])):
        idle[name] += (e - s) * 1e-9
    return {
        "window_s": (w1 - w0) * 1e-9,
        "spans": {k: sorted(v) for k, v in spans.items()},
        "modules": {k: dict(v, scopes=dict(v["scopes"]))
                    for k, v in modules.items()},
        "scopes": dict(scopes),
        "idle_spans": sorted(idle.items(), key=lambda kv: -kv[1])[:10],
        "device_scopes": sorted(scopes.items(), key=lambda kv: -kv[1])[:10],
    }


# ------------------------------------------------------------ readings

def _within(inner, outer):
    """Total length of the ``inner`` intervals inside each of ``outer``."""
    out = []
    inner = sorted(inner)
    for s, e in outer:
        out.append(sum(min(b, e) - max(a, s) for a, b in inner
                       if a < e and b > s))
    return out


def engine_host_ms(summary: dict):
    """Median over traced ticks of the time ``engine.step`` does not
    spend waiting on the device (``engine.admit_wait``,
    ``engine.decode_wait``), in ms."""
    sp = summary["spans"]
    steps = sp.get("engine.step")
    if not steps:
        return None
    waits = sp.get("engine.admit_wait", []) + sp.get("engine.decode_wait",
                                                     [])
    host = [(e - s) - w for (s, e), w in zip(steps, _within(waits, steps))]
    return 1e-6 * statistics.median(host)


def h2d_per_tick(counters: dict):
    """Host -> device arrays the engine made per decode tick."""
    ticks = counters.get("n_decode_ticks")
    if "h2d_uploads" not in counters or not ticks:
        return None
    return counters["h2d_uploads"] / ticks


def scope_time(summary: dict, needle: str, scope: str):
    """(self seconds under ``scope`` at any depth, self seconds in all,
    executions) of the modules whose name holds ``needle``, or None."""
    hits = [m for k, m in summary["modules"].items() if needle in k]
    if not hits:
        return None
    paths = collections.Counter()
    for m in hits:
        paths.update(m["scopes"])
    under = sum(t for path, t in paths.items() if scope in path.split("/"))
    return under, sum(paths.values()), sum(m["count"] for m in hits)


def decode_attn_ms(summary: dict):
    """Device self time under ``attn`` in the decode step (XLA module
    ``decode_fn``) per decode tick, in ms."""
    t = scope_time(summary, "decode_fn", "attn")
    if t is None or not t[2] or not t[0]:
        return None
    return 1e3 * t[0] / t[2]


def base_decode_share(summary: dict):
    """Share of the train step's device self time (XLA module
    ``train_step``) spent under ``salr_base_decode``, in %."""
    t = scope_time(summary, "train_step", "salr_base_decode")
    if t is None or not t[1] or not t[0]:
        return None
    return 100.0 * t[0] / t[1]


# ------------------------------------------------------------ the tool

def context(cell_name: str, seed: int, seconds: float,
            trace_seconds=None):
    """A traced run's context for a cell of BENCHMARK.json; a serving
    cell's ``trace_seconds`` may replace its mix's own."""
    import run
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == cell_name)
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    model = run.load_json(run.ROOT, conf["file"])["model"]
    spec = run.load_json(run.HERE, "traffic", cell["traffic"] + ".json")
    if trace_seconds is not None:
        spec = dict(spec, trace_seconds=trace_seconds)
    return run.Context(cell, model, spec, seed, seconds, True)


def traced_window(ctx):
    """Run the cell's window traced, as ``--trace 1`` does, and keep the
    trace in ``ctx.trace_dir`` with its modules' compiled text:
    (end-to-end metrics, engine counters).  A serving cell skips the
    reference check; a fine-tuning cell runs whole."""
    import jax

    def stop_trace(span):
        span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        ctx.trace_summary = devtrace.reduce(
            devtrace.load_events(ctx.trace_dir))

    ctx.stop_trace = stop_trace
    m, spec, counters = ctx.model, ctx.traffic, {}
    if spec["driver"] == "serve":
        import serve
        eng = serve.build(m, spec, ctx.seed)
        lengths = serve.warm(eng, spec, m["vocab_size"], ctx.seconds)
        ctx.mark_setup()
        metrics = serve.window(ctx, eng, spec, ctx.seed,
                               ctx.seconds)["metrics"]
        counters = {"h2d_uploads": eng.h2d_uploads,
                    "n_decode_ticks": eng.n_decode_ticks,
                    "n_prefills": eng.n_prefills,
                    "n_finished": len(eng.results)}
        texts = _serving_texts(eng, lengths)
    else:
        import train
        metrics = train.run(ctx)["metrics"]
        texts = _training_texts(m, spec, ctx.seed)
    os.makedirs(os.path.join(ctx.trace_dir, "hlo"), exist_ok=True)
    for i, text in enumerate(texts):
        with open(os.path.join(ctx.trace_dir, "hlo", f"{i}.txt"), "w") as f:
            f.write(text)
    return {k: v for k, (v, _) in metrics.items()}, counters


def _training_texts(m: dict, spec: dict, seed: int) -> list:
    """Compiled text of the train step the fine-tuning driver builds."""
    import jax.numpy as jnp
    import train
    state, step = train.build(m, spec["optimizer"], seed)
    tokens = jnp.zeros((spec["batch"], spec["seq"]), jnp.int32)
    return [_fresh_text(lambda: step.lower(state, {"tokens": tokens,
                                                   "labels": tokens}))]


def _fresh_text(lower) -> str:
    """Compiled text of ``lower()``'s program from a compile of its own:
    an executable from JAX's caches keeps the metadata of the program
    that put it there, whose scopes may differ."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    try:
        return lower().compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _serving_texts(eng, lengths) -> list:
    """Compiled text of the engine's decode step, and of its prefill and
    insert at each bucket that prompts of ``lengths`` take."""
    import jax
    import jax.numpy as jnp
    from repro.launch.engine import pick_bucket

    def i32(*shape):
        return jnp.zeros(shape, jnp.int32)

    n = eng.ecfg.n_slots
    out = [_fresh_text(lambda: eng._decode.lower(eng.params, eng.cache,
                                                 i32(n, 1), i32(n)))]
    for b in sorted({pick_bucket(k, eng.buckets) for k in lengths}):
        out.append(_fresh_text(lambda: eng._prefill.lower(
            eng.params, i32(1, b), i32(), None, None, 0)))
        _, rcache = jax.eval_shape(
            lambda p, t, i: eng._prefill(p, t, i, None, None, 0),
            eng.params, i32(1, b), i32())
        out.append(_fresh_text(lambda: eng._insert.lower(
            eng.cache, rcache, i32(), i32())))
    return out


def main(argv) -> int:
    if len(argv) not in (3, 4):
        raise SystemExit(__doc__)
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path[:0] = [here, os.path.join(os.path.dirname(here), "src")]
    import run
    run.check_device(1, True)
    run.enable_cache()
    ctx = context(argv[0], int(argv[1]), float(argv[2]),
                  float(argv[3]) if len(argv) == 4 else None)
    metrics, counters = traced_window(ctx)
    events, texts = load(ctx.trace_dir)
    s = reduce(events, hlo_names(texts))
    steps = s["spans"].get("engine.step", [])
    print(json.dumps({
        "cell": argv[0], "seed": int(argv[1]), "setup_s": ctx.setup_s,
        "metrics": metrics, "counters": counters,
        "readings": {"engine_host_ms": engine_host_ms(s),
                     "h2d_per_tick": h2d_per_tick(counters),
                     "decode_attn_ms": decode_attn_ms(s),
                     "base_decode_share": base_decode_share(s)},
        "window_s": s["window_s"], "busy_s": ctx.trace_summary["busy_s"],
        "idle_gaps": ctx.trace_summary["idle_gaps"],
        "idle_spans": s["idle_spans"], "device_scopes": s["device_scopes"],
        "modules": s["modules"],
        "span_totals": {k: [len(v), sum(e - b for b, e in v) * 1e-9]
                        for k, v in s["spans"].items()},
        "slowest_steps_ns": sorted(steps, key=lambda t: t[0] - t[1])[:5],
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
