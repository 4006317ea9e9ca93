"""Benchmark entry point.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  Everything that belongs to one cell is
found by name from ``BENCHMARK.json``: the configuration file
(``bench/configs/``), the traffic file (``bench/traffic/``, whose
``driver`` names ``serve`` or ``train``), the limits of the correctness
check (``bench/limits/<cell>.json``) and, with ``--trace 1``, one reader
per per-layer metric (``bench/metrics/<name>.py``, else the file of the
name before its first dot).

It needs as many accelerator chips as the cell asks for: without them it
exits with code 3 and prints no result.  With ``BENCH_KEEP_TRACE_EVENTS``
set to a path, a traced run also writes there a census of its trace and
a 30 ms slice of it, for reading a trace by hand (the recorded slice of
``tests/data`` was made so).  The last line of standard
output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and last
``checks``: each number compared beside its limit).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(ROOT, ".bench_out")
DRIVERS = ("serve", "train")


class NoChip(Exception):
    pass


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class GcWatch:
    """Times each collection of Python's garbage collector:
    [(generation, start, seconds)] on the harness's clock."""

    def __init__(self):
        self.pauses: list = []
        self._start = None

    def __call__(self, phase, info):
        now = time.perf_counter()
        if phase == "start":
            self._start = now
        elif self._start is not None:
            self.pauses.append((info["generation"], self._start - T_START,
                                now - self._start))
            self._start = None


class Context:
    """What a driver needs from the harness, and what it hands back."""

    def __init__(self, cell, model, traffic_spec, seed, seconds, trace):
        self.cell, self.model, self.traffic = cell, model, traffic_spec
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.setup_s = None
        self.layer_inputs: dict = {}
        self.trace_summary = None
        self.controls: tuple = ()         # lower-precision readings (study)
        self.gc_watch = GcWatch()
        self.t_start = T_START
        self.trace_dir = os.path.join(OUT, "trace-" + cell["name"])

    def note(self, msg: str) -> None:
        print(f"[{self.cell['name']}] {msg}", file=sys.stderr, flush=True)

    def stamp(self, what: str) -> None:
        """Note how far into the process a set-up step ended."""
        self.note(f"{what} at {time.perf_counter() - T_START:.3f} s")

    def mark_setup(self) -> None:
        """End set-up.  What set-up made is collected once and frozen, so
        a full collection in the window walks only what the window
        makes, as a long-running server would have it."""
        gc.collect()
        gc.freeze()
        gc.callbacks.append(self.gc_watch)
        self.setup_s = time.perf_counter() - T_START
        self.note(f"set-up {self.setup_s:.3f} s")

    def gc_report(self) -> None:
        p = self.gc_watch.pauses
        if p:
            g, at, d = max(p, key=lambda x: x[2])
            self.note(f"garbage collections after set-up: {len(p)}, "
                      f"{sum(x[0] == 2 for x in p)} full, "
                      f"{sum(x[2] for x in p):.4f} s in all, longest "
                      f"{d:.4f} s (generation {g}, at {at:.3f} s)")

    def start_trace(self):
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # the harness's spans suffice
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        span = jax.profiler.TraceAnnotation("bench.window")
        span.__enter__()
        return span

    def stop_trace(self, span) -> None:
        import jax
        import devtrace as trace_mod
        span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        events = trace_mod.load_events(self.trace_dir)
        self.trace_summary = trace_mod.reduce(events)
        if os.environ.get("BENCH_KEEP_TRACE_EVENTS"):
            keep_trace_sample(events, os.environ["BENCH_KEEP_TRACE_EVENTS"])
        shutil.rmtree(self.trace_dir, ignore_errors=True)

    def device_stats(self) -> dict:
        import jax
        dev = jax.devices()
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in dev[:self.cell["chips"]])
        return {"platform": dev[0].platform, "kind": dev[0].device_kind,
                "count": self.cell["chips"], "memory_peak_bytes": int(peak)}


def keep_trace_sample(events, path, slice_ms=30.0):
    """Write a census of the trace's events by (plane, line, name without
    its instance number) and every event of a short slice in the middle
    of the traced window, for reading a trace by hand and for tests."""
    import collections
    import devtrace
    census = collections.Counter()
    for p, line, name, s, d in events:
        census[(p, line, devtrace.base_name(name))] += d
    w = [(s, s + d) for p, _, n, s, d in events if n == devtrace.WINDOW_SPAN]
    mid = (w[0][0] + w[0][1]) // 2
    lo, hi = mid, mid + int(slice_ms * 1e6)

    def short(name):
        """The op's name, and the call if it is a Pallas kernel."""
        head = name.split(" = ", 1)[0]
        return head + (" = custom-call(), " + devtrace.PALLAS
                       if devtrace.PALLAS in name else "")

    sample = [(p, line, short(n), s, d) for p, line, n, s, d in events
              if s < hi and s + d > lo]
    with open(path, "w") as f:
        json.dump({"census": sorted([list(k) + [v] for k, v in census.items()],
                                    key=lambda r: -r[3])[:400],
                   "slice": sample, "window": list(w[0])}, f)


def check_device(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform not in ("tpu", "gpu")
                         or len(devs) < chips):
        raise NoChip(f"needs {chips} accelerator chip(s); JAX has "
                     f"{len(devs)} {devs[0].platform} device(s)")
    print(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
          f"count={len(devs)}", file=sys.stderr, flush=True)


def enable_cache():
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def layer_metrics(bench: dict, cell: dict, ctx: Context, peaks: dict):
    import devtrace as trace_mod
    out = {}
    inputs = dict(ctx.layer_inputs, trace=ctx.trace_summary,
                  model=ctx.model, traffic=ctx.traffic, peaks=peaks,
                  trace_mod=trace_mod,
                  counts=load_module(os.path.join(HERE, "counts",
                                                  "forward.py"),
                                     "bench_counts_forward"))
    for metric in bench["per_layer"]:
        if cell["name"] not in metric.get("workloads", [cell["name"]]):
            continue
        name = metric["name"]
        path = os.path.join(HERE, "metrics", name + ".py")
        if not os.path.exists(path):
            path = os.path.join(HERE, "metrics", name.split(".")[0] + ".py")
        reader = load_module(path, "bench_metric_" + name.replace(".", "_"))
        value = reader.read(inputs)
        if value is not None:
            out[name] = {"value": float(value), "unit": metric["unit"]}
    return out


def peaks_for(kind: str) -> dict:
    """The chip's peaks by ``device_kind``; an unknown kind is an error."""
    table = load_json(HERE, "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}")
    return table[kind]


def judge(limits_dir: str, cell_name: str, numbers: dict):
    """[(name, value, limit, ok)] for every number compared.  The cell's
    limits file names every number: a limit of null marks one that is
    read and printed but not compared (PERF.md says why); a number the
    file does not name fails."""
    path = os.path.join(limits_dir, cell_name + ".json")
    limits = load_json(path) if os.path.exists(path) else {}
    rows = []
    for name, value in sorted(numbers.items()):
        if name not in limits:
            rows.append((name, value, None, False))
            continue
        lim = limits[name]["limit"]
        ok = lim is None or (value == value and value <= lim)
        rows.append((name, value, lim, ok))
    return rows


def main(argv=None, require_chip: bool = True, bench_file=None) -> int:
    """``bench_file`` replaces BENCHMARK.json (tests); it may name its
    own ``traffic_dir`` and ``limits_dir``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json(bench_file or os.path.join(ROOT, "BENCHMARK.json"))
    traffic_dir = bench.get("traffic_dir", os.path.join(HERE, "traffic"))
    limits_dir = bench.get("limits_dir", os.path.join(HERE, "limits"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    model = load_json(ROOT, conf["file"])["model"]
    spec = load_json(traffic_dir, cell["traffic"] + ".json")
    if spec["driver"] not in DRIVERS:
        print(f"unknown driver {spec['driver']!r}", file=sys.stderr)
        return 2
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"no program under {src}", file=sys.stderr)
        return 2
    sys.path[:0] = [HERE, src]
    try:
        check_device(cell["chips"], require_chip)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    import jax
    kind = jax.devices()[0].device_kind
    try:
        peaks = peaks_for(kind) if args.trace else None
    except KeyError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(f"compile cache: {enable_cache()}", file=sys.stderr, flush=True)

    ctx = Context(cell, model, spec, args.seed, args.seconds,
                  bool(args.trace))
    driver = importlib.import_module(spec["driver"])
    res = driver.run(ctx)
    ctx.gc_report()

    rows = judge(limits_dir, cell["name"], res["numbers"])
    correct = all(ok for *_, ok in rows) and res["failed"] == 0
    if args.trace:
        metrics = layer_metrics(bench, cell, ctx, peaks)
    else:
        metrics = {k: {"value": v, "unit": u}
                   for k, (v, u) in res["metrics"].items()}
        metrics["setup_s"] = {"value": ctx.setup_s, "unit": "s"}
    device = dict(res["stats"])
    out = {"correct": correct, "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics, "device": device}
    if args.trace and ctx.trace_summary is not None:
        s = ctx.trace_summary
        device.update(busy_s=s["busy_s"], window_s=s["window_s"])
        out["breakdown"] = {"device_ops": [list(x) for x in s["device_ops"]],
                            "idle_gaps": [list(x) for x in s["idle_gaps"]]}
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim, _ in rows}
    for n, v, lim, ok in rows:
        verdict = "not compared" if ok and lim is None else \
            ("ok" if ok else "FAIL")
        print(f"check {n}: {v!r} limit {lim!r} {verdict}", file=sys.stderr,
              flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
