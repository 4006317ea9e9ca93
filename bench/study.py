"""Calibration runs that the benchmark's own runs never make.

    python3 bench/study.py sweep <cell> <seeds> <seconds> <rate> [<rate> ...]
    python3 bench/study.py check <cell> <seconds> <control> <seed> [<seed> ...]

``sweep`` warms one serving engine and offers the cell's open-loop mix
at each rate in turn, on each of the comma-separated seeds, printing
the queue's growth, the slots in use and the tails, to find the highest
rate the engine sustains.  ``check`` runs the cell once per
seed with a short window and prints each number compared, beside the
same number read from a control put in the program's place (``fp8``:
the reference at the precision below bfloat16; ``half_batch``: a train
step over half of each batch).
"""
from __future__ import annotations

import json
import sys
import time

import run


def _ctx(cell_name, seed, seconds, trace=False):
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == cell_name)
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    model = run.load_json(run.ROOT, conf["file"])["model"]
    spec = run.load_json(run.HERE, "traffic", cell["traffic"] + ".json")
    return run.Context(cell, model, spec, seed, seconds, trace)


def sweep(cell_name, seconds, rates, seeds):
    """Each rate on each seed, one warmed engine for all."""
    import numpy as np
    import serve
    ctx = _ctx(cell_name, seeds[0], seconds)
    eng = serve.build(ctx.model, ctx.traffic, seeds[0])
    serve.warm(eng, dict(ctx.traffic, rate_per_s=max(rates)),
               ctx.model["vocab_size"], seconds)
    ctx.mark_setup()
    for rate in rates:
        for seed in seeds:
            t = dict(ctx.traffic, rate_per_s=rate)
            eng.reset()
            t0 = time.perf_counter()
            w = serve.window(ctx, eng, t, seed, seconds)
            dur = time.perf_counter() - t0
            due, adm = w["due"], w["admitted"]
            half = seconds / 2
            early = [adm[r] - due[r] for r in adm if due[r] < half]
            late = [adm[r] - due[r] for r in adm if due[r] >= half]
            inside = [(d, a) for tm, d, a in w["depth"] if tm <= seconds]
            depth = [d for d, _ in inside]
            qs = [depth[int(len(depth) * f) - 1]
                  for f in (0.25, 0.5, 0.75, 1.0)]
            ttft = [w["first"][r] - due[r] for r in due]
            print(json.dumps({
                "rate": rate, "seed": seed, "requests": len(due),
                "run_s": dur,
                "ttft_p50_s": float(np.median(ttft)),
                "ttft_p95_s": w["metrics"]["ttft_p95_s"][0],
                "itl_p95_ms": w["metrics"]["itl_p95_ms"][0],
                "wait_med_first_half_s":
                    float(np.median(early)) if early else None,
                "wait_med_second_half_s":
                    float(np.median(late)) if late else None,
                "pending_at_quarters": qs,
                "active_slots_mean": float(np.mean([a for _, a in inside])),
                "steps": len(depth)}), flush=True)
            ctx.gc_report()
            ctx.gc_watch.pauses.clear()


def check(cell_name, seconds, control, seeds):
    for seed in seeds:
        ctx = _ctx(cell_name, seed, seconds)
        ctx.controls = (control,)
        driver = run.importlib.import_module(ctx.traffic["driver"])
        res = driver.run(ctx)
        print(json.dumps({"seed": seed, "setup_s": ctx.setup_s,
                          "numbers": res["numbers"],
                          "metrics": res["metrics"]}), flush=True)


def main(argv):
    import os
    sys.path[:0] = [run.HERE, os.path.join(run.ROOT, "src")]
    run.check_device(1, True)
    run.enable_cache()
    if argv[0] == "sweep":
        sweep(argv[1], float(argv[3]), [float(r) for r in argv[4:]],
              [int(s) for s in argv[2].split(",")])
    elif argv[0] == "check":
        check(argv[1], float(argv[2]), argv[3], [int(s) for s in argv[4:]])
    else:
        raise SystemExit(__doc__)


if __name__ == "__main__":
    main(sys.argv[1:])
