"""Reduction of a profiler trace to what the per-layer metrics read.

``load_events`` flattens the ``.xplane.pb`` that ``jax.profiler`` writes
into (plane, line, name, start_ns, dur_ns) tuples; ``reduce`` works on
such a list, so a small recorded list checks it (tests/).

- window: the span of the harness's ``bench.window`` annotation;
- busy: the union of the device's op intervals inside the window;
- time and count per XLA module and per op name, on the device;
- idle: the gaps in the union, each charged to the innermost harness
  span (``bench.*``) that covers its middle on the host.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re

WINDOW_SPAN = "bench.window"
PALLAS = 'custom_call_target="tpu_custom_call"'

_SUFFIX = re.compile(r"(\.\d+|\(\d+\)|_\d+)+$")


def load_events(trace_dir: str) -> list:
    import jax
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out = []
    for path in paths:
        data = jax.profiler.ProfileData.from_file(path)
        for plane in data.planes:
            for line in plane.lines:
                for ev in line.events:
                    out.append((plane.name, line.name, ev.name,
                                int(ev.start_ns), int(ev.duration_ns)))
    return out


def base_name(name: str) -> str:
    """Op or module name without its HLO text and numeric instance
    suffix: ``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion``."""
    short = name.split(" = ", 1)[0].lstrip("%")
    return _SUFFIX.sub("", short) or short


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def is_device_plane(plane: str) -> bool:
    return plane.startswith("/device:") and "CPU" not in plane


def reduce(events: list) -> dict:
    windows = [(s, s + d) for p, _, n, s, d in events
               if n == WINDOW_SPAN and not is_device_plane(p)]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW_SPAN} span")
    w0, w1 = windows[0]
    dev_planes = sorted({p for p, *_ in events if is_device_plane(p)})
    ops = collections.defaultdict(lambda: [0.0, 0])
    modules = collections.defaultdict(lambda: [0.0, 0])
    busy_per_plane, gaps = [], []
    for plane in dev_planes:
        op_iv = []
        for p, line, name, s, d in events:
            if p != plane or s + d <= w0 or s >= w1:
                continue
            if line == "XLA Ops":
                op_iv.append((max(s, w0), min(s + d, w1)))
                ops[name][0] += d * 1e-9
                ops[name][1] += 1
            elif line == "XLA Modules":
                modules[base_name(name)][0] += d * 1e-9
                modules[base_name(name)][1] += 1
        merged = _union(op_iv)
        busy_per_plane.append(sum(e - s for s, e in merged) * 1e-9)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    # the harness's spans run one after another on one thread
    host = sorted((s, s + d, n) for p, _, n, s, d in events
                  if not is_device_plane(p) and n.startswith("bench.")
                  and n != WINDOW_SPAN)
    starts = [h[0] for h in host]
    idle = collections.Counter()
    for s, e in gaps:
        mid = (s + e) / 2
        i = bisect.bisect_right(starts, mid) - 1
        name = host[i][2] if i >= 0 and host[i][1] > mid else \
            "outside bench spans"
        idle[name] += (e - s) * 1e-9
    n_dev = max(len(dev_planes), 1)
    by_base = collections.defaultdict(float)
    for name, (t, _) in ops.items():
        by_base[base_name(name)] += t
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy_per_plane) / n_dev,
        "n_devices": len(dev_planes),
        "ops": {k: tuple(v) for k, v in ops.items()},
        "modules": {k: tuple(v) for k, v in modules.items()},
        "device_ops": sorted(by_base.items(), key=lambda kv: -kv[1])[:10],
        "idle_gaps": sorted(idle.items(), key=lambda kv: -kv[1])[:10],
    }


def op_time(summary: dict, match) -> tuple:
    """(seconds, count) of the device ops whose name (the op's HLO text,
    which also names its operands) holds ``match``, or for which
    ``match(name)`` is true."""
    t = n = 0
    for name, (s, c) in summary["ops"].items():
        if (match(name) if callable(match) else match in name):
            t, n = t + s, n + c
    return t, n


def module_time(summary: dict, needle: str) -> tuple:
    t = n = 0
    for name, (s, c) in summary["modules"].items():
        if needle in name:
            t, n = t + s, n + c
    return t, n
