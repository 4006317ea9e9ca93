"""The trace reduction, on a hand-made trace and on a recorded one."""
import json
import os

import pytest

import devtrace

DEV, HOST = "/device:TPU:0", "/host:CPU"
MS = 1_000_000


def _hand_made():
    return [
        (HOST, "python", "bench.window", 0, 100 * MS),
        (HOST, "python", "bench.step", 0, 60 * MS),
        (HOST, "python", "bench.sleep", 60 * MS, 40 * MS),
        (DEV, "XLA Modules", "jit_decode_fn(12)", 5 * MS, 30 * MS),
        (DEV, "XLA Ops", "fusion.1", 5 * MS, 10 * MS),
        (DEV, "XLA Ops", "%salr_matmul.3 = bf16[128,192] custom-call(), "
         + devtrace.PALLAS, 10 * MS, 20 * MS),                # overlaps
        (DEV, "XLA Ops", "%slice.7 = bf16[64,192] slice(%salr_matmul.3)",
         30 * MS, 0),
        (DEV, "XLA Ops", "fusion.2", 70 * MS, 5 * MS),
        (DEV, "XLA Ops", "fusion.9", 120 * MS, 5 * MS),  # after the window
    ]


def test_union_modules_and_idle_attribution():
    s = devtrace.reduce(_hand_made())
    assert s["window_s"] == pytest.approx(0.1)
    # busy: [5, 30) and [70, 75) ms
    assert s["busy_s"] == pytest.approx(0.030)
    assert devtrace.module_time(s, "decode_fn") == pytest.approx((0.03, 1))
    # an op that reads the kernel's output names it, so match its own name
    assert devtrace.op_time(s, "salr_matmul")[1] == 2
    assert devtrace.op_time(
        s, lambda n: devtrace.base_name(n) == "salr_matmul") == \
        pytest.approx((0.02, 1))
    idle = dict(s["idle_gaps"])
    # a gap goes whole to the span over its middle: [0, 5) and [30, 70)
    # to the step, [75, 100) to the sleep
    assert idle["bench.step"] == pytest.approx(0.045)
    assert idle["bench.sleep"] == pytest.approx(0.025)
    assert dict(s["device_ops"])["fusion"] == pytest.approx(0.015)


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        devtrace.reduce(_hand_made()[1:])


RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "serve_trace_slice.json")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace slice")
def test_recorded_chip_trace_slice():
    with open(RECORDED) as f:
        rec = json.load(f)
    events = [tuple(e) for e in rec["events"]]
    s = devtrace.reduce(events)
    for key, want in rec["expect"].items():
        assert s[key] == pytest.approx(want, rel=1e-9), key
    assert 0 < s["busy_s"] <= s["window_s"]
    pallas = [e for e in events if devtrace.PALLAS in e[2]]
    salr = devtrace.op_time(s, lambda n: devtrace.PALLAS in n
                            and devtrace.base_name(n) == "salr_matmul")
    attn = devtrace.op_time(s, lambda n: devtrace.PALLAS in n
                            and devtrace.base_name(n) != "salr_matmul")
    assert salr[1] + attn[1] == len(pallas)
    assert list(salr) == pytest.approx(rec["kernels"]["salr_matmul"])
    assert list(attn) == pytest.approx(rec["kernels"]["attention"])
    # seven SALR linears to each layer's attention call, cut at the edges
    assert salr[1] > 3 * attn[1] > 0


def test_train_mfu_reads_the_train_step_device_time():
    import run
    counts = run.load_module(os.path.join(run.HERE, "counts", "forward.py"),
                             "bench_counts_forward")
    reader = run.load_module(os.path.join(run.HERE, "metrics",
                                          "mfu.train.py"), "mfu_train")
    events = [(HOST, "python", "bench.window", 0, 100 * MS),
              (HOST, "python", "bench.train_step", 0, 100 * MS),
              (DEV, "XLA Modules", "jit_train_step(7)", 2 * MS, 40 * MS),
              (DEV, "XLA Modules", "jit_train_step(7)", 50 * MS, 40 * MS)]
    model = run.load_json(run.HERE, "configs", "smollm_135m.json")["model"]
    inp = {"trace": devtrace.reduce(events), "trace_mod": devtrace,
           "counts": counts, "model": model, "train_tokens": 4096,
           "traffic": {"batch": 8, "seq": 512},
           "peaks": {"bf16_flops_per_s": 197e12}}
    ops = counts.train_step_ops(model, 8, 512)
    # two steps over their 80 ms on the device, not the 100 ms window
    assert reader.read(inp) == pytest.approx(
        100.0 * 2 * ops / (0.08 * 197e12))
    assert reader.read(dict(inp, trace=None)) is None
