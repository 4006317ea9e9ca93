"""The reduction of the program's spans and named scopes, on hand-made
traces, on a recorded chip trace and on a traced run on the CPU."""
import json
import os

import pytest

import run
import spans

DEV, HOST, MAIN = "/device:TPU:0", "/host:CPU", "python3"
MS = 1_000_000
BODY = "jit(decode_fn)/layers/while/body/closed_call"

# (instruction, result type, metadata name) of the hand-made modules
DECODE = [("%copy.1", "bf16[8,128]{1,0}", "jit(decode_fn)/layers/copy"),
          ("%while.5", "(s32[], bf16[8,128]{1,0})",
           "jit(decode_fn)/layers/while"),
          ("%copy.2", "bf16[4,8,128]{2,1,0:T(8,128)}",
           BODY + "/attn/kv_write/scatter"),
          ("%closed_call.3", "bf16[8,3,64]{2,1,0}",
           BODY + "/attn/paged_gqa/pallas_call"),
          ("%salr_matmul.4", "bf16[8,576]{1,0}", BODY + "/mlp/pallas_call"),
          # a layout copy the compiler put in: no metadata
          ("%copy.9", "bf16[8,576]{0,1}", None),
          ("%fusion.6", "bf16[8,49152]{1,0}", "jit(decode_fn)/head/dot")]
# two buckets of one prefill: same instruction names, other shapes
PREFILL = {16: [("%fusion.1", "bf16[1,16,576]{2,1,0}",
                 "jit(prefill_fn)/layers/while/body/attn/dot_general")],
           32: [("%fusion.1", "bf16[1,32,576]{2,1,0}",
                 "jit(prefill_fn)/layers/while/body/mlp/dot_general")]}


def _text(module, instrs):
    lines = [f"HloModule {module}, entry_computation_layout={{()->()}}",
             "", "ENTRY %main.1 () -> () {"]
    for name, ty, op_name in instrs:
        meta = f', metadata={{op_name="{op_name}"}}' if op_name else ""
        lines.append(f"  {name} = {ty} op(%p.0){meta}")
    return "\n".join(lines + ["}"])


HLO = spans.hlo_names([_text("jit_decode_fn", DECODE)]
                      + [_text("jit_prefill_fn", v)
                         for v in PREFILL.values()])


def _op(instrs, name, start_ms, dur_ms):
    """A device op event, named as the trace names it: the instruction
    with its operands' types."""
    ty = next(t for n, t, _ in instrs if n == name)
    return (DEV, "XLA Ops", f"{name} = {ty} op(bf16[8,8]{{1,0}} %p.0)",
            start_ms * MS, dur_ms * MS)


def _serving():
    """One tick: an admission, then a decode whose step runs a layer
    scan (``while``) around attention and MLP ops."""
    return [
        (HOST, MAIN, "bench.window", 0, 100 * MS),
        (HOST, MAIN, "bench.step", 0, 90 * MS),
        (HOST, MAIN, "engine.step", 1 * MS, 88 * MS),
        (HOST, MAIN, "engine.admit#rid=3#", 1 * MS, 30 * MS),
        (HOST, MAIN, "engine.page_plan", 1 * MS, 2 * MS),
        (HOST, MAIN, "engine.prefill", 3 * MS, 4 * MS),
        (HOST, MAIN, "engine.admit_wait", 7 * MS, 20 * MS),
        # [27, 31) ms: the admission goes on after its wait
        (HOST, MAIN, "engine.decode", 31 * MS, 58 * MS),
        (HOST, MAIN, "engine.decode_dispatch", 31 * MS, 4 * MS),
        (HOST, MAIN, "engine.decode_wait", 35 * MS, 50 * MS),
        (HOST, MAIN, "engine.emit", 85 * MS, 4 * MS),
        (HOST, "other thread", "engine.step", 0, 100 * MS),
        (DEV, "XLA Modules", "jit_prefill_fn(11)", 8 * MS, 10 * MS),
        _op(PREFILL[32], "%fusion.1", 8, 10),
        (DEV, "XLA Modules", "jit_decode_fn(2)", 40 * MS, 40 * MS),
        _op(DECODE, "%copy.1", 40, 2),
        _op(DECODE, "%while.5", 42, 30),
        _op(DECODE, "%copy.2", 42, 5),
        _op(DECODE, "%closed_call.3", 47, 10),
        _op(DECODE, "%salr_matmul.4", 60, 8),
        _op(DECODE, "%copy.9", 68, 1),
        _op(DECODE, "%fusion.6", 74, 6),
        (DEV, "XLA Ops", "%fusion.7 = f32[8]{0} op()", 95 * MS, 2 * MS),
    ]


def test_idle_goes_to_the_innermost_span_over_its_middle():
    s = spans.reduce(_serving(), HLO)
    idle = dict(s["idle_spans"])
    # [0, 8): the middle (4 ms) lies in the prefill dispatch
    assert idle["engine.prefill"] == pytest.approx(0.008)
    # [18, 40): the middle (29 ms) lies after the admission's wait ended,
    # inside the admission itself
    assert idle["engine.admit"] == pytest.approx(0.022)
    # [72, 74) lies in the decode wait; [80, 95) has its middle (87.5 ms)
    # in the emit loop; [97, 100) in no span but the window
    assert idle["engine.decode_wait"] == pytest.approx(0.002)
    assert idle["engine.emit"] == pytest.approx(0.015)
    assert idle[spans.OUTSIDE] == pytest.approx(0.003)
    assert sum(idle.values()) == pytest.approx(0.1 - 0.050)
    # spans on another thread are not the engine's main thread
    assert len(s["spans"]["engine.step"]) == 1


def test_scope_self_times_add_up_to_the_module_busy_time():
    s = spans.reduce(_serving(), HLO)
    dec = s["modules"]["jit_decode_fn"]
    assert dec["count"] == 1
    assert dec["busy_s"] == pytest.approx(0.038)
    assert sum(dec["scopes"].values()) == pytest.approx(dec["busy_s"])
    # the scan's own time is what its body's ops leave of it
    assert dec["scopes"] == pytest.approx({
        "layers": 0.002 + 0.030 - 0.005 - 0.010 - 0.008,  # copy.9 too
        "layers/attn/kv_write": 0.005, "layers/attn/paged_gqa": 0.010,
        "layers/mlp": 0.008, "head": 0.006})
    assert s["scopes"]["paged_gqa"] == pytest.approx(0.010)
    assert s["scopes"][spans.UNSCOPED] == pytest.approx(0.002)
    assert dict(s["device_scopes"])["layers"] == pytest.approx(0.009)
    # the prefill ran its 32-token bucket: its op is the MLP's
    assert s["modules"]["jit_prefill_fn"]["scopes"] == pytest.approx(
        {"layers/mlp": 0.010})


def test_an_event_and_its_module_line_give_one_key():
    line = ('  ROOT %copy-start.2 = (s32[1,64]{1,0:T(1,128)S(1)}, '
            'u32[]{:S(2)}) copy-start(%tokens.1), metadata={op_name="x"}')
    event = ('%copy-start.2 = (s32[1,64]{1,0:T(1,128)S(1)}, u32[]{:S(2)}) '
             'copy-start(s32[1,64]{1,0:T(1,128)} %tokens.1)')
    assert spans.instruction_key(line) == spans.instruction_key(event) == (
        "%copy-start.2", ("s32[1,64]", "u32[]"))
    assert spans.instruction_key("HloModule jit_f") is None


def test_an_instruction_without_metadata_takes_its_operands():
    text = "\n".join([
        "HloModule jit_decode_fn",
        '  %gte.1 = bf16[30,8]{1,0} get-tuple-element(%while.5), index=2, '
        'metadata={op_name="jit(decode_fn)/layers/while"}',
        '  %copy.2 = bf16[30,8]{0,1} copy(%gte.1), backend_config={}'])
    names, = spans.hlo_names([text])["jit_decode_fn"]
    assert names[("%copy.2", ("bf16[30,8]",))] == \
        "jit(decode_fn)/layers/while"
    assert spans.scope_path(names[("%copy.2", ("bf16[30,8]",))]) == "layers"


def test_scope_path_reads_transformed_scopes():
    assert spans.scope_path(
        "jit(train_step)/transpose(jvp(layers))/while/body/checkpoint/"
        "attn/salr_base_decode/gather") == "layers/attn/salr_base_decode"
    assert spans.scope_path("jit(f)/while/body/add") == ""
    # a parameter's name, and the primitive at the end, are no scopes
    assert spans.scope_path("state.trainable['groups'][0][0]['mlp']") == ""
    assert spans.scope_path("jit(f)/layers/head") == "layers"
    # merged ops: the first name's scopes, not a scope of the second's
    assert spans.scope_path(
        "jit(decode_fn)/layers/while/body/closed_call/attn/kv_write/"
        "squeeze;attn/reshape") == "layers/attn/kv_write"
    assert spans.innermost("") == spans.UNSCOPED


def test_the_four_readings():
    s = spans.reduce(_serving(), HLO)
    # host time of the tick: 88 ms less its 20 + 50 ms of waits
    assert spans.engine_host_ms(s) == pytest.approx(18.0)
    assert spans.decode_attn_ms(s) == pytest.approx(15.0)
    assert spans.h2d_per_tick({"h2d_uploads": 12, "n_decode_ticks": 4}) \
        == 3.0
    assert spans.h2d_per_tick({}) is None
    step = [("%fusion.1", "bf16[576,1536]{1,0}",
             "jit(train_step)/transpose(jvp(layers))/while/body/mlp/"
             "salr_base_decode/and"),
            ("%fusion.2", "bf16[8,512,1536]{2,1,0}",
             "jit(train_step)/layers/while/body/mlp/salr_base/dot_general"),
            ("%fusion.3", "f32[]", None)]
    train = [(HOST, MAIN, "bench.window", 0, 10 * MS),
             (DEV, "XLA Modules", "jit_train_step(4)", 0, 10 * MS),
             _op(step, "%fusion.1", 0, 6), _op(step, "%fusion.2", 6, 2),
             _op(step, "%fusion.3", 8, 2)]
    t = spans.reduce(train, spans.hlo_names([_text("jit_train_step",
                                                    step)]))
    assert spans.base_decode_share(t) == pytest.approx(60.0)
    # a trace without the program's spans and scopes reads nothing
    bare = [e for e in _serving() if not e[2].startswith("engine.")]
    b = spans.reduce(bare, spans.hlo_names(
        [_text("jit_decode_fn", [(n, t, None) for n, t, _ in DECODE])]))
    assert spans.engine_host_ms(b) is None
    assert spans.decode_attn_ms(b) is None
    assert spans.base_decode_share(b) is None


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        spans.reduce(_serving()[1:], HLO)


def test_a_traced_serving_window_on_the_cpu(tmp_path, monkeypatch):
    """A short traced window of the two-layer stand-in: every tick's
    engine.step lies in a bench.step, and the engine's count of host
    arrays is 2 a tick, 5 an admission and 1 a finish."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    data = os.path.join(os.path.dirname(__file__), "data")
    model = run.load_json(data, "tiny_model.json")["model"]
    spec = run.load_json(data, "tiny_chat.json")
    ctx = run.Context({"name": "tiny.chat", "chips": 1}, model, spec,
                      2 ** 33 + 5, 2.0, True)
    ctx.trace_dir = str(tmp_path / "trace")
    metrics, counters = spans.traced_window(ctx)
    assert set(metrics) == {"ttft_p95_s", "itl_p95_ms"}
    events, texts = spans.load(ctx.trace_dir)
    hlo = spans.hlo_names(texts)
    assert {"jit_decode_fn", "jit_prefill_fn", "jit_insert_fn"} <= set(hlo)
    # the CPU's trace has no device plane; the compiled decode step names
    # its paged attention kernel's scopes
    assert any("paged_gqa" in spans.scope_path(n) for v in hlo["jit_decode_fn"]
               for n in v.values())
    s = spans.reduce(events, hlo)
    steps, outer = s["spans"]["engine.step"], s["spans"]["bench.step"]
    assert steps and len(steps) <= len(outer)
    assert all(any(a <= s0 and e0 <= b for a, b in outer)
               for s0, e0 in steps)
    assert spans.engine_host_ms(s) > 0
    ticks, admits = counters["n_decode_ticks"], counters["n_prefills"]
    assert counters["h2d_uploads"] == (2 * ticks + 5 * admits
                                       + counters["n_finished"])
    assert spans.h2d_per_tick(counters) == pytest.approx(
        counters["h2d_uploads"] / ticks)


RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "serve_spans_slice.json")


def test_recorded_chip_slice_with_spans_and_scopes():
    with open(RECORDED) as f:
        rec = json.load(f)
    events = [tuple(e) for e in rec["events"]]
    s = spans.reduce(events, spans.hlo_names(rec["hlo"]))
    want = rec["expect"]
    assert s["window_s"] == pytest.approx(want["window_s"])
    assert s["scopes"] == pytest.approx(want["scopes"], rel=1e-9)
    assert dict(s["idle_spans"]) == pytest.approx(want["idle_spans"],
                                                  rel=1e-9)
    dec = s["modules"]["jit_decode_fn"]
    assert dec["busy_s"] == pytest.approx(
        want["modules"]["jit_decode_fn"]["busy_s"])
    # every op of the decode step is named by the compiled text, its
    # scopes' self times add up to its busy time, and under 5 % of it is
    # under no scope
    assert dec["named_s"] == pytest.approx(dec["busy_s"])
    assert sum(dec["scopes"].values()) == pytest.approx(dec["busy_s"])
    assert dec["scopes"].get("", 0.0) < 0.05 * dec["busy_s"]
    assert dec["scopes"]["layers/attn/paged_gqa"] > 0
    # the device idles only while the host is in the engine's spans
    assert set(dict(s["idle_spans"])) <= {
        n for n in s["spans"] if n.startswith("engine.")}
