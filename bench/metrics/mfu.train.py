"""Forward and backward operations of a train step (``counts/forward.py``)
times the traced train steps, over those steps' device time (their XLA
module ``train_step``, from the trace) times the chip's peak."""


def read(inp):
    s, tokens = inp["trace"], inp.get("train_tokens")
    if s is None or not tokens:
        return None
    t, n = inp["trace_mod"].module_time(s, "train_step")
    if not n:
        return None
    tr = inp["traffic"]
    ops = inp["counts"].train_step_ops(inp["model"], tr["batch"], tr["seq"])
    return 100.0 * ops * n / (t * inp["peaks"]["bf16_flops_per_s"])
