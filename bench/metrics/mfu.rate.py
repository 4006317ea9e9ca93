"""Decode ticks' required operations (``counts/forward.py`` at the live
rows and contexts) over their decode modules' device time times the
chip's peak."""


def read(inp):
    s, steps = inp["trace"], inp.get("steps")
    if s is None or not steps:
        return None
    t, n = inp["trace_mod"].module_time(s, "decode_fn")
    if not n:
        return None
    ops = sum(inp["counts"].decode_ops(inp["model"], ctxs)
              for _, ctxs in steps if ctxs)
    return 100.0 * ops / (t * inp["peaks"]["bf16_flops_per_s"])
