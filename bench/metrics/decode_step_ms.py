"""Device time per call of the engine's jitted decode step (its XLA
module ``decode_fn``), from the trace."""


def read(inp):
    s = inp["trace"]
    if s is None:
        return None
    t, n = inp["trace_mod"].module_time(s, "decode_fn")
    return 1e3 * t / n if n else None
