"""Median over requests of the wait from a request's due time to its
admission (the step in which it left the engine's queue), harness clock."""
import statistics


def read(inp):
    waits = inp.get("queue_waits")
    return 1e3 * statistics.median(waits) if waits else None
