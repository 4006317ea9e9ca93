"""Share of the traced window in which no operation ran on the device:
1 - (union of device op intervals / window)."""


def read(inp):
    s = inp["trace"]
    if s is None or s["window_s"] <= 0 or not s["n_devices"]:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
