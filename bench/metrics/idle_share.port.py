"""Share of the traced window in which no operation ran on the device:
100 x (1 - union of device-op intervals / window), from the trace."""


def read(record, trace, ctx):
    if trace is None or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
