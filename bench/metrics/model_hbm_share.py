"""Share of the chip's HBM bandwidth reached by the decode steps: the
bytes the decode steps run in the traced part of the window must move
(``bench/lm_cost.py``: the weights read, with the routed experts touched
at the window's mean, the live rows' latent cache prefixes read and the
entries written) over the peak bandwidth times the device time of the
``jit_serve_step`` program in the trace."""


def read(record, trace, ctx):
    start = record.get("trace_start_s")
    if trace is None or start is None or "events" not in record:
        return None
    device_s = trace["programs_s"].get("jit_serve_step", 0.0)
    nbytes = sum(b for t, kind, _, b in record["events"]
                 if kind == "decode" and t >= start)
    if device_s <= 0 or nbytes <= 0:
        return None
    return 100.0 * nbytes / (ctx["peaks"]["hbm_bytes_per_s"] * device_s)
