"""Share of the HBM roofline reached by the ported programs: the payload
bytes that the requests served in the traced part of the window read and
write (``bench/cost.py``, from their shapes), at the chip's peak
bandwidth, over the device time of the programs in the trace.  Memory
bounds every such kernel: it does a few operations per element moved.

Every program that runs on the device in a port cell is a batched port
program; the trace names each ``jit__unnamed_function``, so they are
taken together."""


def read(record, trace, ctx):
    start = record.get("trace_start_s")
    if trace is None or start is None:
        return None
    device_s = sum(trace["programs_s"].values())
    if device_s <= 0:
        return None
    nbytes = sum(b for t_sub, b in record["slates"] if t_sub >= start)
    return 100.0 * nbytes / ctx["peaks"]["hbm_bytes_per_s"] / device_s
