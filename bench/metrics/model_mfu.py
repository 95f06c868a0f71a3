"""Share of the chip's bfloat16 peak reached by the model's steps: the
operations of the prefills and decode steps run in the traced part of the
window (``bench/lm_cost.py``, from the batches' shapes, live rows only)
over the peak times the device time of the ``jit_prefill`` and
``jit_serve_step`` programs in the trace."""

PROGRAMS = ("jit_prefill", "jit_serve_step")


def read(record, trace, ctx):
    start = record.get("trace_start_s")
    if trace is None or start is None or "events" not in record:
        return None
    device_s = sum(trace["programs_s"].get(p, 0.0) for p in PROGRAMS)
    flops = sum(f for t, _, f, _ in record["events"] if t >= start)
    if device_s <= 0 or flops <= 0:
        return None
    return 100.0 * flops / (ctx["peaks"]["bf16_flops_per_s"] * device_s)
