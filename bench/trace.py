"""Reduction of a profiler trace to device busy time, time per device
operation and program, and idle gaps labelled by the host span open in
them.

The trace holds one plane per device (``/device:TPU:<i>``) with a line of
operations (``XLA Ops``) and a line of whole programs (``XLA Modules``),
and host planes whose thread lines carry the benchmark's own spans
(``bench.<what>``, written by ``jax.profiler.TraceAnnotation``).  The
traced window is the host span ``bench.window``.
"""
from __future__ import annotations

import collections
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."

Interval = Tuple[float, float]


def load(log_dir: str):
    """The newest trace written under ``log_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return ProfileData.from_file(max(files, key=os.path.getmtime))


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping [start, end) intervals, sorted."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s: float, e: float, w: Interval) -> Optional[Interval]:
    s, e = max(s, w[0]), min(e, w[1])
    return (s, e) if e > s else None


def gaps(busy: Sequence[Interval], window: Interval) -> List[Interval]:
    """The parts of ``window`` that no interval of ``busy`` covers."""
    out, t = [], window[0]
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < window[1]:
        out.append((t, window[1]))
    return out


def _label(t: float, spans: Sequence[Tuple[str, float, float]]) -> str:
    """The innermost benchmark span open at ``t`` (the shortest one that
    covers it), or ``host:unmarked``."""
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "host:unmarked"


def short_name(name: str) -> str:
    """``%fusion.62 = bf16[...] fusion(...)`` -> ``fusion.62``;
    ``jit_prefill(3898...)`` -> ``jit_prefill``."""
    name = name.split(" = ", 1)[0].lstrip("%")
    if name.endswith(")") and "(" in name:
        name = name[:name.index("(")]
    return name


def self_times(events: Sequence[Tuple[str, float, float]]):
    """Per event, its duration less that of the events nested directly
    inside it on the same line (a loop op spans its body's ops)."""
    out, stack = [], []
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][2] <= s:
            out.append(stack.pop())
        rec = [name, s, e, e - s]
        if stack:
            stack[-1][3] -= e - s
        stack.append(rec)
    out.extend(stack)
    return [(name, self_ns) for name, _, _, self_ns in out]


def reduce(profile, top: int = 10) -> Dict:
    """Busy seconds (the union of program intervals, or of op intervals
    where the trace has no programs) and window seconds, averaged over the
    devices traced; device self-seconds and counts per operation, device
    seconds and counts per program, and the ``top`` longest idle gaps
    with their host labels, all clipped to the window.

    Raises ``ValueError`` when the trace holds no window span or no device
    plane: a run that traced nothing has nothing to report."""
    spans, window = [], None
    devices = []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_SPAN:
                    window = (ev.start_ns, ev.end_ns)
                elif ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name, ev.start_ns, ev.end_ns))
    if window is None:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    if not devices:
        raise ValueError("trace has no TPU device plane")

    ops, ops_n = collections.Counter(), collections.Counter()
    programs, programs_n = collections.Counter(), collections.Counter()
    busy_ns, all_gaps = 0.0, []
    for plane in devices:
        intervals = {OPS_LINE: [], MODULES_LINE: []}
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            evs = []
            for ev in line.events:
                c = _clip(ev.start_ns, ev.end_ns, window)
                if c is not None:
                    evs.append((short_name(ev.name), c[0], c[1]))
            intervals[line.name] += [(s, e) for _, s, e in evs]
            if line.name == OPS_LINE:
                for name, ns in self_times(evs):
                    ops[name] += ns
                    ops_n[name] += 1
            else:
                for name, s, e in evs:
                    programs[name] += e - s
                    programs_n[name] += 1
        # whole programs bound the busy time; the op line can overflow the
        # profiler's buffers (a strip loop records every trip's ops)
        busy = union(intervals[MODULES_LINE] or intervals[OPS_LINE])
        busy_ns += sum(e - s for s, e in busy)
        all_gaps += gaps(busy, window)
    n = len(devices)
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": (window[1] - window[0]) * 1e-9,
        "busy_s": busy_ns / n * 1e-9,
        "devices": n,
        "ops_s": {k: v / n * 1e-9 for k, v in ops.items()},
        "ops_n": {k: v / n for k, v in ops_n.items()},
        "programs_s": {k: v / n * 1e-9 for k, v in programs.items()},
        "programs_n": {k: v / n for k, v in programs_n.items()},
        "idle_gaps": [[_label((s + e) / 2, spans), (e - s) * 1e-9]
                      for s, e in longest],
    }


def breakdown(summary: Dict, top: int = 10) -> Dict:
    """The contract's ``breakdown``: the device operations that took the
    most time (self time; programs where the trace has no operations),
    and the longest idle gaps by what the host was doing."""
    table = summary["ops_s"] or summary["programs_s"]
    ops = sorted(table.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": summary["idle_gaps"][:top]}
