"""``PortEngine``'s host spans against the device's idle time, from one
profiler trace; and a run of a port cell that reports them.

The engine marks each stage of ``PortEngine.submit`` with a
``jax.profiler.TraceAnnotation`` named ``port.<stage>`` (``port.submit``
> ``port.plan``; ``port.chunk`` > ``port.pad``, ``port.h2d``,
``port.launch``, ``port.fetch``, ``port.slice``; ``port.fallback``).  They
lie on the profiler's host plane beside the benchmark's ``bench.*`` spans,
on the device planes' clock.  :func:`reduce` adds to what
:func:`bench.trace.reduce` gives:

- ``idle_by_span``: idle device seconds in the window, split over time by
  the innermost open ``bench.*`` or ``port.*`` span (a gap is divided
  wherever that span changes); time under no span is ``host:unmarked``;
- ``span_s`` / ``span_n``: self seconds and count per span name;
- ``slate_waits``: per ``port.chunk``, ``[seconds, rows]``: from its
  slate's ``port.submit`` start to its ``port.launch`` start, with its
  live rows;
- ``idle_gaps``: the same longest gaps as ``bench.trace.reduce``, each
  labelled by the innermost span of either prefix at its midpoint.

:func:`idle_engine_port`, :func:`slate_wait_p95_ms` and
:func:`transfer_overhead` compute the per-layer metrics of the same
names, as a reader in ``bench/metrics`` would; each returns None where the
trace or the record lacks what it reads (a program without the spans or
the ``h2d_bytes``/``d2h_bytes`` counters).

    python3 bench/spans.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1> [--out <file>]

runs a cell as ``run.py`` does and prints one JSON line: the latency
percentiles over the window and over its traced part, the engine's
counters over the window, the three metrics above and the cell's own
per-layer metrics, and with ``--trace 1`` the split of the device's idle
time and the host's self time per span.  The benchmark's own runs never
call this.
"""
from __future__ import annotations

import bisect
import collections
import heapq
import json
import os
import sys
from typing import Dict, List, Optional, Sequence, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import trace  # noqa: E402

PORT_PREFIX = "port."
PREFIXES = (trace.SPAN_PREFIX, PORT_PREFIX)
UNMARKED = "host:unmarked"

# (name, start_ns, end_ns, args, line): ``line`` tells the host threads
# apart, since nesting holds only within one
Span = Tuple[str, float, float, Dict, int]


def host_spans(profile) -> Tuple[Tuple[float, float], List[Span]]:
    """The window and every ``bench.*``/``port.*`` span of the host
    planes but the window itself, clipped to it."""
    window, raw = None, []
    line_id = 0
    for plane in profile.planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            line_id += 1
            for ev in line.events:
                if ev.name == trace.WINDOW_SPAN:
                    window = (ev.start_ns, ev.end_ns)
                elif ev.name.startswith(PREFIXES):
                    raw.append((ev.name, ev.start_ns, ev.end_ns,
                                dict(ev.stats), line_id))
    if window is None:
        raise ValueError(f"trace has no {trace.WINDOW_SPAN!r} span")
    spans = []
    for name, s, e, args, line in raw:
        c = trace._clip(s, e, window)
        if c is not None:
            spans.append((name, c[0], c[1], args, line))
    return window, spans


def device_busy(profile, window) -> List[List[Tuple[float, float]]]:
    """Per device plane, its busy intervals in the window, by the rule of
    ``bench.trace.reduce``: whole programs, else operations."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith(trace.DEVICE_PREFIX):
            continue
        intervals = {trace.OPS_LINE: [], trace.MODULES_LINE: []}
        for line in plane.lines:
            if line.name in intervals:
                for ev in line.events:
                    c = trace._clip(ev.start_ns, ev.end_ns, window)
                    if c is not None:
                        intervals[line.name].append(c)
        out.append(trace.union(intervals[trace.MODULES_LINE]
                               or intervals[trace.OPS_LINE]))
    if not out:
        raise ValueError("trace has no TPU device plane")
    return out


def innermost(spans: Sequence[Span]) -> List[Tuple[float, float, str]]:
    """The time covered by spans, cut into pieces over which the innermost
    open span (the shortest that covers it) does not change."""
    bounds = sorted({t for _, s, e, _, _ in spans for t in (s, e)})
    starts = collections.defaultdict(list)
    for name, s, e, _, _ in spans:
        starts[s].append((e - s, -s, e, name))
    pieces, heap = [], []
    for t, nxt in zip(bounds, bounds[1:]):
        for item in starts.get(t, ()):
            heapq.heappush(heap, item)
        while heap and heap[0][2] <= t:
            heapq.heappop(heap)
        if not heap:
            continue
        name = heap[0][3]
        if pieces and pieces[-1][2] == name and pieces[-1][1] == t:
            pieces[-1] = (pieces[-1][0], nxt, name)
        else:
            pieces.append((t, nxt, name))
    return pieces


def split(gaps: Sequence[Tuple[float, float]],
          pieces: Sequence[Tuple[float, float, str]]) -> Dict[str, float]:
    """Nanoseconds of ``gaps`` under each piece's span; the rest under
    ``host:unmarked``.  Both lists are sorted and do not overlap."""
    out = collections.Counter()
    j = 0
    for gs, ge in gaps:
        covered = 0.0
        while j < len(pieces) and pieces[j][1] <= gs:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < ge:
            ps, pe, name = pieces[k]
            d = min(ge, pe) - max(gs, ps)
            if d > 0:
                out[name] += d
                covered += d
            k += 1
        if ge - gs > covered:
            out[UNMARKED] += ge - gs - covered
    return dict(out)


def label_at(t: float, pieces: Sequence[Tuple[float, float, str]]) -> str:
    i = bisect.bisect_right([p[0] for p in pieces], t) - 1
    return pieces[i][2] if i >= 0 and t < pieces[i][1] else UNMARKED


def self_seconds(spans: Sequence[Span]):
    """Self seconds and count per span name, nesting taken per line."""
    by_line = collections.defaultdict(list)
    for name, s, e, _, line in spans:
        by_line[line].append((name, s, e))
    span_s, span_n = collections.Counter(), collections.Counter()
    for evs in by_line.values():
        for name, ns in trace.self_times(evs):
            span_s[name] += ns * 1e-9
            span_n[name] += 1
    return dict(span_s), dict(span_n)


def slate_waits(spans: Sequence[Span]) -> List[List[float]]:
    """Per ``port.chunk`` whose slate's ``port.submit`` is in the trace:
    seconds from that submit's start to the chunk's ``port.launch`` start,
    and the chunk's live rows."""
    submits = {sp[3].get("slate"): sp[1] for sp in spans
               if sp[0] == "port.submit"}
    launches = collections.defaultdict(list)
    for name, s, _, _, line in spans:
        if name == "port.launch":
            launches[line].append(s)
    for v in launches.values():
        v.sort()
    out = []
    for name, s, e, args, line in spans:
        if name != "port.chunk" or args.get("slate") not in submits:
            continue
        starts = launches[line]
        i = bisect.bisect_left(starts, s)
        if i < len(starts) and starts[i] < e:
            out.append([(starts[i] - submits[args["slate"]]) * 1e-9,
                        int(args["rows"])])
    return out


def reduce(profile, top: int = 10) -> Dict:
    """The keys this module adds to ``bench.trace.reduce``'s summary (see
    the module's docstring); seconds are averaged over the devices."""
    window, spans = host_spans(profile)
    busy = device_busy(profile, window)
    pieces = innermost(spans)
    idle = collections.Counter()
    all_gaps = []
    for b in busy:
        g = trace.gaps(b, window)
        idle.update(split(g, pieces))
        all_gaps += g
    n = len(busy)
    span_s, span_n = self_seconds(spans)
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "idle_by_span": {k: v / n * 1e-9 for k, v in idle.items()},
        "span_s": span_s,
        "span_n": span_n,
        "slate_waits": slate_waits(spans),
        "idle_gaps": [[label_at((s + e) / 2, pieces), (e - s) * 1e-9]
                      for s, e in longest],
    }


# -- the per-layer metrics, as readers: read(record, trace, ctx) -----------

def idle_engine_port(record, summary, ctx=None) -> Optional[float]:
    """100 x the window's idle device seconds under any ``port.*`` span
    over the window: idle that the engine's own host path causes."""
    if (summary is None or "idle_by_span" not in summary
            or summary["window_s"] <= 0
            or not any(k.startswith(PORT_PREFIX) for k in summary["span_n"])):
        return None
    engine = sum(v for k, v in summary["idle_by_span"].items()
                 if k.startswith(PORT_PREFIX))
    return 100.0 * engine / summary["window_s"]


def slate_wait_p95_ms(record, summary, ctx=None) -> Optional[float]:
    """Rows-weighted 95th percentile of ``slate_waits``, in ms: the queue
    inside one ``submit``, where its groups are served in turn."""
    import numpy as np
    from bench.harness import percentile
    waits = (summary or {}).get("slate_waits")
    if not waits:
        return None
    w = np.asarray(waits, np.float64)
    return percentile(np.repeat(w[:, 0], w[:, 1].astype(int)) * 1e3, 95)


def transfer_overhead(record, summary, ctx=None) -> Optional[float]:
    """Bytes moved between host and device over the window
    (``h2d_bytes`` + ``d2h_bytes``) over the window's payload bytes
    (``bench/cost.py``), less one: inert rows, bucket padding, scalar
    vectors and output buffers sent both ways."""
    eng = record["engine"]
    payload = sum(b for _, b in record["slates"])
    if "h2d_bytes" not in eng or payload == 0:
        return None
    return (eng["h2d_bytes"] + eng["d2h_bytes"]) / payload - 1.0


METRICS = {"idle_engine.port": idle_engine_port,
           "slate_wait_p95_ms": slate_wait_p95_ms,
           "transfer_overhead": transfer_overhead}


# -- a run of one port cell --------------------------------------------------

def main(argv=None) -> int:
    import argparse
    import shutil
    import time

    import numpy as np

    from bench import harness, traffic
    from bench.run import cell_metrics

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    spec = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = {w["name"]: w for w in spec["workloads"]}[args.workload]
    cfg = harness.load_json(os.path.join(harness.BENCH, "configs",
                                         cell["config"] + ".json"))
    mix = traffic.validate(harness.load_json(
        os.path.join(harness.BENCH, "traffic", cell["traffic"] + ".json")))
    device = harness.check_device(cell["chips"])
    harness.enable_compile_cache()
    driver = harness.load_module(os.path.join(harness.BENCH, "drivers",
                                              cfg["driver"] + ".py"))
    log_dir = os.path.join(ROOT, ".bench_trace", "spans-" + args.workload)
    shutil.rmtree(log_dir, ignore_errors=True)
    tracer = harness.Tracer(bool(args.trace), log_dir, args.seconds)

    t0 = time.perf_counter()
    state = driver.setup(cfg, mix, args.seed, args.seconds)
    setup_s = time.perf_counter() - t0
    eng = state["engine"]
    s0 = eng.stats()
    record = driver.window(state, args.seconds, tracer)
    s1 = eng.stats()
    record["engine"].update({k: s1[k] - s0[k]
                             for k in ("h2d_bytes", "d2h_bytes") if k in s1})
    record["setup_s"] = setup_s
    traced = tracer.finish()
    summary = None
    if traced:
        profile = trace.load(traced)
        summary = trace.reduce(profile)
        summary.update(reduce(profile))
        shutil.rmtree(log_dir, ignore_errors=True)
    checks, correct = driver.check(state, record)

    lat = record["latency_ms"]
    tail = np.asarray(state["due"]) >= tracer.start_at
    out = {"workload": args.workload, "seed": args.seed,
           "seconds": args.seconds, "trace": args.trace,
           "device": device["kind"], "correct": bool(correct),
           "setup_s": setup_s, "attempted": record["attempted"],
           "slates": len(record["slates"]),
           "port_p50_ms": harness.percentile(lat, 50),
           "port_p95_ms": harness.percentile(lat, 95),
           "traced_part": {"requests": int(tail.sum()),
                           "p50_ms": harness.percentile(lat[tail], 50),
                           "p95_ms": harness.percentile(lat[tail], 95)},
           "engine": record["engine"],
           "payload_bytes": sum(b for _, b in record["slates"]),
           "metrics": {}}
    ctx = {"cell": cell, "config": cfg, "traffic": mix, "seconds":
           args.seconds, "peaks": harness.peaks_for(device["kind"])}
    for m in cell_metrics(spec, args.workload, True):
        reader = harness.load_module(os.path.join(harness.BENCH, "metrics",
                                                  m["name"] + ".py"))
        out["metrics"][m["name"]] = reader.read(record, summary, ctx)
    for name, fn in METRICS.items():
        out["metrics"][name] = fn(record, summary, ctx)
    if summary is not None:
        w = summary["window_s"]
        chunks = summary["span_n"].get("port.chunk", 0)
        out["trace"] = {
            "window_s": w, "busy_s": summary["busy_s"],
            "idle_pct_by_span": {k: 100.0 * v / w for k, v in sorted(
                summary["idle_by_span"].items(), key=lambda kv: -kv[1])},
            "span_s": summary["span_s"], "span_n": summary["span_n"],
            "self_ms_per_chunk": {
                k: 1e3 * v / chunks for k, v in summary["span_s"].items()
                if k.startswith(PORT_PREFIX) and chunks},
            "programs_s": summary["programs_s"],
            "programs_n": summary["programs_n"],
            "idle_gaps": summary["idle_gaps"]}
    line = json.dumps(out)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "a") as f:
            f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
