"""The engine's spans against device idle time (``bench/spans.py``), on a
hand-made trace, on the benchmark's hand-made trace without engine spans,
and on a trace of ``PortEngine`` recorded on the CPU."""
import json
import os

import numpy as np
import pytest

from bench import spans, trace
from bench.tests.test_bench_trace import HAND

US = 1000          # ns


def _stat(v):
    return (f"int64_value: {v}" if isinstance(v, int)
            else f"str_value: {json.dumps(v)}")


def _text_proto(planes):
    """An XSpace text proto from [{name, lines: [{name, events: [[name,
    start_ns, duration_ns(, args)], ...]}]}]; args become event stats."""
    out = []
    for p_i, plane in enumerate(planes):
        meta, smeta, lines = {}, {}, []
        for l_i, line in enumerate(plane["lines"]):
            evs = []
            for name, start, dur, *args in line["events"]:
                mid = meta.setdefault(name, len(meta) + 1)
                stats = " ".join(
                    f"stats {{ metadata_id: "
                    f"{smeta.setdefault(k, len(smeta) + 1)} {_stat(v)} }}"
                    for k, v in (args[0] if args else {}).items())
                evs.append(f"events {{ metadata_id: {mid} "
                           f"offset_ps: {int(start) * 1000} "
                           f"duration_ps: {int(dur) * 1000} {stats} }}")
            lines.append(f"lines {{ id: {l_i + 1} "
                         f"name: {json.dumps(line['name'])} "
                         f"timestamp_ns: 0 {' '.join(evs)} }}")
        metas = " ".join(f"event_metadata {{ key: {i} value {{ id: {i} "
                         f"name: {json.dumps(n)} }} }}"
                         for n, i in meta.items())
        smetas = " ".join(f"stat_metadata {{ key: {i} value {{ id: {i} "
                          f"name: {json.dumps(n)} }} }}"
                          for n, i in smeta.items())
        out.append(f"planes {{ id: {p_i + 1} "
                   f"name: {json.dumps(plane['name'])} "
                   f"{' '.join(lines)} {metas} {smetas} }}")
    return "\n".join(out)


def _profile(planes):
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(_text_proto(planes))


def _ev(name, start_us, end_us, **args):
    return [name, start_us * US, (end_us - start_us) * US, args]


# One slate of two chunks (2 and 1 live rows) inside bench.submit, then
# bench.wait; the device runs [20, 30) and [45, 50) us of the window
# [0, 100), so [0, 20), [30, 45) and [50, 100) are idle.
CHUNK = dict(slate=7, kernel="k", target="rvv-128", bucket=64)
HAND_PORT = [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit_port_k_rvv_128(1)", 20 * US, 10 * US],
            ["jit_port_k_rvv_128(1)", 45 * US, 5 * US]]},
        {"name": "XLA Ops", "events": [
            ["%while.1 = (s32[]) while(%t)", 20 * US, 10 * US],
            ["%fusion.2 = f32[8] fusion(%x)", 45 * US, 5 * US]]},
    ]},
    {"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [
            _ev("bench.window", 0, 100),
            _ev("bench.submit", 0, 60),
            _ev("port.submit", 1, 59, slate=7, requests=3, groups=1),
            _ev("port.plan", 1, 5),
            _ev("port.chunk", 5, 33, rows=2, **CHUNK),
            _ev("port.pad", 5, 10), _ev("port.h2d", 10, 15),
            _ev("port.launch", 15, 18, new_program=1),
            _ev("port.fetch", 18, 30), _ev("port.slice", 30, 32),
            _ev("port.chunk", 33, 58, rows=1, **CHUNK),
            _ev("port.pad", 33, 36), _ev("port.h2d", 36, 40),
            _ev("port.launch", 40, 44, new_program=0),
            _ev("port.fetch", 44, 55), _ev("port.slice", 55, 58),
            _ev("bench.wait", 60, 100)]}]},
]


def _without_port_spans(planes):
    planes = json.loads(json.dumps(planes))
    line = planes[1]["lines"][0]
    line["events"] = [e for e in line["events"]
                      if not e[0].startswith("port.")]
    return planes


def _us(d):
    return {k: pytest.approx(v * 1e-6) for k, v in d.items()}


def test_reduce_hand_worked_port_spans():
    s = spans.reduce(_profile(HAND_PORT))
    # the idle 85 us, cut wherever the innermost span changes:
    # [0,20): bench.submit 1, plan 4, pad 5, h2d 5, launch 3, fetch 2;
    # [30,45): slice 2, chunk 1, pad 3, h2d 4, launch 4, fetch 1;
    # [50,100): fetch 5, slice 3, port.submit 1, bench.submit 1, wait 40
    assert s["idle_by_span"] == _us({
        "bench.submit": 2, "port.plan": 4, "port.pad": 8, "port.h2d": 9,
        "port.launch": 7, "port.fetch": 8, "port.slice": 5,
        "port.chunk": 1, "port.submit": 1, "bench.wait": 40})
    # self time: port.submit's 58 less plan 4 and the chunks' 28 + 25
    assert s["span_s"] == _us({
        "bench.submit": 2, "port.submit": 1, "port.plan": 4,
        "port.chunk": 1, "port.pad": 8, "port.h2d": 9, "port.launch": 7,
        "port.fetch": 23, "port.slice": 5, "bench.wait": 40})
    assert s["span_n"]["port.chunk"] == 2 and s["span_n"]["port.plan"] == 1
    # launches at 15 and 40 us, the slate's submit at 1 us
    assert s["slate_waits"] == [[pytest.approx(14e-6), 2],
                                [pytest.approx(39e-6), 1]]
    # the same gaps as bench.trace.reduce, labelled at their midpoints
    # (75, 10 and 37.5 us) by the innermost span of either prefix
    assert s["idle_gaps"] == [["bench.wait", pytest.approx(50e-6)],
                              ["port.h2d", pytest.approx(20e-6)],
                              ["port.h2d", pytest.approx(15e-6)]]


def test_trace_reduce_unchanged_by_port_spans():
    """The summary the accepted metrics read (busy, window, ops,
    programs, idle gaps) is the same with or without the engine's
    spans in the trace."""
    with_port = trace.reduce(_profile(HAND_PORT))
    without = trace.reduce(_profile(_without_port_spans(HAND_PORT)))
    assert with_port == without
    assert with_port["busy_s"] == pytest.approx(15e-6)
    assert with_port["programs_s"] == {"jit_port_k_rvv_128":
                                       pytest.approx(15e-6)}
    assert [g[1] for g in with_port["idle_gaps"]] == [
        g[1] for g in spans.reduce(_profile(HAND_PORT))["idle_gaps"]]


def test_reduce_without_port_spans():
    """On the benchmark's own hand-made trace the idle time splits over
    bench.* alone and adds up to the idle share; no engine metric."""
    s = spans.reduce(_profile(HAND))
    summary = trace.reduce(_profile(HAND))
    # gaps [0,5) and [62,65) under bench.submit, [65,68) and [82,100)
    # under bench.wait
    assert s["idle_by_span"] == _us({"bench.submit": 8, "bench.wait": 21})
    assert sum(s["idle_by_span"].values()) == pytest.approx(
        summary["window_s"] - summary["busy_s"])
    assert s["slate_waits"] == []
    assert [g[0] for g in s["idle_gaps"]] == [g[0] for g in
                                              summary["idle_gaps"]]
    summary.update(s)
    assert spans.idle_engine_port({}, summary) is None
    assert spans.slate_wait_p95_ms({}, summary) is None


def test_unmarked_idle_and_split_gaps():
    pieces = [(10, 20, "a"), (20, 30, "b"), (40, 50, "a")]
    assert spans.split([(0, 15), (25, 45), (60, 70)], pieces) == {
        "host:unmarked": 10 + 10 + 10, "a": 5 + 5, "b": 5}
    assert spans.label_at(35, pieces) == "host:unmarked"
    assert spans.label_at(20, pieces) == "b"


def test_reduce_needs_a_window_and_a_device():
    with pytest.raises(ValueError, match="bench.window"):
        spans.reduce(_profile([HAND_PORT[0]]))
    with pytest.raises(ValueError, match="device"):
        spans.reduce(_profile(HAND_PORT[1:]))


# -- the three metrics ----------------------------------------------------

def _summary():
    s = trace.reduce(_profile(HAND_PORT))
    s.update(spans.reduce(_profile(HAND_PORT)))
    return s


def test_idle_engine_port_reads_port_spans_only():
    s = _summary()
    # idle under port.*: 4 + 8 + 9 + 7 + 8 + 5 + 1 + 1 = 43 of 100 us
    assert spans.idle_engine_port({}, s) == pytest.approx(43.0)
    idle_share = 100.0 * (1 - s["busy_s"] / s["window_s"])
    assert spans.idle_engine_port({}, s) <= idle_share
    assert spans.idle_engine_port({}, None) is None
    assert spans.idle_engine_port({}, trace.reduce(_profile(HAND_PORT))) \
        is None


def test_slate_wait_p95_weights_rows():
    # waits 14, 14 (two rows) and 39 us: the 95th percentile lies 0.9 of
    # the way from the second to the third
    assert spans.slate_wait_p95_ms({}, _summary()) == pytest.approx(
        (14 + 0.9 * 25) * 1e-3)
    assert spans.slate_wait_p95_ms({}, {"slate_waits": [[0.002, 1]]}) == \
        pytest.approx(2.0)
    assert spans.slate_wait_p95_ms({}, None) is None


def test_transfer_overhead_reads_the_byte_counters():
    record = {"engine": {"h2d_bytes": 3000, "d2h_bytes": 1000},
              "slates": [(0.0, 1500), (0.5, 500)]}
    assert spans.transfer_overhead(record, None) == pytest.approx(1.0)
    # a program without the counters, or a window that served nothing
    assert spans.transfer_overhead({"engine": {"payload_elems": 1},
                                    "slates": [(0.0, 1)]}, None) is None
    assert spans.transfer_overhead({"engine": {"h2d_bytes": 1,
                                               "d2h_bytes": 1},
                                    "slates": []}, None) is None


# -- a recorded trace of the engine on the CPU ----------------------------

def test_engine_trace_on_the_cpu(tmp_path):
    """PortEngine's own spans, recorded by the profiler on the CPU, read
    back as the reduction expects: one slate wait per chunk, its rows the
    slate's live rows, the self times of every stage."""
    import jax

    from bench.refs import neon_corpus as refs
    from repro import port
    from repro.serve import PortEngine, Request
    names = ["xnn_f32_vadd_ukernel", "xnn_f32_vdot_ukernel"]
    ks = {k: port.compile_file(refs.KERNELS[k].path, name=k) for k in names}
    rng = np.random.default_rng(0)
    reqs = [Request(ks[k], refs.KERNELS[k].make_args(rng, n),
                    target="rvv-128")
            for k, n in [(names[0], 16), (names[0], 100), (names[1], 30),
                         (names[0], 40)]]
    eng = PortEngine(target="rvv-128", max_batch=4)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            eng.submit(reqs)
    finally:
        jax.profiler.stop_trace()
    _, sp = spans.host_spans(trace.load(str(tmp_path)))
    waits = spans.slate_waits(sp)
    assert len(waits) == 3                  # vadd@64, vadd@128, vdot@64
    assert sorted(r for _, r in waits) == [1, 1, 2]
    assert all(w > 0 for w, _ in waits)
    _, span_n = spans.self_seconds(sp)
    assert span_n["port.submit"] == 1 and span_n["port.plan"] == 1
    for stage in ("port.chunk", "port.pad", "port.h2d", "port.launch",
                  "port.fetch", "port.slice"):
        assert span_n[stage] == 3
