"""The trace reduction, on a hand-made trace and on a recorded one."""
import json
import os

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data")


def _text_proto(planes):
    """An XSpace text proto from [{name, lines: [{name, events: [[name,
    start_ns, duration_ns], ...]}]}]."""
    meta, out = {}, []
    for p_i, plane in enumerate(planes):
        lines = []
        for l_i, line in enumerate(plane["lines"]):
            evs = []
            for name, start, dur in line["events"]:
                mid = meta.setdefault((p_i, name), len(meta) + 1)
                evs.append(f"events {{ metadata_id: {mid} "
                           f"offset_ps: {int(start) * 1000} "
                           f"duration_ps: {int(dur) * 1000} }}")
            lines.append(f"lines {{ id: {l_i + 1} name: {json.dumps(line['name'])} "
                         f"timestamp_ns: 0 {' '.join(evs)} }}")
        metas = " ".join(
            f"event_metadata {{ key: {mid} value {{ id: {mid} "
            f"name: {json.dumps(name)} }} }}"
            for (pi, name), mid in meta.items() if pi == p_i)
        out.append(f"planes {{ id: {p_i + 1} name: {json.dumps(plane['name'])} "
                   f"{' '.join(lines)} {metas} }}")
    return "\n".join(out)


def _profile(planes):
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(_text_proto(planes))


HAND = [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_step(123)", 5000, 57000],
                                           ["jit_step(123)", 68000, 14000]]},
        {"name": "XLA Ops", "events": [
            ["%while.1 = (s32[]) while(%t)", 10000, 50000],
            ["%fusion.2 = f32[8] fusion(%x)", 20000, 10000],
            ["%ssd.3 = bf16[8] custom-call(%y)", 35000, 15000],
            ["%copy.4 = f32[8] copy(%z)", 70000, 10000]]},
        {"name": "Async XLA Ops", "events": [["%copy-start = x", 0, 100000]]},
    ]},
    {"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [["bench.window", 0, 100000],
                                       ["bench.submit", 0, 65000],
                                       ["bench.wait", 65000, 35000]]}]},
]


def test_reduce_hand_worked():
    s = trace.reduce(_profile(HAND))
    # busy: the programs [5, 62) and [68, 82) us; the async copy is not work
    assert s["window_s"] == pytest.approx(100e-6)
    assert s["busy_s"] == pytest.approx(71e-6)
    assert s["programs_s"] == {"jit_step": pytest.approx(71e-6)}
    assert s["programs_n"] == {"jit_step": 2}
    # self time: the loop's 50 us less its body's 10 + 15 us
    assert s["ops_s"]["while.1"] == pytest.approx(25e-6)
    assert s["ops_s"]["ssd.3"] == pytest.approx(15e-6)
    assert s["ops_n"]["copy.4"] == 1
    # gaps [82, 100), [62, 68), [0, 5) us, labelled by the span open
    assert s["idle_gaps"] == [["bench.wait", pytest.approx(18e-6)],
                              ["bench.wait", pytest.approx(6e-6)],
                              ["bench.submit", pytest.approx(5e-6)]]
    b = trace.breakdown(s)
    assert b["device_ops"][0] == ["while.1", pytest.approx(25e-6)]
    assert [g[0] for g in b["idle_gaps"]] == ["bench.wait", "bench.wait",
                                              "bench.submit"]


def test_reduce_clips_to_the_window():
    planes = json.loads(json.dumps(HAND))
    planes[1]["lines"][0]["events"][0] = ["bench.window", 30000, 40000]
    s = trace.reduce(_profile(planes))
    assert s["window_s"] == pytest.approx(40e-6)
    assert s["busy_s"] == pytest.approx(34e-6)     # [30, 62), [68, 70) us
    assert s["idle_gaps"] == [["bench.wait", pytest.approx(6e-6)]]


def test_reduce_needs_a_window_and_a_device():
    no_window = json.loads(json.dumps(HAND))
    no_window[1]["lines"][0]["events"] = []
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce(_profile(no_window))
    with pytest.raises(ValueError, match="device"):
        trace.reduce(_profile(HAND[1:]))


def test_reduce_recorded_prefill_trace():
    """An excerpt of a recorded trace (two mamba2 prefill steps on one
    TPU v5 lite), with the window span added around its programs."""
    with open(os.path.join(DATA, "prefill_trace_excerpt.json")) as f:
        rec = json.load(f)
    planes = rec["planes"]
    mods = planes[0]["lines"][0]["events"]
    end = max(s + d for _, s, d in mods) + 1_000_000
    planes[1]["lines"][0]["events"].append(["bench.window", 0, end])
    s = trace.reduce(_profile(planes))
    # every op of the excerpt lies inside a program, so busy is the sum
    # of the program intervals, which do not overlap
    assert s["busy_s"] == pytest.approx(sum(d for _, _, d in mods) * 1e-9)
    assert s["window_s"] == pytest.approx(end * 1e-9)
    assert s["programs_n"]["jit_prefill"] == 2
    assert s["programs_s"]["jit_prefill"] == pytest.approx(
        (1235779357 + 1235835046) * 1e-9)
    # the layer loop of the first step holds the rest of the excerpt
    assert s["ops_n"]["while.2"] == 1
    b = trace.breakdown(s)
    assert len(b["device_ops"]) == 10
    assert b["device_ops"][0][0] == "while.2"
    # the longest gap runs from the second step to the window's end
    assert b["idle_gaps"][0][0] == "host:unmarked"


def test_short_names():
    assert trace.short_name("%fusion.62 = bf16[8,2] fusion(%a), kind=kLoop") \
        == "fusion.62"
    assert trace.short_name("jit_prefill(3898511431014508036)") == \
        "jit_prefill"


def test_union_and_gaps():
    assert trace.union([(5, 10), (0, 3), (2, 4), (10, 12)]) == [(0, 4),
                                                                (5, 12)]
    assert trace.gaps([(0, 4), (5, 12)], (0, 20)) == [(4, 5), (12, 20)]
