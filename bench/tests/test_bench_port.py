"""The port-serving driver end to end at a tiny size on the CPU, its
references, its control and a planted fault."""
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import harness
from bench.refs import neon_corpus

REPO = os.path.dirname(harness.BENCH)


def _driver():
    return harness.load_module(os.path.join(harness.BENCH, "drivers",
                                            "port_serve.py"))


def _cell(rate=20.0, seconds=1.0, seed=2**31 + 99, fault=None):
    cfg = harness.load_json(os.path.join(harness.BENCH, "configs",
                                         "neon-corpus.json"))
    mix = dict(harness.load_json(os.path.join(harness.BENCH, "traffic",
                                              "port-short.json")),
               rate_per_s=rate, n_min=16, n_max=128)
    drv = _driver()
    state = drv.setup(cfg, mix, seed, seconds)
    from repro.serve import PortEngine
    real = PortEngine.submit
    if fault is not None:
        PortEngine.submit = fault(real)
    try:
        record = drv.window(state, seconds,
                            harness.Tracer(False, "", seconds))
    finally:
        PortEngine.submit = real
    return drv, state, record


@pytest.fixture(scope="module")
def served():
    return _cell()


def _corpus_harness():
    return harness.load_module(os.path.join(REPO, "examples", "neon_corpus",
                                            "harness.py"),
                               "neon_corpus_harness")


@pytest.mark.parametrize("name", sorted(neon_corpus.KERNELS))
def test_reference_copy_matches_corpus_harness(name):
    """The copies agree with the corpus harness's references and budgets
    (the copy is what the benchmark keeps if ``examples/`` changes)."""
    h = _corpus_harness()
    case = {c.kernel: c for c in h.cases(n=256, tail_n=253)}[name]
    k = neon_corpus.KERNELS[name]
    assert k.ulp_budget == h.ulp_budget(case)
    args = case.make_args(np.random.default_rng(5))
    np.testing.assert_array_equal(k.reference(*args), case.reference(*args))
    with open(k.path) as a, open(os.path.join(h.CORPUS_DIR, case.file)) as b:
        assert a.read() == b.read()


def test_window_serves_every_request_correctly(served):
    drv, state, record = served
    assert record["attempted"] == 20 and record["failed"] == 0
    assert len(record["latency_ms"]) == 20
    assert np.all(record["latency_ms"] >= record["queue_wait_ms"])
    checks, correct = drv.check(state, record)
    assert correct, checks
    red = checks.pop("reduction_error_u")
    assert 0 <= red["value"] < red["limit"] == neon_corpus.REDUCTION_BUDGET_U
    assert checks == {"wrong_answers": {"value": 0, "limit": 0},
                      "unanswered": {"value": 0, "limit": 0},
                      "degraded": {"value": 0, "limit": 0}}
    eng = record["engine"]
    assert eng["payload_elems"] > 0 and eng["padded_elems"] >= \
        eng["payload_elems"]
    assert set(record["degraded"]) == set(drv.DEGRADATION)


def test_metrics_read_the_record(served):
    _, _, record = served
    record = dict(record, setup_s=1.5)
    read = {m: harness.load_module(os.path.join(
        harness.BENCH, "metrics", m + ".py")).read(record, None, {})
        for m in ("port_p95_ms", "port_p50_ms", "queue_wait_p95_ms",
                  "pad_overhead", "setup_s", "port_roofline",
                  "idle_share.port")}
    assert 0 < read["port_p50_ms"] <= read["port_p95_ms"]
    assert read["pad_overhead"] >= 0 and read["setup_s"] == 1.5
    # no trace: the device readers find nothing and say so
    assert read["port_roofline"] is None and read["idle_share.port"] is None


def test_control_fails(served):
    """The reference one precision down in the program's place."""
    drv, state, record = served
    checks = drv.answers(state, drv.control_outputs(state))
    assert checks["wrong_answers"]["value"] > 0


def test_reduction_at_long_sizes_within_budget_and_control_over():
    """f32 vdot at the long mix's sizes, served by the engine: within the
    reduction budget on both targets, while the ULP rule of the corpus
    harness (set at n up to 4096) refuses some sound answers, since 32
    lanes and a fused multiply-add reorder a cancelling sum; the control
    reads far over the budget."""
    import ml_dtypes
    from repro import port
    from repro.serve import PortEngine, Request
    k = neon_corpus.KERNELS["xnn_f32_vdot_ukernel"]
    pk = port.compile_file(k.path, name="xnn_f32_vdot_ukernel")
    eng = PortEngine(policy="pallas", revec=True, bucket_policy="fine",
                     max_batch=32)
    rng = np.random.default_rng(2**31 + 17)
    bf = lambda x: x.astype(ml_dtypes.bfloat16).astype(np.float32)  # noqa
    prog, ctl, ulp_refused = [], [], 0
    for tgt in ("rvv-1024", "rvv-128"):
        args = [k.make_args(rng, int(np.exp(rng.uniform(np.log(4096),
                                                        np.log(65536)))))
                for _ in range(32)]
        outs = eng.submit([Request(pk, a, target=tgt) for a in args])
        for a, out in zip(args, outs):
            want = k.reference(*a)
            prog.append(neon_corpus.reduction_error_u(out, want, k, a))
            assert neon_corpus.conforms(out, want, k, a)[0]
            ulp_refused += int(neon_corpus.ulp_distance(
                np.asarray(out), want).max() > k.ulp_budget)
            n, x, y, s = a
            low = bf(k.reference(n, bf(x), bf(y), s))
            ctl.append(neon_corpus.reduction_error_u(low, want, k, a))
    assert max(prog) < 2.0 < neon_corpus.REDUCTION_BUDGET_U
    assert ulp_refused > 0
    assert np.median(ctl) > 10 * neon_corpus.REDUCTION_BUDGET_U


def test_altered_answer_fails():
    """A fault planted where an answer is produced: one element of one
    served result changes inside ``PortEngine.submit``."""
    hits = []

    def fault(real):
        def altered(self, requests):
            out = real(self, requests)
            f32 = [i for i, r in enumerate(requests)
                   if r.kernel.name.startswith("xnn_f32_v") and
                   r.args[0] > 0 and "dot" not in r.kernel.name]
            if f32 and not hits:
                i = f32[0]
                out[i] = np.array(out[i], copy=True)
                out[i][0] += np.float32(1.0)
                hits.append(i)
            return out
        return altered

    drv, state, record = _cell(seed=3, fault=fault)
    assert hits
    checks, correct = drv.check(state, record)
    assert not correct and checks["wrong_answers"]["value"] == 1


def _run(monkeypatch, capsys, fault=None, seed=2**31 + 5):
    """``bench/run.py`` for ``port-serve.short`` with its look for a chip
    skipped, the compile cache left alone, and the mix cut to a CPU's
    size; returns the result line."""
    import json
    from bench import harness as h
    monkeypatch.setattr(h, "check_device", lambda chips: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": chips})
    monkeypatch.setattr(h, "enable_compile_cache", lambda: None)
    load = h.load_json

    def small(path):
        d = load(path)
        if d.get("kind") == "open_loop":
            d = dict(d, rate_per_s=20, n_min=16, n_max=128)
        return d

    monkeypatch.setattr(h, "load_json", small)
    if fault is not None:
        from repro.serve import PortEngine
        monkeypatch.setattr(PortEngine, "submit",
                            fault(PortEngine.submit))
    run = h.load_module(os.path.join(h.BENCH, "run.py"), "bench_run_main")
    assert run.main(["--workload", "port-serve.short", "--seed", str(seed),
                     "--seconds", "1", "--trace", "0"]) == 0
    out = capsys.readouterr()
    return json.loads(out.out.strip().splitlines()[-1]), out.err


def test_run_end_to_end(monkeypatch, capsys):
    line, err = _run(monkeypatch, capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"port_p95_ms", "port_p50_ms", "setup_s"}
    assert list(line)[-1] == "checks"
    assert err.strip().splitlines()[-1] == "check degraded = 0 (limit 0)"


def test_run_with_an_altered_answer_is_not_correct(monkeypatch, capsys):
    """The fault planted under a whole run: ``correct`` comes out false."""
    def fault(real):
        def altered(self, requests):
            out = real(self, requests)
            for i, r in enumerate(requests):
                if r.kernel.name == "xnn_f32_vadd_ukernel" and r.args[0]:
                    out[i] = np.array(out[i], copy=True)
                    out[i][0] += np.float32(1.0)
            return out
        return altered

    line, _ = _run(monkeypatch, capsys, fault=fault)
    assert line["correct"] is False
    assert line["checks"]["wrong_answers"]["value"] > 0


def test_run_with_a_batch_fault_is_not_correct(monkeypatch, capsys):
    """A batched program that faults once under a whole run: the engine
    serves those rows down its ladder, every answer still conforms, and
    ``correct`` comes out false on the degradation count alone."""
    from repro.serve import PortEngine
    real = PortEngine._program
    faults = []

    def faulty(self, kernel, tgt):
        prog = real(self, kernel, tgt)
        if not faults and kernel.fn.name == "xnn_f32_vadd_ukernel" and \
                getattr(self, "_bench_window", False):
            def broken(*cols):
                faults.append(kernel.fn.name)
                raise RuntimeError("planted batch fault")
            return broken
        return prog

    monkeypatch.setattr(PortEngine, "_program", faulty)

    def mark(real_submit):
        def submit(self, requests):
            self._bench_window = any(r.args[0] for r in requests)
            return real_submit(self, requests)
        return submit

    line, _ = _run(monkeypatch, capsys, fault=mark)
    assert faults
    assert line["correct"] is False
    assert line["checks"]["wrong_answers"]["value"] == 0
    assert line["checks"]["unanswered"]["value"] == 0
    assert line["checks"]["degraded"]["value"] >= 1


def test_run_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(REPO, "bench", "run.py"),
                        "--workload", "port-serve.long", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "only on a TPU" in p.stderr
