"""Chaos suite for the degradation ladder + fault-injection harness.

The resilience contract (DESIGN.md §13): the port pipeline never
*silently corrupts* and never *hard-fails when a safe fallback exists*.
Every injected fault must resolve to one of exactly two outcomes:

1. a **recorded degraded path** whose output is bitwise identical to the
   fault-free run of the rung that actually served it, or
2. a **typed PortError** carrying provenance (kernel, stage, target).

Anything else — a raw IndexError out of the parser, a wrong-but-
plausible array out of a corrupted cache hit, a batch stalled behind a
poisoned kernel — is a bug this suite exists to catch.

Structure:

* ``TestChaosLadder`` — the matrix: every corpus kernel, targets
  rvv-64..1024, fault classes injected at each pipeline seam, outputs
  checked bitwise against same-rung fault-free references.
* ``TestCircuitBreaker`` — quarantine semantics: after the threshold the
  poisoned rung is skipped without an attempt and the seam stops firing.
* ``TestConcurrentCompile`` — the compiled-kernel LRU under a
  concurrent warmup stampede: single-flight, no duplicate compiles
  (this test fails on the pre-lock cache).
* ``TestEngineChaos`` — PortEngine slates: a poisoned kernel degrades
  per-row while batch-mates stay on the fast path, deadlines resolve to
  typed errors, the breaker fails fast.
* ``TestMutationSweep`` — the parser/lowering crash UX: no truncation or
  byte-level mutation of any corpus source may escape as anything but a
  typed PortError (with file:line:col provenance on the directed cases).
* ``TestSimFaults`` — directed RvvSim faults: every error names the
  faulting mnemonic and site.
"""
import os
import random
import sys
import threading
import zlib

import numpy as np
import pytest

CORPUS = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                      "examples", "neon_corpus"))
sys.path.insert(0, CORPUS)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402

from repro import port  # noqa: E402
from repro.core.targets import resolve_target  # noqa: E402
from repro.port import faultinject as fi  # noqa: E402
from repro.port import resilience as rz  # noqa: E402
from repro.port.ir import PtrType  # noqa: E402
from repro.rvv.codegen import RvvProgram, V, VSetVL  # noqa: E402
from repro.rvv.sim import RvvSim, SimError  # noqa: E402
from repro.serve.port_engine import PortEngine, Request  # noqa: E402

ALL_TARGETS = ("rvv-64", "rvv-128", "rvv-512", "rvv-1024")

_CASES = {c.kernel: c for c in harness.cases(n=8, tail_n=8)}
KERNELS = sorted(_CASES)


@pytest.fixture(autouse=True)
def _clean_slate():
    """No armed seam or tripped breaker ever leaks between tests."""
    fi.disarm_all()
    rz.reset_resilience()
    yield
    fi.disarm_all()
    rz.reset_resilience()


@pytest.fixture(scope="module")
def kernels():
    out = {}
    for name, case in _CASES.items():
        out[name] = port.compile_file(os.path.join(CORPUS, case.file),
                                      name=case.kernel)
    return out


def _args_for(kname, seed=0):
    args = _CASES[kname].make_args(np.random.default_rng(seed))
    return tuple(np.zeros(1, a.dtype)
                 if isinstance(a, np.ndarray) and a.size == 0 else a
                 for a in args)


def _bitwise_equal(got, want, label):
    got = got if isinstance(got, tuple) else (got,)
    want = want if isinstance(want, tuple) else (want,)
    assert len(got) == len(want), label
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        np.testing.assert_array_equal(
            g, w, err_msg=f"{label}: degraded output diverged — "
                          f"silent corruption")


# ---------------------------------------------------------------------------
# the chaos matrix: every kernel x rvv-64..1024 x fault class
# ---------------------------------------------------------------------------

class TestChaosLadder:

    @pytest.mark.parametrize("kname", KERNELS)
    def test_every_seam_every_target(self, kernels, kname):
        """Inject at each ladder seam; the output must be bitwise the
        fault-free output of whichever rung actually served it, and the
        DegradationRecord must say so."""
        k = kernels[kname]
        args = _args_for(kname)
        for t in ALL_TARGETS:
            port.compiled_cache_clear()
            rz.reset_resilience()
            # fault-free per-rung references
            out, rec = rz.run_resilient(k, *args, target=t, jit=False)
            assert rec.used == "compiled+revec" and not rec.degraded
            ref = {"compiled+revec": out,
                   "compiled": k.compile(target=t, revec=False,
                                         jit=False)(*args),
                   "interp": k(*args, target=t)}

            # forced re-vectorization veto -> compiled narrow
            port.compiled_cache_clear()
            with fi.injected("revec.retile", error=rz.RevecVeto,
                             times=None):
                out, rec = rz.run_resilient(k, *args, target=t,
                                            jit=False)
            assert rec.used == "compiled" and rec.degraded
            assert rec.attempts[0].error_type == "RevecVeto"
            _bitwise_equal(out, ref["compiled"],
                           f"{kname}@{t} revec-veto")

            # persistent compile failure -> interpreter floor
            port.compiled_cache_clear()
            with fi.injected("compile.trace", error=rz.CompileError,
                             times=None):
                out, rec = rz.run_resilient(k, *args, target=t,
                                            jit=False)
            assert rec.used == "interp" and rec.degraded
            _bitwise_equal(out, ref["interp"],
                           f"{kname}@{t} compile-fail")

            # runtime fault inside the compiled program -> interpreter
            with fi.injected("compile.run", error=rz.ExecError,
                             times=None):
                out, rec = rz.run_resilient(k, *args, target=t,
                                            jit=False)
            assert rec.used == "interp" and rec.degraded
            _bitwise_equal(out, ref["interp"],
                           f"{kname}@{t} runtime-fault")

    @pytest.mark.parametrize("kname", KERNELS)
    def test_cache_chaos_and_transients(self, kernels, kname):
        """Target-independent fault classes, one target: transient
        compile timeout retries on the same rung; an eviction storm and
        a corrupted cache entry never change values or degrade."""
        k = kernels[kname]
        args = _args_for(kname)
        t = "rvv-128"
        port.compiled_cache_clear()
        ref, rec = rz.run_resilient(k, *args, target=t, jit=False)
        assert rec.used == "compiled+revec"

        # transient timeout: retried on the same rung, no degradation
        port.compiled_cache_clear()
        with fi.injected("compile.trace", error=rz.CompileTimeout,
                         times=1):
            out, rec = rz.run_resilient(k, *args, target=t, jit=False)
        assert rec.used == "compiled+revec" and not rec.degraded
        assert rec.attempts[0].retries == 1
        _bitwise_equal(out, ref, f"{kname} transient-retry")

        # eviction storm: capacity 1 thrashes every lookup, values hold
        with fi.eviction_storm(1):
            out, rec = rz.run_resilient(k, *args, target=t, jit=False)
        assert rec.used == "compiled+revec" and not rec.degraded
        _bitwise_equal(out, ref, f"{kname} eviction-storm")

        # corrupted cache entry: hit validation detects, recompiles
        port.compiled_cache_clear()
        k.compile(target=t, revec=True, jit=False)
        assert fi.corrupt_cache_entry(k.fn.name)
        before = port.compiled_cache_info()["corruptions"]
        out, rec = rz.run_resilient(k, *args, target=t, jit=False)
        assert port.compiled_cache_info()["corruptions"] > before
        _bitwise_equal(out, ref, f"{kname} corrupted-cache")

    def test_full_exhaustion_is_typed(self, kernels):
        """When every rung fails the ladder raises LadderExhausted with
        the full attempt trail — never a raw exception."""
        k = kernels["xnn_f32_vadd_ukernel"]
        args = _args_for("xnn_f32_vadd_ukernel")
        port.compiled_cache_clear()
        with fi.injected("compile.trace", error=rz.CompileError,
                         times=None), \
             fi.injected("interp.run", error=rz.ExecError, times=None):
            with pytest.raises(rz.LadderExhausted) as ei:
                rz.run_resilient(k, *args, target="rvv-128", jit=False)
        e = ei.value
        assert e.kernel == "xnn_f32_vadd_ukernel"
        assert [a.rung for a in e.attempts] == \
            ["compiled+revec", "compiled", "interp"]
        assert rz.resilience_stats()["exhausted"] == 1

    def test_deadline_respected_mid_ladder(self, kernels):
        k = kernels["xnn_f32_vadd_ukernel"]
        args = _args_for("xnn_f32_vadd_ukernel")
        with pytest.raises(rz.DeadlineExceeded):
            rz.run_resilient(k, *args, target="rvv-128", jit=False,
                             deadline_s=0.0)
        assert rz.resilience_stats()["deadline_misses"] == 1

    def test_stats_and_records_surface(self, kernels):
        k = kernels["xnn_f32_vmul_ukernel"]
        args = _args_for("xnn_f32_vmul_ukernel")
        port.compiled_cache_clear()
        with fi.injected("revec.retile", error=rz.RevecVeto, times=None):
            rz.run_resilient(k, *args, target="rvv-128", jit=False)
        st = rz.resilience_stats()
        assert st["runs"] == 1 and st["degraded"] == 1
        assert st["fallback_rungs"] == {"compiled": 1}
        recs = rz.degradation_records(kernel="xnn_f32_vmul_ukernel")
        assert len(recs) == 1 and recs[0]["used"] == "compiled"
        assert recs[0]["degraded"]
        assert recs[0]["attempts"][0]["error_type"] == "RevecVeto"

    def test_report_resilience_column(self, kernels):
        k = kernels["xnn_f32_vadd_ukernel"]
        args = _args_for("xnn_f32_vadd_ukernel")
        rep = port.report(k, *args, sweep=("rvv-128", "rvv-512"),
                          resilience=True)
        for t in ("rvv-128", "rvv-512"):
            r = rep["targets"][t]["resilience"]
            assert r["used"] == "compiled+revec" and not r["degraded"]
        assert "resilience (ladder rung used)" in \
            port.format_report(rep)


# ---------------------------------------------------------------------------
# the contract per fault class: what a fault may cost, and what it never may
# ---------------------------------------------------------------------------

# fault class -> (seam, error, times, expected rung, expected degraded):
# a veto or a persistent failure always degrades; a transient timeout,
# an eviction storm or a corrupted cache entry never does
FAULT_CLASSES = {
    "revec_veto": ("revec.retile", "RevecVeto", None, "compiled", True),
    "compile_fail": ("compile.trace", "CompileError", None, "interp",
                     True),
    "runtime_fault": ("compile.run", "ExecError", None, "interp", True),
    "transient_timeout": ("compile.trace", "CompileTimeout", 1,
                          "compiled+revec", False),
    "eviction_storm": (None, None, None, "compiled+revec", False),
    "corrupted_cache": (None, None, None, "compiled+revec", False),
}
# an elementwise strip, a reduction and a widening MACC
CONTRACT_KERNELS = ("xnn_f32_vadd_ukernel", "xnn_f32_vdot_ukernel",
                    "qs8_vmlal_dot_ukernel")
CONTRACT_TARGETS = ("rvv-128", "rvv-1024")


def _faulted_run(cls, k, args, t):
    seam, err, times, _, _ = FAULT_CLASSES[cls]
    if cls == "eviction_storm":
        with fi.eviction_storm(1):
            return rz.run_resilient(k, *args, target=t, jit=False)
    port.compiled_cache_clear()
    if cls == "corrupted_cache":
        k.compile(target=t, revec=True, jit=False)
        assert fi.corrupt_cache_entry(k.fn.name)
        return rz.run_resilient(k, *args, target=t, jit=False)
    with fi.injected(seam, error=getattr(rz, err), times=times):
        return rz.run_resilient(k, *args, target=t, jit=False)


@pytest.mark.parametrize("cls", sorted(FAULT_CLASSES))
def test_fault_class_contract(kernels, cls):
    """Per fault class, over an elementwise, a reduction and a widening
    kernel on rvv-128 and rvv-1024: zero silent corruption (bitwise the
    fault-free output of the rung that served), the expected rung every
    time, a fallback rate exactly as expected, and a DegradationRecord
    for every degraded run."""
    *_, want_rung, want_degraded = FAULT_CLASSES[cls]
    degraded = []
    for kname in CONTRACT_KERNELS:
        k, args = kernels[kname], _args_for(kname)
        for t in CONTRACT_TARGETS:
            port.compiled_cache_clear()
            out, _ = rz.run_resilient(k, *args, target=t, jit=False)
            ref = {"compiled+revec": out,
                   "compiled": k.compile(target=t, revec=False,
                                         jit=False)(*args),
                   "interp": k(*args, target=t)}
            rz.reset_resilience()
            out, rec = _faulted_run(cls, k, args, t)
            label = f"{cls}: {kname}@{t}"
            _bitwise_equal(out, ref[rec.used], label)
            assert rec.used == want_rung, f"{label} served by {rec.used}"
            if rec.degraded:
                assert rz.degradation_records(kernel=k.fn.name), \
                    f"{label}: degraded run left no DegradationRecord"
            degraded.append(rec.degraded)
    assert sum(degraded) / len(degraded) == float(want_degraded), degraded


# ---------------------------------------------------------------------------
# circuit breaker
# ---------------------------------------------------------------------------

class TestCircuitBreaker:

    def test_quarantine_after_threshold(self, kernels):
        """After `threshold` consecutive failures the rung is skipped
        without an attempt: the seam's fire count freezes."""
        k = kernels["xnn_f32_vadd_ukernel"]
        args = _args_for("xnn_f32_vadd_ukernel")
        brk = rz.breaker()
        port.compiled_cache_clear()
        with fi.injected("compile.trace", error=rz.CompileError,
                         times=None) as plan:
            for _ in range(brk.threshold):
                _, rec = rz.run_resilient(k, *args, target="rvv-128",
                                          jit=False)
                assert rec.used == "interp"
            fired_at_trip = plan.fired
            assert brk.is_open(("xnn_f32_vadd_ukernel", "rvv-128",
                                "compiled+revec"))
            # quarantined: both compiled rungs are skipped, the seam
            # never fires again, service continues on the floor
            _, rec = rz.run_resilient(k, *args, target="rvv-128",
                                      jit=False)
            assert plan.fired == fired_at_trip
            assert rec.used == "interp"
            assert [a.skipped for a in rec.attempts] == \
                [True, True, False]
            assert rec.attempts[0].error_type == "CircuitOpen"

    def test_success_closes_the_breaker(self, kernels):
        k = kernels["xnn_f32_vadd_ukernel"]
        args = _args_for("xnn_f32_vadd_ukernel")
        brk = rz.breaker()
        key = ("xnn_f32_vadd_ukernel", "rvv-128", "compiled+revec")
        for _ in range(brk.threshold):
            brk.failure(key)
        assert brk.is_open(key)
        brk.reset(key)
        port.compiled_cache_clear()
        _, rec = rz.run_resilient(k, *args, target="rvv-128", jit=False)
        assert rec.used == "compiled+revec"
        assert not brk.is_open(key)


# ---------------------------------------------------------------------------
# compiled-kernel LRU under concurrency
# ---------------------------------------------------------------------------

class TestConcurrentCompile:

    def test_warmup_stampede_single_flight(self, kernels, monkeypatch):
        """Eight threads race the same (kernel, target) compile; the
        locked cache must build it exactly once and hand everyone the
        same object.  The pre-lock cache compiles 8 times (check-then-
        act race) — this is the regression test for it."""
        import time as _time
        k = kernels["xnn_f32_vdot_ukernel"]
        port.compiled_cache_clear()
        calls = []
        real = port.compile_fn

        def counting(*a, **kw):
            calls.append(threading.get_ident())
            _time.sleep(0.05)       # widen the race window
            return real(*a, **kw)

        monkeypatch.setattr(port, "compile_fn", counting)
        barrier = threading.Barrier(8)
        got, errs = [], []

        def worker():
            try:
                barrier.wait()
                got.append(k.compile(target="rvv-128", jit=False))
            except BaseException as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=worker) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        assert len(calls) == 1, \
            f"stampede compiled {len(calls)} times; want single-flight"
        assert len({id(g) for g in got}) == 1
        info = port.compiled_cache_info()
        assert info["misses"] == 1 and info["hits"] == 7

    def test_concurrent_distinct_keys_dont_serialize_results(
            self, kernels):
        """Different (kernel, target) keys compile concurrently and all
        land in the cache intact."""
        names = KERNELS[:6]
        port.compiled_cache_clear()
        errs = []

        def worker(name, tgt):
            try:
                ck = kernels[name].compile(target=tgt, jit=False)
                assert ck.source_kernel is kernels[name]
            except BaseException as e:  # noqa: BLE001
                errs.append((name, e))

        threads = [threading.Thread(target=worker, args=(n, t))
                   for n in names for t in ("rvv-128", "rvv-512")]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        assert port.compiled_cache_info()["size"] == len(names) * 2


# ---------------------------------------------------------------------------
# serving engine under chaos
# ---------------------------------------------------------------------------

def _engine_req(kernels, kname, n, seed=0, **kw):
    k = kernels[kname]
    rng = np.random.default_rng(seed)
    args = []
    for p in k.fn.params:
        if isinstance(p.type, PtrType):
            args.append(rng.standard_normal(n).astype(np.float32))
        else:
            args.append(n)
    return Request(k, args, **kw)


class TestEngineChaos:

    def test_poisoned_kernel_spares_batch_mates(self, kernels):
        """Kernel A's batched executable faults; A's rows degrade to
        per-row ladder recovery (same values), B's rows never leave the
        fast path."""
        eng = PortEngine(target="rvv-128", max_batch=4)
        a = [_engine_req(kernels, "xnn_f32_vadd_ukernel", n, seed=n)
             for n in (8, 16)]
        b = [_engine_req(kernels, "xnn_f32_vmul_ukernel", n, seed=n)
             for n in (8, 16)]
        ref = [np.asarray(r.kernel(*r.args)) for r in a + b]
        with fi.injected(
                "engine.batch", error=rz.ExecError, times=None,
                where=lambda c: c["kernel"] == "xnn_f32_vadd_ukernel"):
            res = eng.submit(a + b)
        for got, want in zip(res, ref):
            _bitwise_equal(got, want, "engine poisoned-A")
        st = eng.stats()["resilience"]
        assert st["batch_faults"] >= 1
        assert st["row_fallbacks"] == len(a)
        assert st["errors_returned"] == 0

    def test_exhausted_row_is_typed_not_fatal(self, kernels):
        """A row whose own ladder also exhausts resolves to its typed
        error in the results; healthy rows still answer."""
        eng = PortEngine(target="rvv-128", max_batch=4)
        bad = _engine_req(kernels, "xnn_f32_vadd_ukernel", 8)
        good = _engine_req(kernels, "xnn_f32_vmul_ukernel", 8)
        want = np.asarray(good.kernel(*good.args))
        port.compiled_cache_clear()
        poisoned = lambda c: c.get("kernel") == "xnn_f32_vadd_ukernel"  # noqa: E731
        with fi.injected("engine.batch", error=rz.ExecError,
                         times=None, where=poisoned), \
             fi.injected("compile.trace", error=rz.CompileError,
                         times=None, where=poisoned), \
             fi.injected("interp.run", error=rz.ExecError,
                         times=None, where=poisoned):
            res = eng.submit([bad, good])
        assert isinstance(res[0], rz.LadderExhausted)
        assert res[0].kernel == "xnn_f32_vadd_ukernel"
        _bitwise_equal(res[1], want, "engine healthy-B")
        assert eng.stats()["resilience"]["errors_returned"] == 1

    def test_on_error_raise_mode(self, kernels):
        eng = PortEngine(target="rvv-128", on_error="raise")
        req = _engine_req(kernels, "xnn_f32_vadd_ukernel", 8,
                          deadline_s=0.0)
        with pytest.raises(rz.DeadlineExceeded):
            eng.submit([req])

    def test_deadline_resolves_typed_without_stalling(self, kernels):
        eng = PortEngine(target="rvv-128", max_batch=4)
        live = _engine_req(kernels, "xnn_f32_vadd_ukernel", 8)
        dead = _engine_req(kernels, "xnn_f32_vadd_ukernel", 16,
                           deadline_s=0.0)
        want = np.asarray(live.kernel(*live.args))
        res = eng.submit([live, dead])
        _bitwise_equal(res[0], want, "engine live-row")
        assert isinstance(res[1], rz.DeadlineExceeded)
        assert eng.stats()["resilience"]["deadline_misses"] == 1

    def test_breaker_quarantines_batched_compile(self, kernels):
        """Persistent batched-compile poison trips the breaker on both
        batched rungs; later slates skip the compile entirely (the seam
        stops firing) and still answer via per-row recovery."""
        eng = PortEngine(target="rvv-128", max_batch=4)
        brk = rz.breaker()
        req = _engine_req(kernels, "xnn_f32_vdot_ukernel", 8)
        want = np.asarray(req.kernel(*req.args))
        port.compiled_cache_clear()
        tgt = resolve_target("rvv-128")
        with fi.injected("engine.batch", error=rz.CompileError,
                         times=None):
            for _ in range(brk.threshold):
                with fi.injected("compile.trace", error=rz.CompileError,
                                 times=None,
                                 where=lambda c: True) as plan:
                    res = eng.submit([req])
                    assert isinstance(res[0], rz.PortError) or \
                        np.array_equal(np.asarray(res[0]), want)
        assert any(k[0] == "xnn_f32_vdot_ukernel" and k[1] == tgt.name
                   for k in brk.open_keys())

    def test_program_falls_back_to_narrow_rung(self, kernels):
        """A revec-rung-only veto makes the *batched* program fall back
        to the narrow compiled rung — still batched, values identical."""
        eng = PortEngine(target="rvv-128", max_batch=4)
        reqs = [_engine_req(kernels, "xnn_f32_vclamp_ukernel", n,
                            seed=n) for n in (8, 16, 24)]
        ref = [np.asarray(r.kernel(*r.args)) for r in reqs]
        port.compiled_cache_clear()
        with fi.injected("revec.retile", error=rz.RevecVeto,
                         times=None):
            res = eng.submit(reqs)
        for got, want in zip(res, ref):
            _bitwise_equal(got, want, "engine narrow-fallback")
        st = eng.stats()["resilience"]
        assert st["program_fallbacks"] == 1
        assert st["batch_faults"] == 0      # still served batched


# ---------------------------------------------------------------------------
# parser / lowering crash UX: the mutation sweep
# ---------------------------------------------------------------------------

def _corpus_sources():
    for fname in sorted(os.listdir(CORPUS)):
        if fname.endswith(".c"):
            with open(os.path.join(CORPUS, fname)) as f:
                yield fname, f.read()


class TestMutationSweep:

    def test_no_mutation_escapes_the_taxonomy(self):
        """Truncations and random single-byte deletions of every corpus
        source must either still compile or raise a typed PortError —
        never a raw IndexError/KeyError/AttributeError."""
        checked = 0
        for fname, src in _corpus_sources():
            rng = random.Random(zlib.crc32(fname.encode()))
            mutants = [src[:len(src) // 4], src[:len(src) // 2],
                       src[:3 * len(src) // 4], src[:-1]]
            for _ in range(6):
                i = rng.randrange(len(src))
                mutants.append(src[:i] + src[i + 1:])
            for mut in mutants:
                checked += 1
                try:
                    port.compile_kernel(mut, filename=fname)
                except rz.PortError:
                    pass        # typed: the contract holds
                except RecursionError:
                    pytest.fail(f"{fname}: mutant blew the stack")
        assert checked >= 20 * 10       # >= 20 corpus files x 10 mutants

    def test_parse_error_has_file_line_col(self):
        src = "void k(int n, float *a) {\n    float x = ;\n}\n"
        with pytest.raises(rz.ParseError) as ei:
            port.compile_kernel(src, filename="k.c")
        e = ei.value
        assert isinstance(e, SyntaxError)       # legacy base preserved
        assert e.provenance["file"] == "k.c"
        assert e.line == 2
        assert str(e).startswith("k.c:2:")

    def test_lexer_error_is_parse_error_with_position(self):
        with pytest.raises(rz.ParseError) as ei:
            port.compile_kernel("void k() {\n  int x = 1 @ 2;\n}",
                                filename="lex.c")
        assert ei.value.line == 2
        assert "unexpected character" in str(ei.value)

    def test_truncated_source_names_eof(self):
        src = "void k(int n, float *a) {\n    for (int i = 0; i < n"
        with pytest.raises(rz.ParseError) as ei:
            port.compile_kernel(src, filename="t.c")
        assert "<eof>" in str(ei.value)

    def test_unknown_intrinsic_names_itself_and_line(self):
        src = ("#include <arm_neon.h>\n"
               "void k(int n, float *a) {\n"
               "    float32x4_t v = vfrobnicateq_f32(a);\n"
               "}\n")
        with pytest.raises(rz.LowerError) as ei:
            port.compile_kernel(src, filename="u.c")
        e = ei.value
        assert isinstance(e, TypeError)         # legacy base preserved
        assert e.provenance["intrinsic"] == "vfrobnicateq_f32"
        assert e.line == 3 and e.kernel == "k"
        assert e.provenance["file"] == "u.c"

    def test_bad_tuple_index_has_line(self):
        src = ("#include <arm_neon.h>\n"
               "void k(float *a) {\n"
               "    float32x4x2_t t = vld2q_f32(a);\n"
               "    float32x4_t x = t.val[7];\n"
               "}\n")
        with pytest.raises(rz.LowerError, match=r"val\[7\] out of "
                                                r"range") as ei:
            port.compile_kernel(src, filename="v.c")
        assert ei.value.line == 4

    def test_nonpointer_indexing_is_typed(self):
        # previously a raw AttributeError out of the lowerer
        src = "void k(int n, float *a) {\n    float x = n[3];\n}\n"
        with pytest.raises(rz.LowerError):
            port.compile_kernel(src, filename="w.c")


# ---------------------------------------------------------------------------
# directed simulator faults: errors name the mnemonic and site
# ---------------------------------------------------------------------------

def _prog(target, body, params=(), writes=()):
    return RvvProgram(fn_name="t", target=resolve_target(target),
                      params=list(params), writes=list(writes),
                      body=list(body))


class TestSimFaults:

    def test_oob_access_names_mnemonic_and_site(self):
        body = [VSetVL("vl0", 4, 32, 1),
                V(mnem="vle", dst="v1", srcs=(("p", "pa"),),
                  dtype="float32", sew=32, emul=1, vl="vl0",
                  site="vld1q_f32")]
        sim = RvvSim(_prog("rvv-128", body))
        sim.env["pa"] = ("a", 6)
        sim.memory["a"] = np.zeros(8, np.float32)
        with pytest.raises(SimError) as ei:
            sim._block(body)
        e = ei.value
        assert "vle" in str(e) and "outside a[8]" in str(e)
        assert e.provenance["mnemonic"] == "vle"
        assert e.provenance["site"] == "vld1q_f32"
        assert e.stage == "simulate"

    def test_undefined_vreg_read_names_mnemonic(self):
        body = [VSetVL("vl0", 4, 32, 1),
                V(mnem="vadd.vv", dst="v2",
                  srcs=(("v", "v0"), ("v", "v1")), dtype="int32",
                  sew=32, emul=1, vl="vl0", site="vaddq_s32")]
        sim = RvvSim(_prog("rvv-128", body))
        with pytest.raises(SimError, match="undefined vreg") as ei:
            sim._block(body)
        assert ei.value.provenance["mnemonic"] == "vadd.vv"
        assert ei.value.provenance["site"] == "vaddq_s32"

    def test_vector_before_vsetvli_names_mnemonic(self):
        body = [V(mnem="vadd.vv", dst="v1",
                  srcs=(("v", "v0"), ("v", "v0")), dtype="int32",
                  sew=32, emul=1, vl="vl0")]
        sim = RvvSim(_prog("rvv-128", body))
        with pytest.raises(SimError, match="before any vsetvli") as ei:
            sim.run()
        assert ei.value.provenance["mnemonic"] == "vadd.vv"

    def test_bad_vxrm_mode_is_typed(self):
        body = [VSetVL("vl0", 4, 16, 1),
                V(mnem="vmv.v.x", dst="vw", srcs=(("x", "z"),),
                  dtype="int32", sew=32, emul=2, vl="vl0"),
                V(mnem="vnclip.wi", dst="vn",
                  srcs=(("v", "vw"), ("i", 1)),
                  dtype="int16", dtype_src="int32", sew=16, emul=1,
                  vl="vl0", vxrm="zz", site="vqshrn_n_s32")]
        sim = RvvSim(_prog("rvv-128", body))
        sim.env["z"] = 70000
        with pytest.raises(SimError, match="bad vxrm mode 'zz'") as ei:
            sim._block(body)
        assert ei.value.provenance["mnemonic"] == "vnclip.wi"
        assert ei.value.provenance["site"] == "vqshrn_n_s32"

    def test_sim_error_is_port_error(self):
        assert issubclass(SimError, rz.PortError)
        assert SimError is rz.SimError

    def test_injected_memory_fault_carries_context(self):
        body = [VSetVL("vl0", 4, 32, 1),
                V(mnem="vle", dst="v1", srcs=(("p", "pa"),),
                  dtype="float32", sew=32, emul=1, vl="vl0",
                  site="vld1q_f32")]
        sim = RvvSim(_prog("rvv-128", body))
        sim.env["pa"] = ("a", 0)
        sim.memory["a"] = np.zeros(8, np.float32)
        with fi.injected("sim.mem", error=rz.SimError, times=1):
            with pytest.raises(SimError) as ei:
                sim._block(body)
        assert "injected fault" in str(ei.value)
        assert ei.value.provenance["mnemonic"] == "vle"
        assert ei.value.provenance["kernel"] == "t"


# ---------------------------------------------------------------------------
# taxonomy invariants
# ---------------------------------------------------------------------------

class TestTaxonomy:

    def test_hierarchy_and_legacy_bases(self):
        assert issubclass(rz.ParseError, SyntaxError)
        assert issubclass(rz.LowerError, TypeError)
        for cls in (rz.CompileError, rz.ExecError, rz.SimError,
                    rz.CacheCorruption, rz.DeadlineExceeded,
                    rz.LadderExhausted):
            assert issubclass(cls, RuntimeError)
        for cls in (rz.ParseError, rz.LowerError, rz.RevecVeto,
                    rz.CompileError, rz.CompileTimeout, rz.ExecError,
                    rz.SimError, rz.CacheCorruption,
                    rz.DeadlineExceeded, rz.LadderExhausted):
            assert issubclass(cls, rz.PortError)

    def test_provenance_rendering_and_add_context(self):
        e = rz.LowerError("bad thing", line=3, col=7)
        e.add_context(file="k.c", kernel="vadd")
        s = str(e)
        assert s.startswith("k.c:3:7: bad thing")
        assert "kernel=vadd" in s and "stage=lower" in s
        # add_context never overwrites what the raise site recorded
        e.add_context(line=99)
        assert e.line == 3

    def test_transient_marker(self):
        assert rz.CompileTimeout("t").transient
        assert not rz.CompileError("c").transient

    def test_wrap_preserves_cause(self):
        try:
            raise ValueError("root cause")
        except ValueError as v:
            e = rz.wrap_error(v, stage="compile", kernel="k",
                              target="rvv-128")
        assert isinstance(e, rz.CompileError)
        assert isinstance(e.__cause__, ValueError)
        assert e.kernel == "k"
