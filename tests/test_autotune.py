"""repro.port.autotune: calibration fit, register-pressure LMUL model,
knob search, and the persistent autotuning cache.

The cache contracts under test are the deploy-critical ones: tuned
decisions survive a *fresh process* (subprocess round-trip, not just a
new object), a corrupt or truncated cache file degrades to static
behavior with a typed error instead of failing compiles, and
concurrent ``tune_or_get``/``PortEngine.warmup`` callers are
single-flight — each (kernel, target) is measured exactly once.
"""
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CORPUS = os.path.join(ROOT, "examples", "neon_corpus")
sys.path.insert(0, CORPUS)

import harness  # noqa: E402

from repro import port, rvv  # noqa: E402
from repro.core import targets, trace  # noqa: E402
from repro.port import autotune  # noqa: E402
from repro.port.resilience import CacheCorruption, PortError  # noqa: E402

CASES = {c.kernel: c for c in harness.cases(n=64, tail_n=67)}


@pytest.fixture(autouse=True)
def _isolate_process_state():
    """Autotune installs process-wide state (the registry calibration
    and the module-level cache); every test starts and ends clean."""
    autotune.reset_cache()
    autotune.uninstall()
    yield
    autotune.reset_cache()
    autotune.uninstall()


def _kernel(name):
    case = CASES[name]
    return port.compile_file(os.path.join(CORPUS, case.file),
                             name=case.kernel)


def _args(name, seed=0):
    return CASES[name].make_args(np.random.default_rng(seed))


def _items(names, seed=0):
    return [(_kernel(n), _args(n, seed)) for n in names]


# ---------------------------------------------------------------------------
# calibration
# ---------------------------------------------------------------------------

def test_calibration_fit_install_uninstall():
    cal = autotune.calibrate(_items(["xnn_f32_vadd_ukernel",
                                     "xnn_f32_vmul_ukernel"]))
    assert cal.factors, "no factors fit"
    assert cal.fitted_on == autotune.CALIBRATION_TARGETS
    for op, f in cal.factors.items():
        assert f > 0, (op, f)
        assert cal.samples[op]["estimated"] > 0
    # predict divides by LMUL (estimates charge lmul micro-ops per
    # grouped issue; the machine retires one instruction per mnemonic)
    per = {"site": {"isa_op": next(iter(cal.factors)), "instrs": 80}}
    assert autotune.CalibrationModel.predict(cal, per, 4) * 4 == \
        pytest.approx(autotune.CalibrationModel.predict(cal, per, 1))
    cal.install()
    try:
        got = trace.get_calibration()
        assert got is not None and got["factors"] == cal.factors
    finally:
        autotune.uninstall()
    assert trace.get_calibration() is None


def test_calibration_survives_cache_roundtrip(tmp_path):
    cal = autotune.calibrate(_items(["xnn_f32_vadd_ukernel"]))
    path = str(tmp_path / "at.json")
    autotune.AutotuneCache(path).set_calibration(cal)
    back = autotune.AutotuneCache(path, strict=True).calibration
    assert back is not None
    assert back.factors == cal.factors
    assert back.samples == cal.samples


# ---------------------------------------------------------------------------
# register-pressure LMUL model
# ---------------------------------------------------------------------------

def test_admissible_lmuls_respects_widening_emul_cap():
    # uniform-width kernel: the full ladder is legal
    assert autotune.admissible_lmuls(
        _kernel("xnn_f32_vadd_ukernel"), "rvv-128") == (1, 2, 4, 8)
    # 2xSEW widening body: LMUL=8 would demand EMUL=16 register groups
    wide = _kernel("qs8_vaddl_requant_ukernel")
    assert autotune.width_scale(wide.fn) >= 2
    adm = autotune.admissible_lmuls(wide, "rvv-128")
    assert 8 not in adm and adm, adm
    # fixed-width targets have no grouping to tune
    assert targets.get_target("tpu-v5e").admissible_lmuls() == (1,)


# ---------------------------------------------------------------------------
# the knob search
# ---------------------------------------------------------------------------

def test_tune_beats_static_and_conforms():
    name = "xnn_f32_vadd_ukernel"
    k, args = _kernel(name), _args(name)
    d = autotune.tune(k, args, "rvv-128")
    assert d.lmul in autotune.admissible_lmuls(k, "rvv-128")
    assert d.static is not None and d.measured is not None
    assert d.measured < d.static, \
        f"vadd must improve on rvv-128 ({d.measured} vs {d.static})"
    assert d.improvement > 1.0
    # the tuned configuration's stream conforms to the reference
    tgt = targets.with_lmul(targets.get_target("rvv-128"), d.lmul)
    out, _ = rvv.run(rvv.emit(k, tgt, factor_cap=d.factor_cap,
                              tail=d.tail), *args, with_counts=True)
    np.testing.assert_allclose(np.asarray(out),
                               CASES[name].reference(*args),
                               rtol=1e-5, atol=1e-6)


def test_tune_rejects_non_rvv_target():
    with pytest.raises(ValueError):
        autotune.tune(_kernel("xnn_f32_vadd_ukernel"),
                      _args("xnn_f32_vadd_ukernel"), "tpu-v5e")


def test_tuned_decision_never_worse_than_static():
    """The fallback contract: when nothing beats static, the returned
    decision *is* the static configuration with its measurement."""
    name = "fold_halves_f32"     # cross-lane: fixed NEON granularity
    if name not in CASES:
        pytest.skip("fold kernel not in corpus")
    k, args = _kernel(name), _args(name)
    d = autotune.tune(k, args, "rvv-128")
    assert d.measured <= d.static


def test_tuned_compile_applies_cached_decision(tmp_path):
    name = "xnn_f32_vadd_ukernel"
    k, args = _kernel(name), _args(name)
    cache = autotune.set_cache_path(str(tmp_path / "at.json"))
    d = cache.tune_or_get(k, args, "rvv-128")
    tuned = k.compile(target="rvv-128", revec=True, jit=False,
                      tuned=True)
    assert tuned.target.lmul == d.lmul
    assert tuned.tail == d.tail
    np.testing.assert_allclose(np.asarray(tuned(*args)),
                               CASES[name].reference(*args),
                               rtol=1e-5, atol=1e-6)
    # a kernel with no cached decision compiles exactly as untuned
    other = _kernel("xnn_f32_vmul_ukernel")
    plain = other.compile(target="rvv-128", revec=True, jit=False,
                          tuned=True)
    assert plain.target.lmul == targets.get_target("rvv-128").lmul


# ---------------------------------------------------------------------------
# persistence: decisions survive a *process* restart
# ---------------------------------------------------------------------------

def test_decisions_survive_fresh_process(tmp_path):
    name = "xnn_f32_vadd_ukernel"
    k, args = _kernel(name), _args(name)
    path = str(tmp_path / "autotune.json")
    cache = autotune.AutotuneCache(path)
    d = cache.tune_or_get(k, args, "rvv-128")

    prog = f"""
import json, os, sys
sys.path.insert(0, {CORPUS!r})
from repro import port
from repro.port import autotune
k = port.compile_file(os.path.join({CORPUS!r}, "vadd.c"),
                      name="xnn_f32_vadd_ukernel")
c = autotune.AutotuneCache({path!r}, strict=True)
assert c.load_error is None
d = c.get(k, "rvv-128")
assert d is not None, "decision lost across process restart"
print(json.dumps(d.to_dict()))
"""
    # the child stays on the CPU: a TPU belongs to one process at a time
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", prog], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    back = json.loads(r.stdout.strip().splitlines()[-1])
    assert back == d.to_dict(), \
        "reloaded decision differs from the tuned one"


def test_ir_fingerprint_orphans_stale_decisions(tmp_path):
    """Editing a kernel changes its fingerprint: the stale decision is
    simply never found (invalidation by construction, no TTL logic)."""
    name = "xnn_f32_vadd_ukernel"
    k, args = _kernel(name), _args(name)
    cache = autotune.AutotuneCache(str(tmp_path / "at.json"))
    cache.put(k, "rvv-128", autotune.TunedDecision(lmul=8))
    assert cache.get(k, "rvv-128") is not None
    with open(os.path.join(CORPUS, "vadd.c")) as f:
        src = f.read()
    edited = src.replace("vaddq_f32(va, vb)", "vaddq_f32(vb, va)")
    assert edited != src
    other = port.compile_kernel(edited, name=name)
    assert cache.get(other, "rvv-128") is None, \
        "edited IR must not hit the old decision"


# ---------------------------------------------------------------------------
# corruption: typed failure, static degradation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("payload", [
    "not json at all {{{",
    '{"version": 999, "entries": {}}',
    '{"version": 1, "entries": {"k": {"lmul": 16}}}',
    "",
], ids=["garbage", "wrong-version", "bad-lmul", "truncated-empty"])
def test_corrupt_cache_degrades_to_static(tmp_path, payload):
    path = str(tmp_path / "bad.json")
    with open(path, "w") as f:
        f.write(payload)
    c = autotune.AutotuneCache(path)
    assert isinstance(c.load_error, CacheCorruption)
    assert isinstance(c.load_error, PortError)       # typed, catchable
    assert c.stats()["load_error"]
    k = _kernel("xnn_f32_vadd_ukernel")
    assert c.get(k, "rvv-128") is None               # static behavior
    # strict mode raises instead of degrading
    with pytest.raises(CacheCorruption):
        autotune.AutotuneCache(path, strict=True)


def test_corrupt_cache_never_breaks_tuned_compile(tmp_path):
    """compile(tuned=True) against a corrupt process-wide cache is the
    static compile — never an exception."""
    path = str(tmp_path / "bad.json")
    with open(path, "w") as f:
        f.write('{"version":')                        # truncated write
    autotune.set_cache_path(path)
    name = "xnn_f32_vadd_ukernel"
    k, args = _kernel(name), _args(name)
    tuned = k.compile(target="rvv-128", revec=True, jit=False,
                      tuned=True)
    assert tuned.target.lmul == targets.get_target("rvv-128").lmul
    np.testing.assert_allclose(np.asarray(tuned(*args)),
                               CASES[name].reference(*args),
                               rtol=1e-5, atol=1e-6)


def test_recovery_overwrites_corrupt_file(tmp_path):
    path = str(tmp_path / "bad.json")
    with open(path, "w") as f:
        f.write("garbage")
    c = autotune.AutotuneCache(path)
    assert c.load_error is not None
    c.put(_kernel("xnn_f32_vadd_ukernel"), "rvv-128",
          autotune.TunedDecision(lmul=4))
    # the atomic rewrite healed the file: a strict load now succeeds
    healed = autotune.AutotuneCache(path, strict=True)
    assert healed.load_error is None
    assert len(healed._entries) == 1


# ---------------------------------------------------------------------------
# concurrency: single-flight tuning, thread-safe warmup
# ---------------------------------------------------------------------------

def test_tune_or_get_is_single_flight(tmp_path, monkeypatch):
    name = "xnn_f32_vadd_ukernel"
    k, args = _kernel(name), _args(name)
    cache = autotune.AutotuneCache(str(tmp_path / "at.json"))

    calls = []
    gate = threading.Event()
    real_tune = autotune.tune

    def slow_tune(*a, **kw):
        calls.append(threading.get_ident())
        gate.wait(timeout=30)            # hold every racer in-flight
        return real_tune(*a, **kw)

    monkeypatch.setattr(autotune, "tune", slow_tune)
    results, errors = [], []

    def worker():
        try:
            results.append(cache.tune_or_get(k, args, "rvv-128"))
        except Exception as e:           # noqa: BLE001 — test harness
            errors.append(e)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    while not calls:                     # first tuner is inside tune()
        pass
    gate.set()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors
    assert len(calls) == 1, \
        f"single-flight violated: tune() ran {len(calls)} times"
    assert len(results) == 8
    assert all(r == results[0] for r in results)
    assert cache.stats()["inflight"] == 0


def test_concurrent_tuned_warmup(tmp_path):
    """Two engines warming up the same corpus concurrently against one
    tuned cache: no exception, and every compile resolves the same
    persisted decision."""
    from repro.serve import PortEngine

    names = ["xnn_f32_vadd_ukernel", "xnn_f32_vmul_ukernel"]
    cache = autotune.set_cache_path(str(tmp_path / "at.json"))
    for n in names:
        cache.tune_or_get(_kernel(n), _args(n), "rvv-128")
    corpus = {n: _kernel(n) for n in names}
    errors = []

    def worker():
        try:
            eng = PortEngine(target="rvv-128", tuned=True)
            eng.warmup(corpus)
        except Exception as e:           # noqa: BLE001 — test harness
            errors.append(e)

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors, errors
    d = cache.get(_kernel(names[0]), "rvv-128")
    tuned = _kernel(names[0]).compile(target="rvv-128", revec=True,
                                      jit=False, tuned=True)
    assert tuned.target.lmul == d.lmul
