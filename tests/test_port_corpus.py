"""Corpus acceptance: every NEON kernel in examples/neon_corpus parses,
translates, executes through registry.dispatch, and matches its NumPy
reference; the migration sweep reproduces the paper's selection
structure (Listing 5-7 wins, Listing 8 no-ops, Table-2 fallbacks)."""
import json
import os
import sys

import numpy as np
import pytest

CORPUS = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                      "examples", "neon_corpus"))
sys.path.insert(0, CORPUS)

import harness  # noqa: E402

from repro import port  # noqa: E402


def _case_ids():
    return [c.kernel for c in harness.cases()]


@pytest.fixture(scope="module")
def compiled():
    return {c.kernel: port.compile_file(os.path.join(CORPUS, c.file),
                                        name=c.kernel)
            for c in harness.cases()}


def test_corpus_is_big_enough():
    assert len(harness.cases()) >= 10


@pytest.mark.parametrize("case", harness.cases(), ids=_case_ids())
def test_corpus_kernel_matches_reference(case, compiled):
    k = compiled[case.kernel]
    rng = np.random.default_rng(hash(case.kernel) % 2**32)
    args = case.make_args(rng)
    got = k(*args)
    want = case.reference(*args)
    np.testing.assert_allclose(np.asarray(got), want,
                               rtol=case.rtol, atol=case.atol)


@pytest.mark.parametrize("kernel", ["xnn_f32_vadd_ukernel",
                                    "bitreverse_u8", "relu_bsl_f32"])
def test_corpus_executes_on_rvv_targets(kernel, compiled):
    """Selection flips per target must not change semantics."""
    case = next(c for c in harness.cases() if c.kernel == kernel)
    rng = np.random.default_rng(7)
    args = case.make_args(rng)
    want = case.reference(*args)
    for tname in ("rvv-64", "rvv-128"):
        got = compiled[kernel](*args, target=tname)
        np.testing.assert_allclose(np.asarray(got), want,
                                   rtol=case.rtol, atol=case.atol,
                                   err_msg=f"{kernel} on {tname}")


# PORT_SWEEP plus the LMUL=2 grouping column
SWEEP = ("rvv-64", "rvv-64-m2", "rvv-128", "rvv-256", "rvv-512",
         "rvv-1024")

# the paper's customized-conversion showcases (Listings 5/6/7): the
# cost-driven selection must beat the original-SIMDe ladder baseline
LISTING_KERNELS = ("fold_halves_f32", "relu_bsl_f32", "bitreverse_u8")
# simple arithmetic keeps the vector tier — no win to be had (Listing 8)
ARITH_KERNELS = ("xnn_f32_vadd_ukernel", "xnn_f32_vmul_ukernel")
# strip-pattern kernels the re-vectorizer must widen on rvv-1024
# (fold_halves is the deliberate counter-example: vget_high/low
# cross-lane structure keeps it at NEON granularity)
UNSCALABLE = ("fold_halves_f32",)
# kernels whose strips nest inside a scalar outer loop: the inner strip
# re-tiles, the outer loop is a *structural* narrow fallback the report
# must carry (not a silent one)
NESTED = ("qs8_gemm_mx8_ukernel", "f32_rowscale_ukernel")
# width-changing strips re-tile by the *narrow* side (lane groups): an
# 8-lane s8 D register has 16x headroom on rvv-1024, not the f32 8x
WIDENING_16 = ("qs8_vaddl_requant_ukernel", "qs8_vmul_requant_ukernel",
               "s8_shl1_widen_narrow_ukernel", "qs8_vmlal_dot_ukernel",
               "qs8_gemm_mx8_ukernel")

with open(os.path.join(os.path.dirname(__file__), "golden",
                       "port_counts.json")) as _f:
    PORT_GOLDEN = json.load(_f)


@pytest.fixture(scope="module")
def sweep_reports():
    """port.report (with the revec column) for every corpus kernel."""
    out = {}
    for i, case in enumerate(harness.cases(n=64)):
        k = port.compile_file(os.path.join(CORPUS, case.file),
                              name=case.kernel)
        args = case.make_args(np.random.default_rng(i))
        out[case.kernel] = port.report(k, *args, sweep=SWEEP,
                                       compiled=True)
    return out


def test_migration_sweep_properties(sweep_reports):
    reports = sweep_reports
    assert len(reports) >= 20, f"corpus shrank to {len(reports)} kernels"
    for name in LISTING_KERNELS:
        rep = reports[name]["targets"]["rvv-128"]
        assert rep["speedup"] > 1.0, \
            f"{name}: customized conversion not cheaper ({rep['speedup']}x)"
    for name in ARITH_KERNELS:
        rep = reports[name]["targets"]["rvv-128"]
        assert abs(rep["speedup"] - 1.0) < 1e-9, \
            f"{name}: simple arithmetic should keep the vector tier"
    # Table-2 'x' entries: on rvv-64 every Q-register intrinsic falls
    # back; LMUL=2 grouping restores the native mapping
    vadd = reports["xnn_f32_vadd_ukernel"]
    assert not vadd["targets"]["rvv-64"]["maps"]["vaddq_f32"]
    assert vadd["targets"]["rvv-64-m2"]["maps"]["vaddq_f32"]
    assert vadd["targets"]["rvv-64"]["total_instrs"] > \
        vadd["targets"]["rvv-128"]["total_instrs"]

    # the re-vectorizer: rvv-1024 must finally diverge from rvv-128
    for name, rep in reports.items():
        r1024 = rep["targets"]["rvv-1024"]["revec"]
        if name in UNSCALABLE:
            assert r1024["factor"] == 1, \
                f"{name}: unscalable kernel must not re-tile"
            assert r1024["vetoes"], \
                f"{name}: narrow fallback must carry a structured veto"
            continue
        r128 = rep["targets"]["rvv-128"]["revec"]
        want = 16 if name in WIDENING_16 else 8
        assert r1024["factor"] == want, \
            f"{name}: expected {want}x re-tile on rvv-1024, got " \
            f"{r1024['factor']}x"
        assert r1024["retiled"] >= 1, f"{name}: no strip re-tiled"
        if name in NESTED:
            assert r1024["narrow_fallbacks"] >= 1 and r1024["vetoes"], \
                f"{name}: nested outer loop must be a recorded veto"
        else:
            assert r1024["narrow_fallbacks"] == 0, \
                f"{name}: unexpected narrow fallback " \
                f"({r1024['vetoes']})"
        assert r1024["total_instrs"] * 2 <= r128["total_instrs"], \
            f"{name}: rvv-1024 re-tile only " \
            f"{r128['total_instrs'] / max(1, r1024['total_instrs']):.2f}x " \
            f"under rvv-128 (want >= 2x)"


def test_listing_patterns_win_on_rvv128(sweep_reports):
    """The customized conversions carry the corpus exactly where the
    paper says: vrbit (Listing 7) is the largest win."""
    speedups = {name: rep["targets"]["rvv-128"]["speedup"]
                for name, rep in sweep_reports.items()}
    assert max(speedups, key=speedups.get) == "bitreverse_u8"
    assert speedups["bitreverse_u8"] > 4.0
    assert speedups["relu_bsl_f32"] > 1.5
    assert speedups["fold_halves_f32"] > 1.5


@pytest.mark.parametrize("kernel", sorted(PORT_GOLDEN))
def test_port_counts_match_golden(kernel, sweep_reports):
    """Estimated counts and re-tile structure on every sweep target are
    deterministic: any drift is a reviewed change of
    ``tests/golden/port_counts.json``.  (Among them: bitreverse_u8's
    ``vrbitq_u8`` is unmapped on rvv-64, and vadd re-tiles 8x on
    rvv-1024.)"""
    for t in SWEEP:
        row = sweep_reports[kernel]["targets"][t]
        rv = row["revec"]
        fresh = {"total_instrs": row["total_instrs"],
                 "baseline_instrs": row["baseline_total_instrs"],
                 "scalar_instrs": row["scalar_instrs"],
                 "revec_instrs": rv["total_instrs"],
                 "retile_factor": rv["factor"],
                 "strips": rv["strips"],
                 "retiled_strips": rv["retiled"],
                 "masked_tails": rv["masked"],
                 "narrow_fallbacks": rv["narrow_fallbacks"],
                 "unmapped": sorted(i for i, ok in row["maps"].items()
                                    if not ok)}
        assert fresh == PORT_GOLDEN[kernel][t], \
            f"{kernel}@{t} drifted from the golden; fresh row: {fresh}"


@pytest.mark.parametrize("case", harness.cases(n=2048, tail_n=2051),
                         ids=_case_ids())
def test_retiled_rvv1024_estimate_at_serving_size(case, compiled):
    """At serving size, re-tiled rvv-1024 must retire >= 4x fewer
    estimated vector instructions than the fixed-128-bit port for every
    scalable strip kernel; the unscalable one must not move."""
    k = compiled[case.kernel]
    args = case.make_args(np.random.default_rng(0))
    fixed = k.estimate(*args, target="rvv-1024")["total_instrs"]
    rev = k.compile(target="rvv-1024", revec=True).estimate(
        *args)["total_instrs"]
    if case.kernel in UNSCALABLE:
        assert rev == fixed, (rev, fixed)
    else:
        assert fixed >= 4 * rev, \
            f"{case.kernel}: re-tiled rvv-1024 only " \
            f"{fixed / max(1, rev):.2f}x fewer instrs (want >= 4x)"


def test_retile_coverage_on_rvv1024(compiled):
    """A silent narrow fallback is a bug, not a perf footnote: on
    rvv-1024, 23 of the 24 corpus kernels re-tile, and exactly three
    carry one recorded narrow fallback each."""
    retiled, fallbacks = [], {}
    for name, k in compiled.items():
        res = k.retile("rvv-1024")
        if res.factor > 1 and res.retiled:
            retiled.append(name)
        if res.narrow_fallbacks:
            fallbacks[name] = res.narrow_fallbacks
    assert (len(retiled), len(compiled)) == (23, 24), sorted(retiled)
    assert fallbacks == {"f32_rowscale_ukernel": 1, "fold_halves_f32": 1,
                         "qs8_gemm_mx8_ukernel": 1}, fallbacks
