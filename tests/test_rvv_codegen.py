"""repro.rvv differential conformance: every corpus kernel is emitted
as real RVV intrinsic C, executed on the in-repo instruction simulator,
and proven bitwise-equal (ints) / tolerance-equal (floats) to the exact
NumPy reference across the width family and adversarial tail lengths.

The compiled==interp==reference chain is already closed by
test_port_conformance.py; here the new edge is emitted-RVV-on-simulator
against the same references, plus the retired-instruction facts the
cost model can only estimate."""
import os
import sys
from functools import lru_cache

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CORPUS = os.path.join(ROOT, "examples", "neon_corpus")
GOLDEN_DIR = os.path.join(ROOT, "examples", "rvv_emitted")
sys.path.insert(0, CORPUS)

import harness  # noqa: E402

from repro import port, rvv  # noqa: E402

SWEEP = ("rvv-64", "rvv-128", "rvv-512", "rvv-1024")
CASES = {c.kernel: c for c in harness.cases()}

# kernels whose geometry is driven by harness's tail_n (scalar-tail
# kernels); the strip-only rest are covered by the main differential
TAIL_KERNELS = (
    "xnn_f32_vadd_ukernel", "xnn_f32_vmul_ukernel",
    "xnn_f32_vclamp_ukernel", "xnn_f32_vdot_ukernel",
    "qs8_vaddsub_biased_ukernel", "reduce_max_f32",
    "qs8_vaddl_requant_ukernel", "qs8_vmul_requant_ukernel",
    "s8_shl1_widen_narrow_ukernel", "cmul_f32_ukernel",
    "u8_rgbx_deinterleave_ukernel", "qs8_vmlal_dot_ukernel",
    "xnn_f32_vadd_x2_ukernel", "f32_rowscale_ukernel",
    "f32_butterfly_ukernel",
)


@lru_cache(maxsize=None)
def _kernel(name):
    case = CASES[name]
    return port.compile_file(os.path.join(CORPUS, case.file),
                             name=case.kernel)


def _tuple(x):
    return x if isinstance(x, tuple) else (x,)


def _assert_matches(got, want, case, ctx):
    got, want = _tuple(got), _tuple(want)
    assert len(got) == len(want), f"{ctx}: arity {len(got)} != {len(want)}"
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype, f"{ctx}: dtype {g.dtype} != {w.dtype}"
        if g.dtype.kind in "iu":
            np.testing.assert_array_equal(g, w, err_msg=ctx)
        else:
            np.testing.assert_allclose(g, w, rtol=case.rtol,
                                       atol=case.atol, err_msg=ctx)


# ---------------------------------------------------------------------------
# the tentpole bar: emitted RVV on the simulator == exact reference,
# for every corpus kernel, across the width family
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("target", SWEEP)
@pytest.mark.parametrize("name", sorted(CASES))
def test_emitted_rvv_matches_reference(name, target):
    case = CASES[name]
    args = case.make_args(np.random.default_rng(0))
    prog = rvv.emit(_kernel(name), target)
    out, counts = rvv.execute(prog, *args)
    _assert_matches(out, case.reference(*args), case,
                    f"{name} on {target}")
    assert counts["executed"] > 0
    assert counts["executed"] == (counts["vector"] + counts["vsetvli"]
                                  + counts["implicit_vsetvli"])
    # every emitted unit opens its strips with a real vsetvli
    c = prog.render_c()
    assert "__riscv_vsetvl_e" in c
    assert "#include <riscv_vector.h>" in c


@pytest.mark.parametrize("name", ["xnn_f32_vadd_ukernel",
                                  "qs8_vmul_requant_ukernel",
                                  "u8_rgbx_deinterleave_ukernel"])
def test_sim_matches_interpreter(name):
    # three-way closure on representative kernels: simulator output ==
    # the logical-ISA interpreter's (reference equality is proven above)
    case = CASES[name]
    args = case.make_args(np.random.default_rng(1))
    k = _kernel(name)
    out, _ = rvv.execute(rvv.emit(k, "rvv-128"), *args)
    _assert_matches(out, k(*args, target="rvv-128"), case,
                    f"{name}: sim vs interp")


# ---------------------------------------------------------------------------
# adversarial tails: n in {0, 1, K-1, K, K+1} around the re-tiled strip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("target,K", [("rvv-64", 16), ("rvv-1024", 256)])
def test_adversarial_tails(target, K):
    for t in (0, 1, K - 1, K, K + 1):
        for case in harness.cases(n=64, tail_n=t):
            if case.kernel not in TAIL_KERNELS:
                continue
            if case.kernel == "reduce_max_f32" and t == 0:
                # an empty max has no identity: the kernel's own
                # reference (and the interpreter) reject n=0 too
                continue
            args = case.make_args(np.random.default_rng(2 + t))
            out, _ = rvv.execute(rvv.emit(_kernel(case.kernel), target),
                                 *args)
            _assert_matches(out, case.reference(*args), case,
                            f"{case.kernel} on {target}, tail n={t}")


# ---------------------------------------------------------------------------
# retired-instruction facts: the scalable kernels must actually shrink
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["xnn_f32_vadd_ukernel",
                                  "qs8_vmlal_dot_ukernel",
                                  "qs8_vmul_requant_ukernel"])
def test_executed_scales_with_vlen(name):
    case = {c.kernel: c for c in harness.cases(n=1024,
                                               tail_n=1024)}[name]
    args = case.make_args(np.random.default_rng(3))
    k = _kernel(name)
    executed = {}
    for target in ("rvv-128", "rvv-1024"):
        out, counts = rvv.execute(rvv.emit(k, target), *args)
        _assert_matches(out, case.reference(*args), case,
                        f"{name} on {target} at n=1024")
        executed[target] = counts["executed"]
    ratio = executed["rvv-128"] / max(1, executed["rvv-1024"])
    assert ratio >= 4.0, \
        f"{name}: rvv-1024 retired only {ratio:.2f}x fewer than rvv-128"


def test_counts_reconcile_with_revec_estimate():
    # port.report(executed=True) joins retired counts to the cost
    # model's revec_instrs and flags per-intrinsic divergence
    case = CASES["xnn_f32_vadd_ukernel"]
    args = case.make_args(np.random.default_rng(4))
    rep = port.report(_kernel(case.kernel), *args,
                      sweep=("rvv-128", "rvv-1024"), executed=True)
    for tgt in ("rvv-128", "rvv-1024"):
        row = rep["targets"][tgt]["executed"]
        assert row["total"] > 0
        per = row["per_intrinsic"]
        assert per, f"{tgt}: empty per-intrinsic join"
        for label, cell in per.items():
            assert set(cell) == {"executed", "revec_instrs", "diverges"}
            assert cell["diverges"] == (cell["executed"]
                                        != cell["revec_instrs"])


def test_parked_offset_site_counted_and_conformant():
    """A vl=0 *parked* offset site must neither vanish from the
    executed-report join nor corrupt the result.

    On rvv-1024 the x2-unrolled add re-tiles 8x, so one strip iteration
    covers 64 elements with the second offset sites (a+4/b+4/y+4 in
    NEON units, offset 32 after re-tiling) active for cnt-32 elements.
    At n=20 that clamps to zero: the second sites are parked (vl=0) for
    the *entire* run.  The simulator counts per-site before mnemonic
    dispatch, so the retired stream is identical to a length where the
    sites are live — and the report's union join must carry every
    simulated site."""
    case = CASES["xnn_f32_vadd_x2_ukernel"]
    k = _kernel(case.kernel)
    prog = rvv.emit(k, "rvv-1024")

    def run(n, seed):
        rng = np.random.default_rng(seed)
        args = (n, rng.standard_normal(n).astype(np.float32),
                rng.standard_normal(n).astype(np.float32),
                np.zeros(n, np.float32))
        out, counts = rvv.execute(prog, *args)
        return args, out, counts

    # n=20 parks the offset-32 sites (vl=0); n=36 activates them
    args_p, out_p, parked = run(20, 5)
    _args_a, _out_a, active = run(36, 6)
    assert dict(parked["per_site"]) == dict(active["per_site"]), \
        "parked sites must retire the same stream as active ones"
    assert parked["executed"] > 0

    # conformance at the parking length: sim == interp == reference
    want = case.reference(*args_p)
    _assert_matches(out_p, want, case, "parked-site sim vs reference")
    _assert_matches(out_p, k(*args_p, target="rvv-1024"), case,
                    "parked-site sim vs interp")

    # the executed-report join is a union: every simulated site label
    # appears, parked or not, with its retired count intact
    rep = port.report(k, *args_p, sweep=("rvv-1024",), executed=True)
    per = rep["targets"]["rvv-1024"]["executed"]["per_intrinsic"]
    for label, retired in parked["per_site"].items():
        assert label in per, f"join dropped simulated site {label!r}"
        assert per[label]["executed"] == retired


# ---------------------------------------------------------------------------
# golden emitted units: codegen drift is a reviewed diff, not a silent one
# ---------------------------------------------------------------------------

GOLDEN = ("xnn_f32_vadd_ukernel", "qs8_vmlal_dot_ukernel",
          "qs8_vmul_requant_ukernel")


@pytest.mark.parametrize("name", GOLDEN)
def test_golden_emitted_c(name):
    path = os.path.join(GOLDEN_DIR, f"{name}__rvv_256.c")
    with open(path) as f:
        want = f.read()
    got = rvv.emit(_kernel(name), "rvv-256").render_c()
    assert got == want, \
        f"{name}: emitted C drifted from {os.path.relpath(path, ROOT)} " \
        f"— regenerate via rvv.emit(k, 'rvv-256').render_c() and review"


# ---------------------------------------------------------------------------
# retired counts pinned: every kernel x every width, exactly
# ---------------------------------------------------------------------------

# the retired-count sweep: the rvv-256 column joins the family here
SIM_SWEEP = ("rvv-64", "rvv-128", "rvv-256", "rvv-512", "rvv-1024")
# enough strips that per-loop constants amortize and the widths separate
SIM_N, SIM_TAIL_N = 1024, 1027
SIM_CASES = {c.kernel: c for c in harness.cases(n=SIM_N, tail_n=SIM_TAIL_N)}
# fixed-shape counter-example: fold's cross-lane vget_high/low structure
# stays at NEON granularity, so its retired count must NOT scale with VLEN
UNSCALABLE = ("fold_halves_f32",)


@lru_cache(maxsize=None)
def _golden(name):
    import json
    with open(os.path.join(ROOT, "tests", "golden", name)) as f:
        return json.load(f)


@lru_cache(maxsize=None)
def _sim_counts(name):
    """The emitted RVV's retired counts on the simulator at every width
    of ``SIM_SWEEP``, each run checked against the NumPy reference."""
    case = SIM_CASES[name]
    args = case.make_args(np.random.default_rng(0))
    want = case.reference(*args)
    rows = {}
    for target in SIM_SWEEP:
        out, counts = rvv.execute(rvv.emit(_kernel(name), target), *args)
        _assert_matches(out, want, case, f"{name} on {target} at n={SIM_N}")
        rows[target] = {
            "executed": counts["executed"], "vector": counts["vector"],
            "vsetvli": counts["vsetvli"] + counts["implicit_vsetvli"],
            "vuops": counts["vuops"]}
    return rows


@pytest.mark.parametrize("kernel", sorted(SIM_CASES))
def test_retired_counts_match_golden(kernel):
    """Retired counts are deterministic: any change, up or down, is a
    reviewed change of ``tests/golden/rvv_sim_counts.json``.  Scalable
    kernels retire >= 4x fewer instructions on rvv-1024 than on rvv-128,
    the fixed-shape one does not budge, and no wider register ever
    retires more."""
    rows = _sim_counts(kernel)
    assert rows == _golden("rvv_sim_counts.json")[kernel], \
        f"{kernel} drifted from the golden; fresh rows: {rows}"
    r = rows["rvv-128"]["executed"] / max(1, rows["rvv-1024"]["executed"])
    if kernel in UNSCALABLE:
        assert r <= 1.5, \
            f"{kernel}: fixed-shape kernel's retired count moved with " \
            f"VLEN ({r:.2f}x)"
    else:
        assert r >= 4.0, \
            f"{kernel}: rvv-1024 retired only {r:.2f}x fewer " \
            f"instructions than rvv-128 (want >= 4x)"
    seq = [rows[t]["executed"] for t in SIM_SWEEP]
    assert all(a >= b for a, b in zip(seq, seq[1:])), \
        f"{kernel}: retired counts not monotone across {SIM_SWEEP}: {seq}"


@pytest.mark.parametrize("kernel", sorted(CASES))
def test_rvv_counts_pinned_to_committed_baselines(kernel):
    """What models RVV re-tiles at the RVV width, never at a chip's
    tile: on rvv-128 and rvv-1024, revec's factor and its retiled and
    masked strip counts equal ``tests/golden/port_counts.json``'s, and
    the emitted RVV's retired counts on the simulator equal
    ``tests/golden/rvv_sim_counts.json``'s."""
    port_rows = _golden("port_counts.json")[kernel]
    sim_rows = _golden("rvv_sim_counts.json")[kernel]
    k = _kernel(kernel)
    for target in ("rvv-128", "rvv-1024"):
        res = port.retile(k.fn, target)
        assert (res.factor, res.retiled, res.masked) == (
            port_rows[target]["retile_factor"],
            port_rows[target]["retiled_strips"],
            port_rows[target]["masked_tails"]), target
        assert res.tiled == 0
        assert _sim_counts(kernel)[target] == sim_rows[target], target


def _find_rvv_cc():
    """A compiler on PATH that accepts ``-march=rv64gcv``, or None."""
    import shutil
    import subprocess
    import tempfile
    probes = [["clang", "--target=riscv64", "-march=rv64gcv"],
              ["riscv64-linux-gnu-gcc", "-march=rv64gcv"],
              ["riscv64-unknown-elf-gcc", "-march=rv64gcv"]]
    for cc in probes:
        if shutil.which(cc[0]) is None:
            continue
        with tempfile.NamedTemporaryFile("w", suffix=".c") as f:
            f.write("#include <riscv_vector.h>\nint main(void)"
                    "{return 0;}\n")
            f.flush()
            r = subprocess.run(cc + ["-fsyntax-only", f.name],
                               capture_output=True)
        if r.returncode == 0:
            return cc
    return None


def test_emitted_units_pass_rv64gcv_syntax_check(tmp_path):
    """Every emitted unit compiles under ``-fsyntax-only
    -march=rv64gcv`` where such a compiler is on PATH."""
    import subprocess
    cc = _find_rvv_cc()
    if cc is None:
        pytest.skip("no RVV-capable compiler on PATH (probed clang "
                    "--target=riscv64, riscv64-linux-gnu-gcc, "
                    "riscv64-unknown-elf-gcc)")
    for name in sorted(CASES):
        for target in SIM_SWEEP:
            path = tmp_path / f"{name}__{target}.c"
            path.write_text(rvv.emit(_kernel(name), target).render_c())
            subprocess.run(cc + ["-fsyntax-only", str(path)], check=True)
