"""Differential conformance suite for the port pipeline.

VecIntrinBench-style methodology: migrated width-changing and cross-lane
intrinsics are where NEON->RVV translators silently diverge, so every
corpus kernel is checked for

    interpreter == compiled == compiled+revec == exact NumPy reference

across the RVV width family, over n values that hit every tail shape:
0, 1, strip-1, strip, strip+1, and a seeded pseudo-random length (the
length set is derived per kernel from its *actual* strip step, read off
the IR).  Integer kernels must match bitwise; float kernels within a
small ULP budget (XLA fuses mul+add chains across intrinsic boundaries
in the whole-kernel jaxpr, so bitwise is not the right bar — but a few
ULP is).

Runtime budget: the full matrix stays under the CI step's 120 s cap by
running the cheap interpreter differential over every (kernel, target,
n) cell and the XLA-compiled executors over the tail-critical n subset.
The hypothesis property tests (lane-group widening equivalence) run the
re-tiled IR through the *interpreter*, so random lengths cost no
recompiles; the profile is capped and seeded for reproducibility.
"""
import os
import sys
import zlib

import numpy as np
import pytest

CORPUS = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                      "examples", "neon_corpus"))
sys.path.insert(0, CORPUS)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402

from hypothesis_compat import HAS_HYPOTHESIS, given, settings, st  # noqa: E402,F401

from repro import port  # noqa: E402
from repro.port import revec  # noqa: E402
from repro.port.interp import Machine  # noqa: E402

CONFORMANCE_TARGETS = ("rvv-64", "rvv-128", "rvv-512", "rvv-1024")

_KERNELS = [c.kernel for c in harness.cases()]
# the new width-changing / struct-load surface this suite guards
WIDENING_KERNELS = ("qs8_vaddl_requant_ukernel", "qs8_vmul_requant_ukernel",
                    "s8_shl1_widen_narrow_ukernel",
                    "qs8_vmlal_dot_ukernel")
STRUCT_KERNELS = ("cmul_f32_ukernel",)


def _case_for(kernel: str, n: int) -> harness.Case:
    return {c.kernel: c for c in harness.cases(n=n, tail_n=n)}[kernel]


def _args_for(case: harness.Case, seed: int):
    args = case.make_args(np.random.default_rng(seed))
    # n == 0 builds zero-length buffers; pad to one element so traced
    # (zero-trip) loop bodies stay shape-valid.  Kernels touch exactly
    # the first n elements, references slice [:n] — the pad is inert.
    return tuple(np.zeros(1, a.dtype)
                 if isinstance(a, np.ndarray) and a.size == 0 else a
                 for a in args)


def _kernel_obj(kernel: str):
    case = _case_for(kernel, 8)
    return port.compile_file(os.path.join(CORPUS, case.file),
                             name=case.kernel)


def _strip_step(k) -> int:
    strips = revec.strip_loops(k.fn)
    return strips[0].step if strips else 8


def _lengths(kernel: str, target: str, step: int):
    """0, 1, strip-1, strip, strip+1, and a seeded pseudo-random tail
    length — deterministic per (kernel, target)."""
    r = zlib.crc32(f"{kernel}:{target}".encode())
    rand_n = step + 2 + r % (4 * step)
    return sorted({0, 1, step - 1, step, step + 1, rand_n})


@pytest.fixture(scope="module")
def kernels():
    return {name: _kernel_obj(name) for name in _KERNELS}


# ---------------------------------------------------------------------------
# interpreter differential: full kernel x target x length matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("target", CONFORMANCE_TARGETS)
@pytest.mark.parametrize("kernel", _KERNELS)
def test_interp_conformance(kernel, target, kernels):
    k = kernels[kernel]
    step = _strip_step(k)
    lengths = _lengths(kernel, target, step)
    if kernel not in NEW_SURFACE:
        # legacy kernels: zero/one/strip+1/random is enough here — the
        # whole-strip boundaries are already pinned by test_port_compile
        lengths = sorted({0, 1, step + 1, lengths[-1]})
    for i, n in enumerate(lengths):
        case = _case_for(kernel, n)
        args = _args_for(case, seed=1000 + i)
        got = k(*args, target=target)
        harness.assert_conforms(got, case.reference(*args), case,
                         f"{kernel}/{target}/n={n}/interp")


# ---------------------------------------------------------------------------
# compiled + re-vectorized executors: tail-critical lengths
# ---------------------------------------------------------------------------

NEW_SURFACE = ("qs8_vaddl_requant_ukernel", "qs8_vmul_requant_ukernel",
               "s8_shl1_widen_narrow_ukernel", "cmul_f32_ukernel",
               "qs8_gemm_mx8_ukernel", "qs8_vmlal_dot_ukernel",
               "xnn_f32_vadd_x2_ukernel", "f32_rowscale_ukernel",
               "f32_butterfly_ukernel")

# the per-site offset re-tiling surface: unrolled strips (two sites per
# walk), nested inner strips (outer loop stays a recorded fallback),
# and the rounded masked-tail mode (no whole-lane count per element,
# but one per whole narrow strip)
OFFSET_KERNELS = ("xnn_f32_vadd_x2_ukernel", "f32_rowscale_ukernel",
                  "f32_butterfly_ukernel", "qs8_gemm_mx8_ukernel")
NESTED_KERNELS = ("f32_rowscale_ukernel", "qs8_gemm_mx8_ukernel")


# XLA recompiles per buffer shape, so the compiled matrix is the
# suite's budget driver: the new widening/struct surface runs the full
# rvv-64..1024 family; legacy kernels run the family endpoints here
# (their compiled middle-width behavior is already swept by
# tests/test_port_compile.py's corpus and focus-kernel matrices).
_COMPILED_CELLS = [
    (kernel, target)
    for kernel in _KERNELS
    for target in (CONFORMANCE_TARGETS if kernel in NEW_SURFACE
                   else ("rvv-64", "rvv-1024"))
]


@pytest.mark.parametrize(
    "kernel,target", _COMPILED_CELLS,
    ids=[f"{k}-{t}" for k, t in _COMPILED_CELLS])
def test_compiled_conformance(kernel, target, kernels):
    k = kernels[kernel]
    step = _strip_step(k)
    # length subset: zero-trip, sub-strip+tail, and the seeded random
    # length; the new surface adds the strip+1 boundary
    lengths = ((0, step + 1, _lengths(kernel, target, step)[-1])
               if kernel in NEW_SURFACE
               else (0, _lengths(kernel, target, step)[-1]))
    for i, n in enumerate(sorted(set(lengths))):
        case = _case_for(kernel, n)
        args = _args_for(case, seed=2000 + i)
        want = case.reference(*args)
        for revec_mode in (False, True):
            got = k.compile(target=target, revec=revec_mode)(*args)
            harness.assert_conforms(
                got, want, case,
                f"{kernel}/{target}/n={n}/compiled+revec={revec_mode}")


# ---------------------------------------------------------------------------
# lane-group widening properties (the new re-tiling rule)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kernel", WIDENING_KERNELS + STRUCT_KERNELS)
def test_widened_strip_retiles_without_narrow_fallback(kernel, kernels):
    """The new surface must actually take the lane-group path on a wide
    target: re-tiled, with the remainder subsumed by a masked strip."""
    res = kernels[kernel].retile("rvv-1024")
    assert res.retiled == 1, res.notes
    assert res.masked == 1, res.notes
    want = 16 if kernel in WIDENING_KERNELS else 8
    assert res.factor == want, res.notes


@pytest.mark.parametrize("kernel", WIDENING_KERNELS + STRUCT_KERNELS)
def test_widened_strip_matches_narrow_port_all_tails(kernel, kernels):
    """Widened execution == narrow port == reference for every tail
    shape (interpreting the re-tiled IR: no XLA compiles, so the sweep
    is dense)."""
    k = kernels[kernel]
    wide_fn = k.retile("rvv-1024").fn
    step = _strip_step(k)
    for n in sorted({0, 1, step - 1, step, step + 1, 2 * step - 1,
                     2 * step + 3, 3 * step + 1}):
        case = _case_for(kernel, n)
        args = _args_for(case, seed=n)
        narrow = k(*args, target="rvv-128")
        wide = Machine(wide_fn, policy="pallas", target="rvv-1024").run(
            *args)
        harness.assert_conforms(wide, case.reference(*args), case,
                         f"{kernel}/n={n}/widened")
        harness.assert_conforms(wide, tuple(np.asarray(x) for x in narrow)
                         if isinstance(narrow, tuple)
                         else np.asarray(narrow), case,
                         f"{kernel}/n={n}/widened-vs-narrow")


@pytest.mark.parametrize("kernel", OFFSET_KERNELS)
def test_offset_site_retile_structure(kernel, kernels):
    """The per-site offset surface re-tiles on rvv-1024 with a masked
    tail; nested kernels carry their scalar outer loop as a *recorded*
    structured veto (site, reason, file), never a silent fallback."""
    res = kernels[kernel].retile("rvv-1024")
    assert res.retiled == 1, res.notes
    assert res.masked == 1, res.notes
    if kernel in NESTED_KERNELS:
        assert res.strips == 2
        assert res.narrow_fallbacks == 1
        assert res.vetoes, "outer-loop fallback must be recorded"
        for v in res.vetoes:
            assert v["reason"]
            assert v["file"].endswith(".c")
    else:
        assert res.narrow_fallbacks == 0
        assert res.vetoes == []


# per-kernel tail-critical lengths: each set crosses the narrow-strip
# boundary, the wide-strip boundary (step * factor on rvv-1024), and
# both +-1 neighbours; rowscale/gemm lengths drive the *inner* strip
_OFFSET_LENGTHS = {
    "xnn_f32_vadd_x2_ukernel": (0, 1, 7, 8, 9, 63, 64, 65, 67),
    "f32_rowscale_ukernel": (0, 1, 3, 4, 5, 31, 32, 33, 37),
    "f32_butterfly_ukernel": (0, 1, 7, 8, 9, 63, 64, 65, 67),
    "qs8_gemm_mx8_ukernel": (0, 1, 2, 15, 16, 17, 33),
}


@pytest.mark.parametrize("kernel", OFFSET_KERNELS)
def test_offset_site_matches_narrow_port_all_tails(kernel, kernels):
    """Widened execution == narrow port == reference for every tail
    shape of the offset-site surface (interpreting the re-tiled IR:
    no XLA compiles, so the sweep is dense)."""
    k = kernels[kernel]
    wide_fn = k.retile("rvv-1024").fn
    for n in _OFFSET_LENGTHS[kernel]:
        case = _case_for(kernel, n)
        args = _args_for(case, seed=n)
        narrow = k(*args, target="rvv-128")
        wide = Machine(wide_fn, policy="pallas", target="rvv-1024").run(
            *args)
        harness.assert_conforms(wide, case.reference(*args), case,
                         f"{kernel}/n={n}/offset-widened")
        harness.assert_conforms(wide, tuple(np.asarray(x) for x in narrow)
                         if isinstance(narrow, tuple)
                         else np.asarray(narrow), case,
                         f"{kernel}/n={n}/offset-widened-vs-narrow")


def test_rounded_tail_mode_matches_narrow_floor(kernels):
    """Satellite regression for the loosened tail-legality rule: the
    butterfly kernel has no scalar tail and no whole-lane count per
    element (scale % div != 0), but (scale * step) % div == 0 proves a
    whole-lane count per narrow strip — the rounded mode must floor the
    active count exactly like the narrow port does, bitwise."""
    k = kernels["f32_butterfly_ukernel"]
    res = k.retile("rvv-1024")
    assert res.retiled == 1 and res.masked == 1, res.notes
    wide_fn = res.fn
    for n in (0, 1, 7, 8, 9, 15, 16, 17, 23, 24, 25, 63, 64, 65):
        case = _case_for("f32_butterfly_ukernel", n)
        args = _args_for(case, seed=n)
        narrow = np.asarray(k(*args, target="rvv-128"))
        wide = np.asarray(Machine(wide_fn, policy="pallas",
                                  target="rvv-1024").run(*args))
        np.testing.assert_array_equal(
            wide, narrow,
            err_msg=f"rounded tail diverged from narrow floor at n={n}")


@pytest.mark.parametrize("kernel", WIDENING_KERNELS + STRUCT_KERNELS)
def test_widening_revec_instrs_shrink_2x_128_to_1024(kernel, kernels):
    """Regression guard on the widening path specifically: the re-tiled
    dynamic instruction estimate must keep shrinking with the register,
    >= 2x from rvv-128 to rvv-1024."""
    k = kernels[kernel]
    case = _case_for(kernel, 67)
    args = _args_for(case, seed=7)
    instrs = {}
    for target in ("rvv-128", "rvv-1024"):
        fn = k.retile(target).fn
        est = Machine(fn, policy="pallas", target=target,
                      abstract=True).run(*args)
        instrs[target] = est["total_instrs"]
    assert instrs["rvv-1024"] * 2 <= instrs["rvv-128"], instrs


if HAS_HYPOTHESIS:
    @settings(max_examples=15, deadline=None, derandomize=True)
    @given(n=st.integers(min_value=0, max_value=301),
           seed=st.integers(min_value=0, max_value=2 ** 20))
    def test_property_widening_tail_equivalence(n, seed):
        """Hypothesis sweep: random lengths and data, the widened
        vmull/vqmovn strip stays bitwise-equal to the narrow port."""
        kernel = "qs8_vmul_requant_ukernel"
        k = _kernel_obj(kernel)
        wide_fn = k.retile("rvv-1024").fn
        case = _case_for(kernel, n)
        args = _args_for(case, seed=seed)
        narrow = np.asarray(k(*args, target="rvv-128"))
        wide = np.asarray(Machine(wide_fn, policy="pallas",
                                  target="rvv-1024").run(*args))
        np.testing.assert_array_equal(wide, narrow)
        np.testing.assert_array_equal(wide, case.reference(*args))

    @settings(max_examples=10, deadline=None, derandomize=True)
    @given(n=st.integers(min_value=0, max_value=150),
           seed=st.integers(min_value=0, max_value=2 ** 20))
    def test_property_struct_load_tail_equivalence(n, seed):
        """Random lengths/data: the lane-group vld2/vst2 re-tile (with
        its per-site stride-2 masked tail) matches the narrow port."""
        kernel = "cmul_f32_ukernel"
        k = _kernel_obj(kernel)
        wide_fn = k.retile("rvv-512").fn
        case = _case_for(kernel, n)
        args = _args_for(case, seed=seed)
        narrow = np.asarray(k(*args, target="rvv-128"))
        wide = np.asarray(Machine(wide_fn, policy="pallas",
                                  target="rvv-512").run(*args))
        harness.assert_conforms(wide, case.reference(*args), case,
                         f"{kernel}/n={n}/property")
        harness.assert_conforms(wide, narrow, case,
                         f"{kernel}/n={n}/property-vs-narrow")


# ---------------------------------------------------------------------------
# eager (jit=False) executor: the serving warm-up path
# ---------------------------------------------------------------------------

# the kernels the serving tier's bench exercises: elementwise,
# reduction, widening MACC
EAGER_KERNELS = ("xnn_f32_vadd_ukernel", "xnn_f32_vdot_ukernel",
                 "qs8_vmlal_dot_ukernel")


@pytest.mark.parametrize("kernel", EAGER_KERNELS)
def test_eager_compile_conformance(kernel, kernels):
    """``compile(jit=False)`` is the serving tier's shape-probing
    warm-up and the callable its batch programs ``vmap`` — the eager
    trace must agree with the jitted executor and the reference at
    tail-critical lengths, with and without re-vectorization."""
    k = kernels[kernel]
    step = _strip_step(k)
    for target in ("rvv-128", "rvv-1024"):
        for revec_mode in (False, True):
            eager = k.compile(target=target, revec=revec_mode, jit=False)
            jitted = k.compile(target=target, revec=revec_mode, jit=True)
            assert eager is not jitted, \
                "jit=False and jit=True must be distinct cache entries"
            for i, n in enumerate((0, step + 1)):
                case = _case_for(kernel, n)
                args = _args_for(case, seed=3000 + i)
                want = case.reference(*args)
                label = f"{kernel}/{target}/n={n}/revec={revec_mode}"
                harness.assert_conforms(eager(*args), want, case,
                                 label + "/eager")
                harness.assert_conforms(jitted(*args), want, case,
                                 label + "/jitted")


# ---------------------------------------------------------------------------
# abstract-mode tuple values (the _UNKNOWN_SCALAR satellite fix)
# ---------------------------------------------------------------------------

def test_abstract_mode_handles_tuple_values(kernels):
    """vld2 results in abstract cost-estimation mode are tuples of
    per-register abstract values, not scalar unknowns — the estimate
    must run and charge the struct ops."""
    k = kernels["cmul_f32_ukernel"]
    case = _case_for("cmul_f32_ukernel", 19)
    args = _args_for(case, seed=3)
    est = k.estimate(*args, target="rvv-1024")
    assert est["total_instrs"] > 0
    assert "vld2q_f32" in est["per_intrinsic"]
    assert "vst2q_f32" in est["per_intrinsic"]
    # and through the re-tiled IR, where the struct ops are masked
    rev = k.compile(target="rvv-1024", revec=True).estimate(*args)
    names = set(rev["per_intrinsic"])
    assert any(n.endswith("[masked]") and n.startswith("vld2") for n in
               names), names
    assert rev["total_instrs"] < est["total_instrs"]


def test_abstract_tuple_member_flow_does_not_leak_unknowns(kernels):
    """tuple_get/tuple_set are free SSA plumbing in abstract mode: no
    scalar-unknown sentinels escape into control flow, and the struct
    registers carry per-register shapes."""
    import jax
    k = kernels["cmul_f32_ukernel"]
    m = Machine(k.fn, policy="pallas", target="rvv-128", abstract=True)
    case = _case_for("cmul_f32_ukernel", 9)
    args = _args_for(case, seed=5)
    rows = m.run(*args)
    tup = rows["per_intrinsic"]["vld2q_f32"]
    assert tup["issues"] == 2 * (9 // 4)
    # struct plumbing never reaches the registry
    assert not any(name.startswith("tuple.") for name in
                   rows["per_intrinsic"])
