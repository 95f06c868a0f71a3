"""Differential harness for the NEON corpus.

Each corpus kernel gets (a) an argument builder fixing buffer shapes and
(b) a NumPy reference implementing the *same algorithm* in float32 (not
a looser mathematical ideal), so ported execution must match tightly —
the SIMDe unit-test methodology.  ``run_differential()`` compiles every
``.c`` file, executes it through ``registry.dispatch`` under the given
target/policy, and asserts against the reference.

Run directly:  PYTHONPATH=src python examples/neon_corpus/harness.py
"""
from __future__ import annotations

import dataclasses
import os
from typing import Callable, Optional, Sequence, Tuple

import numpy as np

CORPUS_DIR = os.path.dirname(os.path.abspath(__file__))

F = np.float32


@dataclasses.dataclass(frozen=True)
class Case:
    file: str
    kernel: str
    make_args: Callable[[np.random.Generator], tuple]
    reference: Callable[..., tuple]
    rtol: float = 1e-6
    atol: float = 1e-6


def _rand(rng, n, lo=-2.0, hi=2.0):
    return rng.uniform(lo, hi, n).astype(F)


# -- reference algorithms (float32 mirrors of the kernels) -------------------

def _tanh_rational(t: np.ndarray) -> np.ndarray:
    t = np.clip(t, F(-4.0), F(4.0))
    t2 = t * t
    p = t2 + F(378.0)
    p = p * t2 + F(17325.0)
    p = p * t2 + F(135135.0)
    p = p * t
    q = t2 * F(28.0) + F(3150.0)
    q = q * t2 + F(62370.0)
    q = q * t2 + F(135135.0)
    r = (F(1.0) / q).astype(F)
    r = r * (F(2.0) - q * r)
    r = r * (F(2.0) - q * r)
    return (p * r).astype(F)


def _ref_vadd(n, a, b, y):
    out = y.copy()
    out[:n] = a[:n] + b[:n]
    return out


def _ref_vmul(n, a, b, y):
    out = y.copy()
    out[:n] = a[:n] * b[:n]
    return out


def _ref_vmulcaddc(n, x, scale, bias, y):
    out = y.copy()
    m = (n // 4) * 4
    k = m // 4
    out[:m] = x[:m] * np.tile(scale, k) + np.tile(bias, k)
    return out


def _ref_vclamp(n, x, y, lo, hi):
    out = y.copy()
    out[:n] = np.clip(x[:n], F(lo), F(hi))
    return out


def _ref_vtanh(n, x, y):
    out = y.copy()
    m = (n // 4) * 4
    out[:m] = _tanh_rational(x[:m])
    return out


def _ref_vsigmoid(n, x, y):
    out = y.copy()
    m = (n // 4) * 4
    th = _tanh_rational((x[:m] * F(0.5)).astype(F))
    out[:m] = F(0.5) + th * F(0.5)
    return out


def _ref_vdot(n, a, b, sum_buf):
    m = (n // 4) * 4
    acc = np.zeros(4, F)
    for i in range(0, m, 4):
        acc = acc + a[i:i + 4] * b[i:i + 4]
    s = F(acc.sum())
    for i in range(m, n):
        s = F(s + a[i] * b[i])
    out = sum_buf.copy()
    out[0] = s
    return out


def _ref_vrsqrt(n, x, y):
    out = y.copy()
    m = (n // 4) * 4
    v = x[:m]
    r = (F(1.0) / np.sqrt(v)).astype(F)
    r = r * ((F(3.0) - (v * r) * r) * F(0.5))
    r = r * ((F(3.0) - (v * r) * r) * F(0.5))
    out[:m] = r
    return out


def _ref_vfold(n, x, y):
    out = y.copy()
    m = (n // 4) * 4
    q = x[:m].reshape(-1, 4)
    out[:m // 2] = (q[:, 2:] + q[:, :2]).reshape(-1)
    return out


def _ref_vselect(n, x, y):
    out = y.copy()
    m = (n // 4) * 4
    out[:m] = np.where(x[:m] > 0, x[:m], F(0.0))
    return out


def _ref_vrbit(n, x, y):
    out = y.copy()
    m = (n // 16) * 16
    v = x[:m]
    v = ((v >> 1) & 0x55) | ((v & 0x55) << 1)
    v = ((v >> 2) & 0x33) | ((v & 0x33) << 2)
    v = ((v >> 4) & 0x0F) | ((v & 0x0F) << 4)
    out[:m] = v
    return out


def _ref_vqaddsub(n, a, b, ya, ys):
    outa, outs = ya.copy(), ys.copy()
    s = np.clip(a[:n].astype(np.int32) + b[:n].astype(np.int32), -128, 127)
    d = np.clip(a[:n].astype(np.int32) - b[:n].astype(np.int32), -128, 127)
    outa[:n] = (s + 128).astype(np.uint8)
    outs[:n] = (d + 128).astype(np.uint8)
    return outa, outs


def _ref_reduce_max(n, x, out_buf):
    out = out_buf.copy()
    # the kernel seeds its accumulator with x[0] before the strip loop,
    # so the n == 0 result is x[0] (and x[0] participates for any n)
    out[0] = np.max(x[:max(n, 1)])
    return out


def _ref_vcvt(n, x, y):
    out = y.copy()
    m = (n // 4) * 4
    out[:m] = x[:m].astype(np.int32)    # C truncation semantics
    return out


def _ref_vaddl_requant(n, a, b, bias, y):
    out = y.copy()
    s = a[:n].astype(np.int32) + b[:n].astype(np.int32) + bias
    out[:n] = np.clip(s, 0, 255).astype(np.uint8)
    return out


def _ref_vmull_requant(n, a, b, y):
    out = y.copy()
    p = (a[:n].astype(np.int32) * b[:n].astype(np.int32)) >> 5
    out[:n] = np.clip(p, -128, 127).astype(np.int8)
    return out


def _ref_shl1_widen_narrow(n, x, y):
    out = y.copy()
    t = (x[:n].astype(np.int16) << 1) & 0xFF
    out[:n] = t.astype(np.uint8).view(np.int8)
    return out


def _ref_cmul(n, a, b, y):
    """n complex pairs; the strip computes in float32 two-step (vmul,
    then vmls/vmla), the scalar tail in double rounded once at store —
    the reference mirrors both exactly."""
    out = y.copy()
    m = (n // 4) * 4
    ar, ai = a[0:2 * m:2], a[1:2 * m:2]
    br, bi = b[0:2 * m:2], b[1:2 * m:2]
    out[0:2 * m:2] = ar * br - ai * bi
    out[1:2 * m:2] = ar * bi + ai * br
    for i in range(m, n):
        re = float(a[2 * i]) * float(b[2 * i]) - \
            float(a[2 * i + 1]) * float(b[2 * i + 1])
        im = float(a[2 * i]) * float(b[2 * i + 1]) + \
            float(a[2 * i + 1]) * float(b[2 * i])
        out[2 * i] = np.float32(re)
        out[2 * i + 1] = np.float32(im)
    return out


def _ref_vld3_rgbx(n, rgb, r, g, b):
    """Packed RGB split into planes: member i of each pixel triple."""
    ro, go, bo = r.copy(), g.copy(), b.copy()
    ro[:n] = rgb[0:3 * n:3]
    go[:n] = rgb[1:3 * n:3]
    bo[:n] = rgb[2:3 * n:3]
    return ro, go, bo


def _ref_vmlal_dot(n, a, b, sum_buf):
    # integer accumulation is associative — exact in any order as long
    # as the int16 accumulator cannot overflow (the args builder keeps
    # |a*b| <= 4, so |sum| <= 4n stays well inside int16 for corpus n)
    out = sum_buf.copy()
    out[0] = np.int16(np.dot(a[:n].astype(np.int32),
                             b[:n].astype(np.int32)))
    return out


def _ref_rowscale(m, n, x, s, y):
    out = y.copy()
    if m and n:
        out[:m * n] = (x[:m * n].reshape(m, n) * s[:m, None]).reshape(-1)
    return out


def _ref_butterfly(n, x, y):
    # no scalar tail: the kernel floors to whole 8-float strips
    out = y.copy()
    w = n - n % 8
    e, o = x[0:w:2], x[1:w:2]
    out[0:w:2] = e + o
    out[1:w:2] = e - o
    return out


def _ref_qs8_gemm(m, k, a, b, c):
    out = c.copy()
    if m:
        a2 = a[:m * k].astype(np.int32).reshape(m, k)
        b2 = b[:k * 8].astype(np.int32).reshape(k, 8)
        out[:m * 8] = (a2 @ b2).astype(np.int16).reshape(-1)
    return out


# -- conformance check ----------------------------------------------------------

# float ULP budgets: the executors agree bitwise per-op, but XLA's
# whole-kernel fusion re-associates mul/add chains; polynomial kernels
# (rational tanh/sigmoid, Newton rsqrt, dot accumulation) compound that
# over the chain, mirrored by their harness rtol.
_F32_EPS = float(np.finfo(np.float32).eps)


def ulp_budget(case: Case) -> int:
    return max(4, int(2 * case.rtol / _F32_EPS))


def ulp_distance(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    def ordered(x):
        i = x.view(np.int32).astype(np.int64)
        return np.where(i < 0, -(i & 0x7FFFFFFF), i)

    return np.abs(ordered(a) - ordered(b))


def assert_conforms(got, want, case: Case, label: str) -> None:
    """Raise AssertionError unless ``got`` conforms to the reference:
    bitwise for integer outputs, within the case's ULP budget (or its
    absolute tolerance) for float outputs."""
    got = got if isinstance(got, tuple) else (got,)
    want = want if isinstance(want, tuple) else (want,)
    if len(got) != len(want):
        raise AssertionError(f"{label}: {len(got)} outputs, want "
                             f"{len(want)}")
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        if g.shape != w.shape or g.dtype != w.dtype:
            raise AssertionError(f"{label}: shape/dtype {g.shape}/{g.dtype} "
                                 f"vs {w.shape}/{w.dtype}")
        if np.issubdtype(w.dtype, np.integer):
            np.testing.assert_array_equal(
                g, w, err_msg=f"{label}: integer kernel must match "
                              f"bitwise")
        else:
            # ULP budget, with an absolute-tolerance escape: XLA fuses
            # mul+add chains into FMAs, so a catastrophically-cancelling
            # lane (|result| << |operands|) can sit many ULP-of-result
            # from the two-step reference while the absolute error stays
            # at one ULP of the *operands* — that is conforming.
            budget = ulp_budget(case)
            ulp = ulp_distance(g.astype(np.float32), w.astype(np.float32))
            ok = (ulp <= budget) | \
                (np.abs(g.astype(np.float64) - w.astype(np.float64))
                 <= max(case.atol, 1e-6))
            if not np.all(ok):
                raise AssertionError(
                    f"{label}: float divergence of {int(ulp.max())} ULP "
                    f"(budget {budget}) beyond atol {max(case.atol, 1e-6)}")


# -- the corpus ---------------------------------------------------------------

def cases(n: int = 64, tail_n: int = 67, seed: int = 0) -> Sequence[Case]:
    """``n`` drives strip-only kernels (a multiple of 16 covers every
    strip width exactly; any value is legal — references mirror the
    kernels' floor-to-strip semantics, which is what the conformance
    suite sweeps); ``tail_n`` drives the kernels with scalar tails
    (deliberately not a multiple of 4 by default)."""

    def args_abn(rng):     # (n, a, b, y) with tail
        return (tail_n, _rand(rng, tail_n), _rand(rng, tail_n),
                np.zeros(tail_n, F))

    def gemm_args(rng):    # m x 8 tile over k = n (small operands: the
        # int16 accumulator must stay exact — |sum| <= 4 * k)
        m, k = 3, n
        return (m, k,
                rng.integers(-2, 3, max(1, m * k)).astype(np.int8),
                rng.integers(-2, 3, max(1, k * 8)).astype(np.int8),
                np.zeros(m * 8, np.int16))

    def rowscale_args(rng):   # 3 rows of tail_n (inner strip + inner
        # scalar tail per row; the outer row loop stays scalar)
        m = 3
        return (m, tail_n, _rand(rng, max(1, m * tail_n)),
                _rand(rng, m, 0.5, 1.5),
                np.zeros(max(1, m * tail_n), F))

    return [
        Case("vadd.c", "xnn_f32_vadd_ukernel", args_abn, _ref_vadd),
        Case("vadd_x2.c", "xnn_f32_vadd_x2_ukernel", args_abn,
             _ref_vadd),
        Case("rowscale.c", "f32_rowscale_ukernel", rowscale_args,
             _ref_rowscale),
        Case("butterfly.c", "f32_butterfly_ukernel",
             lambda rng: (tail_n, _rand(rng, max(1, tail_n)),
                          np.zeros(max(1, tail_n), F)),
             _ref_butterfly),
        Case("vmul.c", "xnn_f32_vmul_ukernel", args_abn, _ref_vmul),
        Case("vmulcaddc.c", "xnn_f32_vmulcaddc_ukernel_c4",
             lambda rng: (n, _rand(rng, n), _rand(rng, 4, 0.5, 1.5),
                          _rand(rng, 4), np.zeros(n, F)),
             _ref_vmulcaddc),
        Case("vclamp.c", "xnn_f32_vclamp_ukernel",
             lambda rng: (tail_n, _rand(rng, tail_n, -3, 3),
                          np.zeros(tail_n, F), -1.0, 1.5),
             _ref_vclamp),
        Case("vtanh.c", "xnn_f32_vtanh_ukernel",
             lambda rng: (n, _rand(rng, n, -6, 6), np.zeros(n, F)),
             _ref_vtanh, rtol=2e-5, atol=1e-6),
        Case("vsigmoid.c", "xnn_f32_vsigmoid_ukernel",
             lambda rng: (n, _rand(rng, n, -8, 8), np.zeros(n, F)),
             _ref_vsigmoid, rtol=2e-5, atol=1e-6),
        Case("vdot.c", "xnn_f32_vdot_ukernel",
             lambda rng: (tail_n, _rand(rng, tail_n), _rand(rng, tail_n),
                          np.zeros(1, F)),
             _ref_vdot, rtol=1e-5),
        Case("vrsqrt.c", "xnn_f32_vrsqrt_ukernel",
             lambda rng: (n, _rand(rng, n, 0.01, 9.0), np.zeros(n, F)),
             _ref_vrsqrt, rtol=1e-5),
        Case("vfold.c", "fold_halves_f32",
             lambda rng: (n, _rand(rng, n), np.zeros(n // 2, F)),
             _ref_vfold),
        Case("vselect.c", "relu_bsl_f32",
             lambda rng: (n, _rand(rng, n), np.zeros(n, F)),
             _ref_vselect),
        Case("vrbit.c", "bitreverse_u8",
             lambda rng: (n, rng.integers(0, 256, n).astype(np.uint8),
                          np.zeros(n, np.uint8)),
             _ref_vrbit),
        Case("vqaddsub.c", "qs8_vaddsub_biased_ukernel",
             lambda rng: (tail_n,
                          rng.integers(-128, 128, tail_n).astype(np.int8),
                          rng.integers(-128, 128, tail_n).astype(np.int8),
                          np.zeros(tail_n, np.uint8),
                          np.zeros(tail_n, np.uint8)),
             _ref_vqaddsub),
        Case("vreduce_max.c", "reduce_max_f32",
             lambda rng: (tail_n, _rand(rng, tail_n), np.zeros(1, F)),
             _ref_reduce_max),
        Case("vcvt.c", "cvt_f32_s32",
             lambda rng: (n, _rand(rng, n, -100, 100),
                          np.zeros(n, np.int32)),
             _ref_vcvt),
        Case("vaddl_requant.c", "qs8_vaddl_requant_ukernel",
             lambda rng: (tail_n,
                          rng.integers(-128, 128, tail_n).astype(np.int8),
                          rng.integers(-128, 128, tail_n).astype(np.int8),
                          int(rng.integers(-100, 100)),
                          np.zeros(tail_n, np.uint8)),
             _ref_vaddl_requant),
        Case("vmull_requant.c", "qs8_vmul_requant_ukernel",
             lambda rng: (tail_n,
                          rng.integers(-128, 128, tail_n).astype(np.int8),
                          rng.integers(-128, 128, tail_n).astype(np.int8),
                          np.zeros(tail_n, np.int8)),
             _ref_vmull_requant),
        Case("vmovl_shift.c", "s8_shl1_widen_narrow_ukernel",
             lambda rng: (tail_n,
                          rng.integers(-128, 128, tail_n).astype(np.int8),
                          np.zeros(tail_n, np.int8)),
             _ref_shl1_widen_narrow),
        Case("vcmul.c", "cmul_f32_ukernel",
             lambda rng: (tail_n, _rand(rng, 2 * tail_n),
                          _rand(rng, 2 * tail_n),
                          np.zeros(2 * tail_n, F)),
             _ref_cmul),
        Case("vld3_rgbx.c", "u8_rgbx_deinterleave_ukernel",
             lambda rng: (tail_n,
                          rng.integers(0, 256,
                                       3 * tail_n).astype(np.uint8),
                          np.zeros(tail_n, np.uint8),
                          np.zeros(tail_n, np.uint8),
                          np.zeros(tail_n, np.uint8)),
             _ref_vld3_rgbx),
        Case("vmlal_dot.c", "qs8_vmlal_dot_ukernel",
             lambda rng: (tail_n,
                          rng.integers(-2, 3, tail_n).astype(np.int8),
                          rng.integers(-2, 3, tail_n).astype(np.int8),
                          np.zeros(1, np.int16)),
             _ref_vmlal_dot),
        Case("qs8gemm.c", "qs8_gemm_mx8_ukernel", gemm_args,
             _ref_qs8_gemm),
    ]


def run_differential(n: int = 64, seed: int = 0, target=None,
                     policy: Optional[str] = "pallas",
                     verbose: bool = False) -> Tuple[int, int]:
    """Compile + execute + check every corpus kernel.  Returns
    (checked, total-dynamic-instrs-counted)."""
    from repro import port
    from repro.core import trace

    checked, instrs = 0, 0
    for case in cases(n=n, seed=seed):
        k = port.compile_file(os.path.join(CORPUS_DIR, case.file),
                              name=case.kernel)
        rng = np.random.default_rng(seed + checked)
        args = case.make_args(rng)
        with trace.count() as c:
            got = k(*args, policy=policy, target=target)
        want = case.reference(*args)
        np.testing.assert_allclose(np.asarray(got), want,
                                   rtol=case.rtol, atol=case.atol,
                                   err_msg=f"{case.kernel} diverged from "
                                           f"its NumPy reference")
        checked += 1
        instrs += c["total"]
        if verbose:
            print(f"  {case.kernel:32s} OK   ({c['total']:>5d} instrs)")
    return checked, instrs


if __name__ == "__main__":
    for tgt in (None, "rvv-128"):
        label = tgt or "ambient"
        print(f"# differential corpus run (target={label})")
        k, i = run_differential(verbose=True, target=tgt)
        print(f"# {k} kernels OK, {i} dynamic instructions counted\n")
