"""Plain NumPy references of the served corpus kernels, their argument
builders at any ``n``, and the conformance rule.

Each reference computes the kernel's own algorithm in float32 (or its
integer type), in the kernel's order of accumulation, so a served result
must match bitwise for integer outputs and within a few ULP for
elementwise floats.  A float reduction is held to its error against the
sum of its terms' magnitudes instead: a port may accumulate in more lanes
and fuse the multiply-add, and where the terms cancel, a few ULP of the
small result is far less than that reordering moves it.
The C sources beside this file are what the benchmark ports and serves.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Callable, Optional, Tuple

import numpy as np

SOURCE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "neon_corpus")

F = np.float32
_F32_EPS = float(np.finfo(np.float32).eps)
U = _F32_EPS / 2                # float32 unit roundoff
# A float reduction's largest error, in units of U times the sum of its
# terms' magnitudes: served results read at most 1.2 on the CPU and the
# control (bfloat16 inputs and result) reads hundreds (PERF.md, section 2).
REDUCTION_BUDGET_U = 8.0


@dataclasses.dataclass(frozen=True)
class Kernel:
    file: str
    make_args: Callable[[np.random.Generator, int], tuple]
    reference: Callable[..., np.ndarray]
    rtol: float = 1e-6
    atol: float = 1e-6
    # for a float reduction: the sum of its terms' magnitudes, from args
    magnitude: Optional[Callable[..., float]] = None

    @property
    def path(self) -> str:
        return os.path.join(SOURCE_DIR, self.file)

    @property
    def ulp_budget(self) -> int:
        return max(4, int(2 * self.rtol / _F32_EPS))


def _rand(rng, n, lo=-2.0, hi=2.0):
    return rng.uniform(lo, hi, n).astype(F)


def _tanh_rational(t):
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
    # four lane accumulators, each summed in strip order (cumsum adds
    # sequentially), then the horizontal add and the scalar tail
    m = (n // 4) * 4
    acc = np.zeros(4, F)
    if m:
        acc = np.cumsum((a[:m] * b[:m]).reshape(-1, 4), axis=0,
                        dtype=F)[-1]
    s = F(acc.sum())
    for i in range(m, n):
        s = F(s + a[i] * b[i])
    out = sum_buf.copy()
    out[0] = s
    return out


def _ref_vmull_requant(n, a, b, y):
    out = y.copy()
    p = (a[:n].astype(np.int32) * b[:n].astype(np.int32)) >> 5
    out[:n] = np.clip(p, -128, 127).astype(np.int8)
    return out


def _ref_vmlal_dot(n, a, b, sum_buf):
    # int16 accumulation wraps, and wrapping sums are exact in any order
    out = sum_buf.copy()
    out[0] = np.asarray(np.dot(a[:n].astype(np.int64),
                               b[:n].astype(np.int64))).astype(np.int16)
    return out


def _dot_magnitude(n, a, b, sum_buf):
    return float(np.abs(a[:n].astype(np.float64) * b[:n]).sum())


def _abn(rng, n):
    return (n, _rand(rng, n), _rand(rng, n), np.zeros(n, F))


def _i8(rng, n, lo=-128, hi=128):
    return rng.integers(lo, hi, n).astype(np.int8)


KERNELS = {
    "xnn_f32_vadd_ukernel": Kernel("vadd.c", _abn, _ref_vadd),
    "xnn_f32_vmul_ukernel": Kernel("vmul.c", _abn, _ref_vmul),
    "qs8_vmul_requant_ukernel": Kernel(
        "vmull_requant.c",
        lambda rng, n: (n, _i8(rng, n), _i8(rng, n), np.zeros(n, np.int8)),
        _ref_vmull_requant),
    "xnn_f32_vclamp_ukernel": Kernel(
        "vclamp.c",
        lambda rng, n: (n, _rand(rng, n, -3, 3), np.zeros(n, F), -1.0, 1.5),
        _ref_vclamp),
    "qs8_vmlal_dot_ukernel": Kernel(
        "vmlal_dot.c",
        lambda rng, n: (n, _i8(rng, n, -2, 3), _i8(rng, n, -2, 3),
                        np.zeros(1, np.int16)),
        _ref_vmlal_dot),
    "xnn_f32_vtanh_ukernel": Kernel(
        "vtanh.c",
        lambda rng, n: (n, _rand(rng, n, -6, 6), np.zeros(n, F)),
        _ref_vtanh, rtol=2e-5),
    "xnn_f32_vsigmoid_ukernel": Kernel(
        "vsigmoid.c",
        lambda rng, n: (n, _rand(rng, n, -8, 8), np.zeros(n, F)),
        _ref_vsigmoid, rtol=2e-5),
    "xnn_f32_vdot_ukernel": Kernel(
        "vdot.c",
        lambda rng, n: (n, _rand(rng, n), _rand(rng, n), np.zeros(1, F)),
        _ref_vdot, rtol=1e-5, magnitude=_dot_magnitude),
}


def ulp_distance(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    def ordered(x):
        i = x.view(np.int32).astype(np.int64)
        return np.where(i < 0, -(i & 0x7FFFFFFF), i)

    return np.abs(ordered(a) - ordered(b))


def reduction_error_u(got, want, k: Kernel, args) -> Optional[float]:
    """A float reduction's error in units of ``U`` times the sum of its
    terms' magnitudes; None for every other kernel."""
    if k.magnitude is None:
        return None
    diff = float(np.max(np.abs(np.asarray(got, np.float64) -
                               np.asarray(want, np.float64))))
    mag = k.magnitude(*args)
    if mag == 0.0:
        return 0.0 if diff == 0.0 else float("inf")
    return diff / (U * mag)


def conforms(got, want, k: Kernel, args) -> Tuple[bool, Optional[str]]:
    """Bitwise for integer outputs; a float reduction within
    ``REDUCTION_BUDGET_U``; other float outputs within the kernel's ULP
    budget, or within its absolute tolerance (a cancelling lane may sit
    many ULP of the result from the two-step reference while the error
    stays at one ULP of the operands).  Returns (ok, why not)."""
    got = got if isinstance(got, tuple) else (got,)
    want = want if isinstance(want, tuple) else (want,)
    if len(got) != len(want):
        return False, f"{len(got)} outputs, want {len(want)}"
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        if g.shape != w.shape or g.dtype != w.dtype:
            return False, f"shape/dtype {g.shape}/{g.dtype} vs {w.shape}/{w.dtype}"
        if np.issubdtype(w.dtype, np.integer):
            bad = int(np.count_nonzero(g != w))
            if bad:
                return False, f"{bad} integer lanes differ"
        elif k.magnitude is not None:
            e = reduction_error_u(g, w, k, args)
            if not e <= REDUCTION_BUDGET_U:
                return False, (f"reduction error {e:.4g} U x sum|terms| "
                               f"over {REDUCTION_BUDGET_U}")
        else:
            ulp = ulp_distance(g.astype(F), w.astype(F))
            ok = (ulp <= k.ulp_budget) | (
                np.abs(g.astype(np.float64) - w.astype(np.float64))
                <= max(k.atol, 1e-6))
            if not np.all(ok):
                return False, (f"{int(np.count_nonzero(~ok))} lanes beyond "
                               f"{k.ulp_budget} ULP (worst {int(ulp.max())})")
    return True, None
