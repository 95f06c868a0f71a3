"""Dynamic vector-instruction counting — the Spike-simulator analogue.

The paper measures on Spike, a *functional* RISC-V simulator, and reports
**dynamic instruction count** as the performance metric because no
cycle-accurate hardware was available.  This container is CPU-only, so we
adopt the same methodology tier for the kernel-level comparison:

  * every registry lowering declares ``cost(*args) -> int`` — the number
    of dynamic vector instructions it retires for those operand shapes
    (generic/scalar tiers count element ops; vector tiers count
    ceil(elems/vreg) whole-register ops; pallas kernels count their
    grid x per-block op structure);
  * :func:`count` runs a function under a policy and accumulates the
    per-op counts through dispatch — giving the baseline-vs-customized
    instruction ratio, directly comparable to the paper's Figure 2;
  * :func:`jaxpr_vector_instrs` independently estimates instruction count
    from a traced jaxpr (each primitive = ceil(out_elems / vreg) vector
    instructions, transcendentals scalarized when the backend has no
    vector libm — the reason the paper's vtanh/vsigmoid baselines are
    slow), used to cross-check the declared models.

``repro.kernels.figure2`` builds the paper's Figure-2 columns from these
counts.  They are counts, not times: device time and roofline shares come
from the chip benchmark (``bench/``).
"""
from __future__ import annotations

import contextlib
import logging
import math
import threading
from collections import defaultdict
from typing import Dict, Optional

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np

from .targets import current_target, use_target

log = logging.getLogger(__name__)

_tls = threading.local()


def _counts() -> Optional[Dict]:
    return getattr(_tls, "counts", None)


_cost_warned = set()

# ---------------------------------------------------------------------------
# Profile-guided calibration (repro.port.autotune installs this).
#
# The declared cost models are *estimates*: they charge LMUL micro-ops
# per grouped issue while the simulator retires one instruction per
# mnemonic, and per-op constants drift from what the emitted RVV stream
# actually does (vbsl estimates 3 bitwise ops but retires a
# 2-instruction mask+merge).  A calibration maps measured retired
# counts back onto the abstract model as per-op multiplicative
# correction factors; the registry consults it for every non-generic
# candidate so selection ranks by *measured*, not declared, cost.
# ---------------------------------------------------------------------------

_calibration_lock = threading.Lock()
_calibration: Optional[Dict] = None


def set_calibration(factors: Optional[Dict[str, float]],
                    default: float = 1.0) -> None:
    """Install per-op correction factors (``{isa_op: retired/estimated}``)
    applied by the registry to every non-generic candidate cost.
    ``None`` uninstalls.  Callers that memoize selections (the registry
    does) must invalidate after changing this — use
    ``registry.REGISTRY.set_calibration`` which does both."""
    global _calibration
    with _calibration_lock:
        if factors is None:
            _calibration = None
        else:
            _calibration = {"factors": {str(k): float(v)
                                        for k, v in factors.items()},
                            "default": float(default)}


def get_calibration() -> Optional[Dict]:
    """The installed calibration (``{"factors": {...}, "default": f}``)
    or None."""
    with _calibration_lock:
        return None if _calibration is None else {
            "factors": dict(_calibration["factors"]),
            "default": _calibration["default"]}


def calibrated_cost(op: str, cost: Optional[int]) -> Optional[int]:
    """Apply the installed per-op correction factor to an abstract cost
    (identity when no calibration is installed or cost is unknown).
    Never rounds a positive cost below 1 — a measured op is never free."""
    if cost is None:
        return None
    with _calibration_lock:
        cal = _calibration
    if cal is None:
        return cost
    f = cal["factors"].get(op, cal["default"])
    return max(1, int(round(cost * f))) if cost > 0 else 0


def warn_cost_model(lowering, exc, consequence: str,
                    what: str = "cost model") -> None:
    """Log a broken cost model (or ``supports`` predicate) once per
    (op, tier) — it is a real defect in the selection data, not
    something to silently mask."""
    key = (lowering.op, lowering.tier, what)
    if key not in _cost_warned:
        _cost_warned.add(key)
        log.warning("%s for %s/%s raised %r; %s (fix the model — "
                    "selection quality depends on it)",
                    what, lowering.op, lowering.tier, exc, consequence)


def record(lowering, *args, cost=None, **kw) -> None:
    """Called by registry.dispatch for every op issue.

    ``cost`` is the count already evaluated (and memoized) at selection
    time; when absent the lowering's model is evaluated here.
    """
    c = _counts()
    if c is None:
        return
    n = 0
    if cost is not None:
        n = int(cost)
    elif lowering.cost is not None:
        try:
            n = int(lowering.cost(*args, **kw))
        except Exception as e:
            warn_cost_model(lowering, e, "counting 0")
    c["per_op"][(lowering.op, lowering.tier)] += n
    c["total"] += n


@contextlib.contextmanager
def count():
    """Collect dynamic instruction counts for dispatches in this scope."""
    prev = _counts()
    _tls.counts = {"per_op": defaultdict(int), "total": 0}
    try:
        yield _tls.counts
    finally:
        _tls.counts = prev


# ---------------------------------------------------------------------------
# Cost targets come from repro.core.targets (tpu-v5e/tpu-v6 + the VLA
# rvv-64..1024 family).  ``cost_target`` is the historical name for
# scoping the active target during cost evaluation.
# ---------------------------------------------------------------------------

cost_target = use_target


def vreg_for(dtype) -> int:
    """Elements per vector register for ``dtype`` on the active target."""
    return current_target().vreg_elems(dtype)


def vinstrs_for(n_elems: int, dtype) -> int:
    """Dynamic vector micro-ops to touch ``n_elems`` of ``dtype`` on the
    active target — ceil(n / vreg_elems), times ``lmul`` on VLA targets
    (an LMUL-grouped instruction retires lmul register passes; see
    targets.Target.vinstrs)."""
    return current_target().vinstrs(n_elems, dtype)


# scalar libm call costs (instructions per element) when the baseline
# toolchain scalarizes — grounded in typical libm implementations
PRIM_SCALAR_COST = {"tanh": 30, "exp": 25, "logistic": 28, "log": 25,
                    "log1p": 28, "expm1": 28, "erf": 30, "sin": 28,
                    "cos": 28, "pow": 40, "sqrt": 10, "rsqrt": 8,
                    "atan2": 40, "cbrt": 30}
# vector-libm polynomial expansions (ops per vreg) when NOT scalarized
VEC_EXPANSION = {"tanh": 22, "exp": 14, "logistic": 24, "log": 20,
                 "log1p": 22, "expm1": 16, "erf": 24, "sin": 20, "cos": 20,
                 "pow": 34, "sqrt": 1, "rsqrt": 1, "atan2": 36, "cbrt": 24}


def _elems(x) -> int:
    return int(np.prod(jnp.shape(x))) if jnp.ndim(x) else 1


def _arrays(args):
    return [a for a in args if hasattr(a, "shape") and hasattr(a, "dtype")]


def scalar_cost(ops_per_elem: int = 1):
    """Generic-tier cost: the scalar loop retires one instr per element op
    (what you get when auto-vectorization fails, e.g. libm calls).

    Scalar (non-array) operands — e.g. ``vdup`` of a Python float — count
    as a single element rather than raising.
    """

    def cost(*args, **kw):
        elems = [_elems(a) for a in _arrays(args)]
        return ops_per_elem * (max(elems) if elems else 1)

    return cost


def vector_cost(ops_per_vec: int = 1):
    """Vector-tier cost: whole-register ops, ceil(elems / vreg_elems).

    With no array operand (a pure-scalar issue like ``vdup`` of a Python
    float) the op still retires one whole-register instruction.
    """

    def cost(*args, **kw):
        arrs = _arrays(args)
        if not arrs:
            return ops_per_vec
        n = max(_elems(a) for a in arrs)
        return ops_per_vec * vinstrs_for(n, arrs[0].dtype)

    return cost


def traced_cost(fn, *, union_overhead: bool = True,
                transcendental: bool = False):
    """Cost model that *analyzes the lowering's generated code* (its
    jaxpr) against the active target — the paper's §4 methodology as a
    first-class cost model for the jnp-level tiers.

    ``union_overhead``: the original-SIMDe generic-union memory
    round-trip per op (paper §3.2 / Listing 4) — charged only on VLA
    targets, where the SIMDe flow actually materializes the union; a
    fusing compiler (XLA on TPU) optimizes the round-trip away, and the
    TPU column of the benchmark uses the same un-overheaded counts.
    ``transcendental``: on targets without a vector libm (the baseline
    RVV toolchain) the prim scalarizes — why the paper's vtanh/vsigmoid
    baselines are slowest.

    The jaxpr trace is cheap (abstract, no compile) and the registry
    memoizes selections per (op, shapes, policy, target), so jit-traced
    dispatch stays zero-overhead.
    """

    def cost(*args, **kw):
        tgt = current_target()
        scalarize = transcendental and not tgt.has_vector_libm
        ovh = union_overhead and tgt.vla
        return jaxpr_vector_instrs(fn, *args, scalarize=scalarize,
                                   union_overhead=ovh, **kw)

    return cost


# ---------------------------------------------------------------------------
# Jaxpr-based independent estimate (cross-check for the declared models).
# ---------------------------------------------------------------------------

# Primitives with no vector libm on the baseline path: the compiler falls
# back to a scalarized loop (this is precisely why the paper's baseline
# vtanh/vsigmoid/vsqrt are slow on the generic path).
SCALARIZED_PRIMS = set(PRIM_SCALAR_COST)
_FREE_PRIMS = {"reshape", "broadcast_in_dim", "squeeze", "convert_element_type",
               "copy", "stop_gradient", "slice", "transpose", "bitcast_convert_type"}
_CTRL_PRIMS = ("jit", "scan", "while", "cond", "custom_jvp_call",
               "custom_vjp_call", "remat", "checkpoint")


def jaxpr_vector_instrs(fn, *args, scalarize: bool = False,
                        union_overhead: bool = False, **kw) -> int:
    """Estimate dynamic vector instrs of ``fn(*args)`` from its jaxpr.

    ``scalarize``: transcendentals cost their scalar-libm instruction
    counts (baseline has no vector libm).  ``union_overhead``: every
    vector op pays a 2x factor for the SIMDe generic union round-trip
    through memory (paper §3.2 Listing 4 discussion).  Non-array
    positional args are closed over rather than traced.
    """
    is_arr = [hasattr(a, "shape") and hasattr(a, "dtype") for a in args]
    arr_args = [a for a, ok in zip(args, is_arr) if ok]

    def wrapper(*traced):
        it = iter(traced)
        full = [next(it) if ok else a for a, ok in zip(args, is_arr)]
        return fn(*full, **kw)

    closed = jax.make_jaxpr(wrapper)(*arr_args)
    return _walk(closed.jaxpr, scalarize, union_overhead)


def _walk(jaxpr, scalarize: bool, union_overhead: bool = False) -> int:
    tgt = current_target()
    ovh = 2 if union_overhead else 1
    total = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        for sub in _subjaxprs(eqn):
            total += _trip_count(eqn) * _walk(sub, scalarize, union_overhead)
        if name in _FREE_PRIMS or name in _CTRL_PRIMS:
            continue
        out = eqn.outvars[0].aval
        n = int(np.prod(out.shape)) if out.shape else 1
        dt = getattr(out, "dtype", jnp.float32)
        if jnp.dtype(dt) == jnp.bool_ and eqn.invars:
            # mask-producing op (vmseq & co): the compare executes at the
            # *data* register width; a bool-width vreg would overstate
            # how many lanes one instruction covers
            in0 = getattr(eqn.invars[0], "aval", None)
            dt = getattr(in0, "dtype", dt)
        # LMUL-aware register-pass count (== ceil(elems/vreg) at lmul=1)
        vi = lambda m: tgt.vinstrs(m, dt)  # noqa: E731
        if name == "dot_general":
            a = eqn.invars[0].aval
            dims = eqn.params["dimension_numbers"]
            k = int(np.prod([a.shape[i] for i in dims[0][0]]))
            if tgt.has_mxu:    # systolic macro-ops
                total += math.ceil(n / (tgt.mxu * tgt.mxu)) * \
                    math.ceil(k / tgt.mxu)
            else:              # vfma ladder (+ union loads on baseline)
                total += ovh * vi(n * k)
        elif name == "conv_general_dilated":
            # HWIO rhs: (kh, kw, ci_per_group, co) — contracted size per
            # output element is kh*kw*ci_per_group regardless of groups
            rhs = eqn.invars[1].aval
            k_total = int(np.prod(rhs.shape[:-1]))
            groups = eqn.params.get("feature_group_count", 1)
            if tgt.has_mxu and groups == 1:     # depthwise can't use MXU
                total += math.ceil(n / (tgt.mxu * tgt.mxu)) * \
                    math.ceil(k_total / tgt.mxu)
            else:
                total += ovh * vi(n * k_total)
        elif "reduce_window" in name:
            wd = eqn.params.get("window_dimensions", ())
            win = int(np.prod(wd)) if wd else 2
            total += ovh * win * vi(n)
        elif name in ("gather", "scatter", "scatter-add", "scatter_add"):
            # no per-lane vector gather; TPU moves (sublane,128) rows
            gran = 8 if tgt.has_mxu else 1
            total += max(1, n // gran)
        elif name in ("sort", "top_k"):
            total += ovh * vi(n * max(1, int(np.log2(max(2, n)))))
        elif name in SCALARIZED_PRIMS:
            if scalarize:
                total += PRIM_SCALAR_COST[name] * n
            else:
                # vector libm exists (e.g. XLA:TPU): polynomial expansion,
                # roughly the same op count per *vector* as our kernels
                total += ovh * VEC_EXPANSION.get(name, 1) * vi(n)
        elif name in ("reduce_sum", "reduce_max", "reduce_min", "argmax",
                      "argmin"):
            inx = eqn.invars[0].aval
            nin = int(np.prod(inx.shape)) if inx.shape else 1
            total += ovh * vi(nin)
        else:
            total += ovh * vi(n)
    return total


def _subjaxprs(eqn):
    for v in eqn.params.values():
        if isinstance(v, jax.extend.core.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jax.extend.core.Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for u in v:
                if isinstance(u, jax.extend.core.ClosedJaxpr):
                    yield u.jaxpr
                elif isinstance(u, jax.extend.core.Jaxpr):
                    yield u


def _trip_count(eqn) -> int:
    if eqn.primitive.name == "scan":
        return int(eqn.params.get("length", 1))
    return 1
