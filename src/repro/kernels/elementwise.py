"""Customized elementwise TPU lowerings: vrelu, vsqrt, vtanh, vsigmoid.

These four are the paper's clearest wins (Figure 2: vtanh/vsigmoid show
the largest speedups).  The generic tier scalarizes transcendental calls
(no vector libm), while the customized conversions compute them with pure
vector arithmetic — the TPU analogue of XNNPACK's NEON polynomial
microkernels:

  vsqrt    — vrsqrte seed + 2 Newton-Raphson refinements (NEON vrsqrte/
             vrsqrts ladder), fixed up at x=0/inf,
  vtanh    — expm1-free rational form using an exp2 range reduction with
             bit-assembled 2^n scaling (binary-magic flavor, like the
             paper's vrbit conversion),
  vsigmoid — same exp2 reduction + one-Newton reciprocal (vrecpe ladder),
  vrelu    — fused minmax clamp (XNNPACK vrelu is clamp, one VPU op pair).

All operate on 2-D padded tiles; ops.py handles the logical-shape
packing and the tail (vl) slicing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.targets import compile_target
from repro.core.vtypes import round_up
from repro.core import masks

_LN2 = 0.6931471805599453
_LOG2E = 1.4426950408889634
BLOCK_ROWS = 256  # x 128 lanes x 4B = 128 KiB per buffer — far under VMEM


# ---------------------------------------------------------------------------
# kernel bodies (operate on fp32 tiles)
# ---------------------------------------------------------------------------

def _exp2_poly(f):
    """2^f for f in [-0.5, 0.5], degree-5 minimax-ish polynomial."""
    c = (1.0, 0.6931471805599453, 0.24022650695910072,
         0.05550410866482158, 0.009618129107628477, 0.0013333558146428443)
    p = c[5]
    for ci in (c[4], c[3], c[2], c[1], c[0]):
        p = p * f + ci
    return p


def _exp(x):
    """Vector exp via exp2 range reduction with bit-assembled scaling.

    exp(x) = 2^(x*log2e) = 2^n * 2^f;  2^n is assembled by shifting the
    biased exponent into an IEEE-754 payload (the binary-magic-numbers
    move, cf. paper Listing 7).
    """
    y = x * _LOG2E
    n = jnp.round(y)
    f = y - n
    two_n = jax.lax.bitcast_convert_type(
        ((n.astype(jnp.int32) + 127) << 23).astype(jnp.int32), jnp.float32)
    return _exp2_poly(f) * two_n


# The pure tile math lives in standalone functions so the declared cost
# models can be *calibrated* against trace.jaxpr_vector_instrs of the
# very code the kernels execute (tests/test_cost_calibration.py).

def vtanh_math(x):
    t = jnp.clip(jnp.abs(x), 0.0, 20.0)
    z = _exp(-2.0 * t)                       # in (0, 1]
    th = (1.0 - z) / (1.0 + z)
    return jnp.sign(x) * th


def vsigmoid_math(x):
    t = jnp.clip(x, -30.0, 30.0)
    z = _exp(-jnp.abs(t))
    den = 1.0 + z
    # vrecpe + one Newton step: r <- r * (2 - den * r)
    r = 1.0 / den  # seed (TPU has a fast vector reciprocal)
    r = r * (2.0 - den * r)
    pos = 1.0 - z * r          # sigma(|t|)
    return jnp.where(t >= 0, pos, z * r)


def vsqrt_math(x):
    y = jax.lax.rsqrt(x)                      # vrsqrte seed
    for _ in range(2):                        # vrsqrts Newton ladder
        y = y * (1.5 - 0.5 * x * y * y)
    s = x * y
    s = jnp.where(x == 0.0, 0.0, s)
    return jnp.where(jnp.isinf(x), jnp.inf, s)


def vrelu_math(x, clamp_min, clamp_max):
    return jnp.clip(x, jnp.asarray(clamp_min, x.dtype),
                    jnp.asarray(clamp_max, x.dtype))


def _vtanh_body(x_ref, o_ref, *, out_dtype):
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = vtanh_math(x).astype(out_dtype)


def _vsigmoid_body(x_ref, o_ref, *, out_dtype):
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = vsigmoid_math(x).astype(out_dtype)


def _vsqrt_body(x_ref, o_ref, *, out_dtype):
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = vsqrt_math(x).astype(out_dtype)


def _vrelu_body(x_ref, o_ref, *, clamp_min, clamp_max, out_dtype):
    x = x_ref[...]
    o_ref[...] = vrelu_math(x, clamp_min, clamp_max).astype(out_dtype)


# ---------------------------------------------------------------------------
# pallas_call wrapper shared by the four kernels
# ---------------------------------------------------------------------------

def _elementwise_call(body, x, *, interpret=False, **body_kw):
    """Pack any logical shape into (rows, 128) tiles, run, slice the tail."""
    shape, dtype = x.shape, x.dtype
    tgt = compile_target()
    n = x.size
    lane = tgt.lane
    rows = max(1, round_up(n, lane) // lane)
    rows_p = round_up(rows, tgt.sublane(dtype))
    flat = masks.pad_to(x.reshape(-1), (rows_p * lane,)).reshape(rows_p, lane)
    br = min(BLOCK_ROWS, rows_p)
    rows_p2 = round_up(rows_p, br)
    if rows_p2 != rows_p:
        flat = masks.pad_to(flat, (rows_p2, lane))
    out = pl.pallas_call(
        functools.partial(body, out_dtype=dtype, **body_kw),
        grid=(rows_p2 // br,),
        in_specs=[pl.BlockSpec((br, lane), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, lane), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_p2, lane), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(flat)
    return out.reshape(-1)[:n].reshape(shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def vtanh(x, *, interpret=False):
    return _elementwise_call(_vtanh_body, x, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def vsigmoid(x, *, interpret=False):
    return _elementwise_call(_vsigmoid_body, x, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def vsqrt(x, *, interpret=False):
    return _elementwise_call(_vsqrt_body, x, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("clamp_min", "clamp_max", "interpret"))
def vrelu(x, clamp_min=0.0, clamp_max=float("inf"), *, interpret=False):
    return _elementwise_call(_vrelu_body, x, clamp_min=clamp_min,
                             clamp_max=clamp_max, interpret=interpret)


# ---------------------------------------------------------------------------
# dynamic-instruction cost models (vector ops per register tile)
# ---------------------------------------------------------------------------

def _ew_cost(ops_per_vec):
    def cost(x, *a, **kw):
        import math
        from repro.core import trace
        return ops_per_vec * math.ceil(x.size / trace.vreg_for(x.dtype))
    return cost


# declared ops/vreg, read off the kernel bodies above — the single
# source for both the registered cost models and CALIBRATION, so the
# two cannot drift apart
DECLARED_OPS_PER_VREG = {
    "vtanh": 22,      # exp2 poly(10) + reduction(6) + rational(6)
    "vsigmoid": 24,
    "vsqrt": 12,      # seed + 2 Newton x4 + fixups
    "vrelu": 2,       # min + max
}

cost_vtanh = _ew_cost(DECLARED_OPS_PER_VREG["vtanh"])
cost_vsigmoid = _ew_cost(DECLARED_OPS_PER_VREG["vsigmoid"])
cost_vsqrt = _ew_cost(DECLARED_OPS_PER_VREG["vsqrt"])
cost_vrelu = _ew_cost(DECLARED_OPS_PER_VREG["vrelu"])

# (tile math, declared ops/vreg) pairs: the calibration tests assert the
# declared numbers against trace.jaxpr_vector_instrs of the same code
CALIBRATION = {
    "vtanh": (vtanh_math, DECLARED_OPS_PER_VREG["vtanh"]),
    "vsigmoid": (vsigmoid_math, DECLARED_OPS_PER_VREG["vsigmoid"]),
    "vsqrt": (vsqrt_math, DECLARED_OPS_PER_VREG["vsqrt"]),
    "vrelu": (lambda x: vrelu_math(x, 0.0, 6.0),
              DECLARED_OPS_PER_VREG["vrelu"]),
}


def supports(x, *a, **kw) -> bool:
    return x.dtype in (jnp.float32, jnp.bfloat16)
