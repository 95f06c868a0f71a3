"""Customized TPU lowering of the XNNPACK f32/bf16 GEMM microkernel.

XNNPACK's NEON gemm ladders 4x8 register tiles of C with fused bias +
minmax clamp.  The TPU-native adaptation retiles for the MXU: (bm, bk) x
(bk, bn) VMEM blocks feeding 128x128 systolic macro-ops, fp32 accumulator
scratch persisting across the K grid dimension, epilogue (bias + clamp)
fused into the final K step — the same fusion the paper gets by writing
the epilogue in RVV intrinsics instead of letting the generic tier emit a
separate pass.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.targets import compile_target
from repro.core.vtypes import round_up
from repro.core import masks

DEFAULT_BM, DEFAULT_BN, DEFAULT_BK = 256, 256, 512


def _gemm_kernel(a_ref, b_ref, bias_ref, o_ref, acc_ref, *,
                 nk: int, clamp_min: float, clamp_max: float,
                 has_bias: bool, out_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # f32 operands take the MXU's full-precision passes: by default it
    # rounds them to bf16, which an f32 GEMM must not do
    prec = jax.lax.Precision.HIGHEST if a_ref.dtype == jnp.float32 else None
    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...], precision=prec,
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        acc = acc_ref[...]
        if has_bias:
            acc = acc + bias_ref[...].astype(jnp.float32)
        acc = jnp.clip(acc, clamp_min, clamp_max)
        o_ref[...] = acc.astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "clamp_min",
                                             "clamp_max", "interpret"))
def gemm(a: jnp.ndarray, b: jnp.ndarray, bias: Optional[jnp.ndarray] = None,
         clamp_min: float = float("-inf"), clamp_max: float = float("inf"),
         *, bm: int = DEFAULT_BM, bn: int = DEFAULT_BN, bk: int = DEFAULT_BK,
         interpret: bool = False) -> jnp.ndarray:
    """clamp(A @ B + bias) with MXU-tiled Pallas.  a:(M,K) b:(K,N)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    # Tail predication (paper Listing 4): pad to hardware tiles, slice the
    # logical extent back out.  Zero K-padding is exact for accumulation.
    tgt = compile_target()
    bm_, bn_, bk_ = min(bm, round_up(m, tgt.mxu)), min(bn, round_up(n, tgt.lane)), min(bk, round_up(k, tgt.lane))
    mp, np_, kp = round_up(m, bm_), round_up(n, bn_), round_up(k, bk_)
    a_p = masks.pad_to(a, (mp, kp))
    b_p = masks.pad_to(b, (kp, np_))
    has_bias = bias is not None
    bias_p = masks.pad_to(bias.reshape(1, n), (1, np_)) if has_bias else \
        jnp.zeros((1, np_), a.dtype)
    nk = kp // bk_
    grid = (mp // bm_, np_ // bn_, nk)

    out = pl.pallas_call(
        functools.partial(_gemm_kernel, nk=nk, clamp_min=clamp_min,
                          clamp_max=clamp_max, has_bias=has_bias,
                          out_dtype=a.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk_, bn_), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn_), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a_p, b_p, bias_p)
    return out[:m, :n]


def cost(a, b, bias=None, *_, **kw) -> int:
    """Dynamic instruction model (cost-target aware: MXU macro-ops on TPU,
    vfma ladder at RVV width)."""
    import math
    from repro.core import trace
    m, k = a.shape
    n = b.shape[1]
    tgt = trace.current_target()
    vreg = trace.vreg_for(a.dtype)
    if tgt.mxu >= 8:
        macro = math.ceil(m / tgt.mxu) * math.ceil(n / tgt.mxu) * \
            math.ceil(k / tgt.mxu)
    else:
        macro = math.ceil(m * n * k / vreg)
    epilogue = math.ceil(m * n / vreg) * 2
    return macro + epilogue


def supports(a, b, bias=None, *_, **kw) -> bool:
    return a.ndim == 2 and b.ndim == 2 and a.dtype in (jnp.float32, jnp.bfloat16)
