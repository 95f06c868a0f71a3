"""Customized TPU lowerings: conv_hwc (direct conv) + dwconv (depthwise).

XNNPACK's NEON convhwc walks HWC pointers with 4-wide vfma ladders.  The
TPU adaptation turns the kh*kw taps into MXU matmuls: the kernel holds a
whole (H, W, Ci) image slab in VMEM, statically unrolls the taps and
accumulates

    acc[oh, ow, co] += x[oh*sh + i, ow*sw + j, :] @ w[i, j, :, :]

i.e. (oh*ow, Ci) x (Ci, Co) per tap — im2col without ever materializing
the im2col matrix in HBM.  dwconv has no contraction, so the taps become
lane-aligned vfma chains on (oh, ow, C) slabs — a pure VPU kernel,
matching XNNPACK's dwconv structure.

The pallas tier's ``supports`` requires the slab working set to fit the
VMEM budget (the TPU version of the paper's "vlen >= width" rule);
larger images fall back to the vector tier (lax.conv).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.vtypes import round_up, vmem_fit
from repro.core import masks


def _conv_body(x_ref, w_ref, b_ref, o_ref, *, kh, kw, sh, sw, has_bias,
               out_dtype):
    # f32 inputs take the MXU's full-precision passes (the default
    # rounds them to bf16); bf16 inputs are exact in one pass
    prec = jax.lax.Precision.HIGHEST if x_ref.dtype == jnp.float32 else None
    x = x_ref[...].astype(jnp.float32)            # (1, H, W, Ci)
    w = w_ref[...].astype(jnp.float32)            # (kh, kw, Ci, Co)
    _, ih, iw, ci = x.shape
    co = w.shape[-1]
    oh = (ih - kh) // sh + 1
    ow = (iw - kw) // sw + 1
    acc = jnp.zeros((oh * ow, co), jnp.float32)
    for i in range(kh):
        for j in range(kw):
            tap = jax.lax.slice(x, (0, i, j, 0),
                                (1, i + sh * (oh - 1) + 1,
                                 j + sw * (ow - 1) + 1, ci),
                                (1, sh, sw, 1))   # (1, oh, ow, ci)
            acc += jnp.dot(tap.reshape(oh * ow, ci), w[i, j],
                           precision=prec,
                           preferred_element_type=jnp.float32)
    if has_bias:
        acc = acc + b_ref[...].astype(jnp.float32)
    o_ref[...] = acc.reshape(1, oh, ow, co).astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("stride", "interpret"))
def conv_hwc(x, w, bias=None, stride=(1, 1), *, interpret=False):
    """x:(N,H,W,Ci) w:(Kh,Kw,Ci,Co), VALID padding."""
    n, h, iw, ci = x.shape
    kh, kw, _, co = w.shape
    sh, sw = stride
    oh = (h - kh) // sh + 1
    ow = (iw - kw) // sw + 1
    has_bias = bias is not None
    b = bias if has_bias else jnp.zeros((co,), x.dtype)
    out = pl.pallas_call(
        functools.partial(_conv_body, kh=kh, kw=kw, sh=sh, sw=sw,
                          has_bias=has_bias, out_dtype=x.dtype),
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, h, iw, ci), lambda bi: (bi, 0, 0, 0)),
            pl.BlockSpec((kh, kw, ci, co), lambda bi: (0, 0, 0, 0)),
            pl.BlockSpec((co,), lambda bi: (0,)),
        ],
        out_specs=pl.BlockSpec((1, oh, ow, co), lambda bi: (bi, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, oh, ow, co), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, w, b)
    return out


def _dwconv_body(x_ref, w_ref, b_ref, o_ref, *, kh, kw, has_bias, out_dtype):
    x = x_ref[...].astype(jnp.float32)            # (1, H, W, C)
    w = w_ref[...].astype(jnp.float32)            # (kh, kw, C)
    _, ih, iw, c = x.shape
    oh, ow = ih - kh + 1, iw - kw + 1
    acc = jnp.zeros((oh, ow, c), jnp.float32)
    for i in range(kh):
        for j in range(kw):
            acc += x[0, i:i + oh, j:j + ow, :] * w[i, j][None, None, :]
    if has_bias:
        acc = acc + b_ref[...].astype(jnp.float32)
    o_ref[...] = acc[None].astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dwconv(x, w, bias=None, *, interpret=False):
    """Depthwise conv, stride 1, VALID.  x:(N,H,W,C) w:(Kh,Kw,C)."""
    n, h, iw, c = x.shape
    kh, kw, _ = w.shape
    oh, ow = h - kh + 1, iw - kw + 1
    has_bias = bias is not None
    b = bias if has_bias else jnp.zeros((c,), x.dtype)
    out = pl.pallas_call(
        functools.partial(_dwconv_body, kh=kh, kw=kw, has_bias=has_bias,
                          out_dtype=x.dtype),
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, h, iw, c), lambda bi: (bi, 0, 0, 0)),
            pl.BlockSpec((kh, kw, c), lambda bi: (0, 0, 0)),
            pl.BlockSpec((c,), lambda bi: (0,)),
        ],
        out_specs=pl.BlockSpec((1, oh, ow, c), lambda bi: (bi, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, oh, ow, c), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, w, b)
    return out


def supports_conv(x, w, bias=None, stride=(1, 1), **kw) -> bool:
    if x.ndim != 4 or w.ndim != 4:
        return False
    n, h, iw, ci = x.shape
    co = w.shape[-1]
    # slab + weights + fp32 accumulator must fit VMEM
    return vmem_fit([(h * iw * ci, x.dtype), (w.size, w.dtype),
                     (h * iw * co, jnp.float32)])


def supports_dwconv(x, w, bias=None, stride=(1, 1), **kw) -> bool:
    if x.ndim != 4 or w.ndim != 3 or tuple(stride) != (1, 1):
        return False
    n, h, iw, c = x.shape
    return vmem_fit([(h * iw * c, x.dtype), (h * iw * c, jnp.float32)])


def cost_conv(x, w, bias=None, stride=(1, 1), **_) -> int:
    import math
    from repro.core import trace
    n, h, iw, ci = x.shape
    kh, kw_, _, co = w.shape
    sh, sw = stride
    oh, ow = (h - kh) // sh + 1, (iw - kw_) // sw + 1
    tgt = trace.current_target()
    if tgt.mxu >= 8:
        return kh * kw_ * n * math.ceil(oh * ow / tgt.mxu) * \
            math.ceil(co / tgt.mxu) * math.ceil(ci / tgt.mxu)
    vreg = trace.vreg_for(x.dtype)
    return math.ceil(kh * kw_ * n * oh * ow * co * ci / vreg)


def cost_dwconv(x, w, bias=None, **_) -> int:
    import math
    from repro.core import trace
    n, h, iw, c = x.shape
    kh, kw_, _ = w.shape
    oh, ow = h - kh + 1, iw - kw_ + 1
    return kh * kw_ * math.ceil(n * oh * ow * c / trace.vreg_for(x.dtype))
