"""Customized TPU lowerings: maxpool + argmaxpool (NHWC, stride == window).

XNNPACK's NEON maxpool walks 9-high pointer ladders with vmax chains; the
TPU adaptation keeps whole (rows, W, C) slabs in VMEM and reduces windows
by *reshape decimation* — (H, W) -> (oh, kh, ow, kw) — so the reduction is
lane-aligned vmax ops with no gathers.  argmaxpool tracks the running max
and its window index with a vbsl/select ladder (the paper's vceq->merge
composition, method 5).

The pallas tier registers ``supports`` = (stride == window, exact
decimation) — the paper's "vlen >= width" validity rule; other configs
fall back to the vector tier (lax.reduce_window).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.vtypes import round_up
from repro.core import masks


def _maxpool_body(x_ref, o_ref, *, kh, kw):
    x = x_ref[...]                                # (1, bh*kh, W, C)
    _, ih, w, c = x.shape
    oh, ow = ih // kh, w // kw
    x = x.reshape(oh, kh, ow, kw, c)
    o_ref[...] = jnp.max(x, axis=(1, 3))[None]


def _argmaxpool_body(x_ref, o_ref, idx_ref, *, kh, kw):
    x = x_ref[...]
    _, ih, w, c = x.shape
    oh, ow = ih // kh, w // kw
    x = x.reshape(oh, kh, ow, kw, c)
    neg = jnp.asarray(-jnp.inf, x.dtype) if jnp.issubdtype(x.dtype, jnp.floating) \
        else jnp.iinfo(x.dtype).min
    best = jnp.full((oh, ow, c), neg, x.dtype)
    best_i = jnp.zeros((oh, ow, c), jnp.int32)
    # select ladder over the kh*kw window positions (static unroll)
    for i in range(kh):
        for j in range(kw):
            cand = x[:, i, :, j, :]
            take = cand > best                    # vmsgt
            best = jnp.where(take, cand, best)    # vmerge
            best_i = jnp.where(take, i * kw + j, best_i)
    o_ref[...] = best[None]
    idx_ref[...] = best_i[None]


def _pool_call(body, x, window, n_out, out_dtypes, *, interpret):
    n, h, w, c = x.shape
    kh, kw = window
    oh, ow = h // kh, w // kw
    # trim ragged tail rows/cols (VALID pooling semantics)
    x = x[:, :oh * kh, :ow * kw]
    bh = max(1, min(oh, 512 * 1024 // max(1, (ow * kw * c * x.dtype.itemsize * kh))))
    ohp = round_up(oh, bh)
    xp = masks.pad_to(x, (n, ohp * kh, ow * kw, c))
    grid = (n, ohp // bh)
    outs = pl.pallas_call(
        functools.partial(body, kh=kh, kw=kw),
        grid=grid,
        in_specs=[pl.BlockSpec((1, bh * kh, ow * kw, c), lambda b, i: (b, i, 0, 0))],
        out_specs=tuple(
            pl.BlockSpec((1, bh, ow, c), lambda b, i: (b, i, 0, 0))
            for _ in range(n_out)),
        out_shape=tuple(
            jax.ShapeDtypeStruct((n, ohp, ow, c), dt) for dt in out_dtypes),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(xp)
    return tuple(o[:, :oh] for o in outs)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def maxpool(x, window=(2, 2), *, interpret=False):
    (out,) = _pool_call(_maxpool_body, x, window, 1, (x.dtype,),
                        interpret=interpret)
    return out


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def argmaxpool(x, window=(2, 2), *, interpret=False):
    out, idx = _pool_call(_argmaxpool_body, x, window, 2, (x.dtype, jnp.int32),
                          interpret=interpret)
    return out, idx


def supports(x, window=(2, 2), stride=None, **kw) -> bool:
    """Pallas tier valid iff stride == window (decimation reshape exact)."""
    return (stride is None or tuple(stride) == tuple(window)) and x.ndim == 4


def cost_maxpool(x, window=(2, 2), **kw) -> int:
    import math
    from repro.core import trace
    kh, kw_ = window
    out_elems = x.size // (kh * kw_)
    return (kh * kw_ - 1) * math.ceil(out_elems / trace.vreg_for(x.dtype))


def cost_argmaxpool(x, window=(2, 2), **kw) -> int:
    import math
    from repro.core import trace
    kh, kw_ = window
    out_elems = x.size // (kh * kw_)
    return 3 * kh * kw_ * math.ceil(out_elems / trace.vreg_for(x.dtype))
