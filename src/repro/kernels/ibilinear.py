"""Customized TPU lowering of XNNPACK ibilinear (bilinear interpolation).

XNNPACK precomputes per-output-pixel top-left pointers + fractional
weights and the NEON microkernel loads 2x2 corner pairs.  On TPU the
per-pixel corner coordinates are *scalar prefetch* arguments (SMEM), so
the kernel can issue dynamic VMEM slices for the 2x2xC corner loads while
the channel axis rides the lanes — the TPU-idiomatic replacement for the
pointer ladder (per-lane gathers don't exist on the VPU; channels-last
vectorization is the adaptation).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.vtypes import round_up, vmem_fit
from repro.core import masks

BP = 8  # pixels per block (sublane-aligned)


def _ibilinear_body(iy_ref, ix_ref, wy_ref, wx_ref, img_ref, o_ref, *, bp, w):
    blk = pl.program_id(0)
    for p in range(bp):  # static unroll; each p is one output pixel
        # top-left corner's row in the (H*W, C) image; the four corners
        # are single-row loads with the channels on the lanes
        r = iy_ref[blk * bp + p] * w + ix_ref[blk * bp + p]

        def corner(off):
            return img_ref[pl.ds(r + off, 1), :].astype(jnp.float32)

        wy = wy_ref[p:p + 1, :].astype(jnp.float32)   # (1, 1)
        wx = wx_ref[p:p + 1, :].astype(jnp.float32)
        top = corner(0) * (1 - wx) + corner(1) * wx
        bot = corner(w) * (1 - wx) + corner(w + 1) * wx
        o_ref[p:p + 1, :] = (top * (1 - wy) + bot * wy).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ibilinear(img, iy, ix, wy, wx, *, interpret=False):
    """img:(H,W,C) iy,ix:(P,) int32 wy,wx:(P,) -> (P,C)."""
    h, w, c = img.shape
    p = iy.shape[0]
    pp = round_up(p, BP)
    iy_p = masks.pad_to(iy, (pp,))
    ix_p = masks.pad_to(ix, (pp,))
    # weights as (P, 1) columns: a rank-1 (BP,) block is not a legal
    # TPU tile
    wy_p = masks.pad_to(wy, (pp,)).reshape(pp, 1)
    wx_p = masks.pad_to(wx, (pp,)).reshape(pp, 1)
    grid = (pp // BP,)
    out = pl.pallas_call(
        functools.partial(_ibilinear_body, bp=BP, w=w),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((BP, 1), lambda i, iy_r, ix_r: (i, 0)),
                pl.BlockSpec((BP, 1), lambda i, iy_r, ix_r: (i, 0)),
                pl.BlockSpec((h * w, c), lambda i, iy_r, ix_r: (0, 0)),
            ],
            out_specs=pl.BlockSpec((BP, c), lambda i, iy_r, ix_r: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((pp, c), img.dtype),
        interpret=interpret,
    )(iy_p, ix_p, wy_p, wx_p, img.reshape(h * w, c))
    return out[:p]


def supports(img, iy, ix, wy, wx, **kw) -> bool:
    h, w, c = img.shape
    return vmem_fit([(h * w * c, img.dtype)])


def cost(img, iy, ix, wy, wx, **_) -> int:
    import math
    from repro.core import trace
    p = iy.shape[0]
    c = img.shape[-1]
    # per pixel: 4 corner vector loads + 6 fma-class ops on C-lane vectors
    return p * (4 + 6) * math.ceil(c / trace.current_target().lane)
