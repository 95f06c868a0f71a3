"""Mamba2 SSD (state-space duality) chunked scan — customized lowering.

The sequential SSD recurrence

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t ;   y_t = C_t . S_t

has no 1:1 TPU op — the paper's "method 5" case (compose a conversion
from several target ops).  The SSD block decomposition (Dao & Gu 2024)
adapted to the MXU: each length-L chunk becomes

    y_intra = ((C B^T) * decay) @ (dt * x)       -- MXU matmuls
    y_inter = exp(la) * (C @ S_chunk_start^T)    -- MXU matmul
    S_next  = exp(la_L) S + (w * x)^T B          -- MXU matmul

with the chunk grid axis sequential and the (p, n) state living in VMEM
scratch across grid steps.  The VPU handles only the O(L) decay vectors.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.targets import compile_target, current_target
from repro.core.vtypes import round_up
from repro.core import masks


def _ssd_body(a_ref, x_ref, dt_ref, dtr_ref, b_ref, c_ref, o_ref, state_ref,
              *, nchunks, out_dtype):
    bh, ci = pl.program_id(0), pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    a = a_ref[bh]                                  # scalar A (negative)
    x = x_ref[0].astype(jnp.float32)               # (L, p)
    dt_c = dt_ref[0].astype(jnp.float32)           # (L, 1) column layout
    dt_r = dtr_ref[0].astype(jnp.float32)          # (1, L) row layout
    bm = b_ref[0].astype(jnp.float32)              # (L, n)
    cm = c_ref[0].astype(jnp.float32)              # (L, n)
    L = x.shape[0]
    t = (((1,), (1,)), ((), ()))                   # contract both minor dims

    row = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    causal = row >= col
    # inclusive log-decay la_i = A * sum_{j<=i} dt_j, as a column and as a
    # row: masked sums, because the TPU lowering has no cumsum
    la_c = a * jnp.sum(jnp.where(causal, dt_r, 0.0), axis=1, keepdims=True)
    la_r = a * jnp.sum(jnp.where(row <= col, dt_c, 0.0), axis=0,
                       keepdims=True)
    # inter-chunk: y_i += exp(la_i) * C_i . S
    y_inter = jnp.exp(la_c) * jax.lax.dot_general(
        cm, state_ref[...], t, preferred_element_type=jnp.float32)  # (L, p)
    # intra-chunk: masked decay kernel exp(la_i - la_j) dt_j
    w = jnp.where(causal, jnp.exp(la_c - la_r), 0.0) * dt_r
    g = jax.lax.dot_general(cm, bm, t,
                            preferred_element_type=jnp.float32)     # (L, L)
    y_intra = jnp.dot(g * w, x, preferred_element_type=jnp.float32)
    o_ref[0] = (y_inter + y_intra).astype(out_dtype)
    # state update: S <- exp(la_L) S + sum_j exp(la_L - la_j) dt_j x_j (x) B_j
    la_last = a * jnp.sum(dt_c, axis=0, keepdims=True)             # (1, 1)
    wj = jnp.exp(la_last - la_c) * dt_c                            # (L, 1)
    state_ref[...] = jnp.exp(la_last) * state_ref[...] + jax.lax.dot_general(
        x * wj, bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x, dt, A, B, C, D=None, *, chunk=128, interpret=False):
    """Chunked SSD.  x:(b,s,h,p) dt:(b,s,h) A:(h,) B,C:(b,s,g,n)."""
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    L = min(chunk, round_up(s, compile_target().sublane(jnp.float32)))
    sp = round_up(s, L)
    nchunks = sp // L
    # (b,h) flattened onto the leading grid axis; groups expanded to heads
    xt = masks.pad_to(x.transpose(0, 2, 1, 3).reshape(b * h, s, p),
                      (b * h, sp, p))
    dtt = masks.pad_to(dt.transpose(0, 2, 1).reshape(b * h, s, 1),
                       (b * h, sp, 1))            # zero dt => no-op steps
    dtr = dtt.reshape(b * h, 1, sp)
    Bh = jnp.repeat(B, rep, axis=2).transpose(0, 2, 1, 3).reshape(b * h, s, n)
    Ch = jnp.repeat(C, rep, axis=2).transpose(0, 2, 1, 3).reshape(b * h, s, n)
    Bh = masks.pad_to(Bh, (b * h, sp, n))
    Ch = masks.pad_to(Ch, (b * h, sp, n))
    Ab = jnp.tile(A.astype(jnp.float32), (b,))    # (b*h,)

    out = pl.pallas_call(
        functools.partial(_ssd_body, nchunks=nchunks, out_dtype=x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * h, nchunks),
            in_specs=[
                pl.BlockSpec((1, L, p), lambda i, c, ar: (i, c, 0)),
                pl.BlockSpec((1, L, 1), lambda i, c, ar: (i, c, 0)),
                pl.BlockSpec((1, 1, L), lambda i, c, ar: (i, 0, c)),
                pl.BlockSpec((1, L, n), lambda i, c, ar: (i, c, 0)),
                pl.BlockSpec((1, L, n), lambda i, c, ar: (i, c, 0)),
            ],
            out_specs=pl.BlockSpec((1, L, p), lambda i, c, ar: (i, c, 0)),
            scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b * h, sp, p), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(Ab, xt, dtt, dtr, Bh, Ch)
    y = out[:, :s].reshape(b, h, s, p).transpose(0, 2, 1, 3)
    if D is not None:
        y = y + (D[None, None, :, None] * x.astype(jnp.float32)).astype(y.dtype)
    return y


def supports(x, dt, A, B, C, D=None, **kw) -> bool:
    b, s, h, p = x.shape
    n = B.shape[-1]
    return h % B.shape[2] == 0


def cost(x, dt, A, B, C, D=None, *, chunk=128, **_) -> int:
    import math
    b, s, h, p = x.shape
    n = B.shape[-1]
    L = chunk
    tgt = current_target()
    nch = math.ceil(s / L)
    vreg = tgt.vreg_elems(x.dtype)
    if tgt.has_mxu:
        mx = tgt.mxu
        mm = (math.ceil(L / mx) ** 2 * math.ceil(n / mx)         # C B^T
              + math.ceil(L / mx) ** 2 * math.ceil(p / mx)       # (GW) x
              + 2 * math.ceil(L / mx) * math.ceil(n / mx) * math.ceil(p / mx))
    else:                        # vfma ladder at VLA width
        mm = math.ceil(L * L * (n + p) / vreg) + 2 * math.ceil(L * n * p / vreg)
    per_chunk = mm + 8 * math.ceil(L * L / vreg)
    return b * h * nch * per_chunk
