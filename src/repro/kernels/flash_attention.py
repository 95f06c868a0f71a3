"""Flash attention — beyond-paper customized lowering for the LM zoo.

The paper's customized conversions fuse what the generic tier would
materialize; attention is the framework-scale instance of the same move:
the generic (vector-tier) lowering materializes the (Sq, Sk) logits in
HBM, while this kernel keeps the running softmax statistics in VMEM
scratch (online softmax) and never leaves the chip.

Features needed by the assigned archs, all fused:
  * GQA        — kv blocks indexed by h // group (no kv broadcast in HBM),
  * causal     — with block-level skipping of fully-masked kv blocks,
  * sliding window (gemma2/3 local layers),
  * logit softcap (gemma2) — reuses the vtanh lowering inside the kernel,
  * decode     — one-query variant with dynamic valid-length masking via
                 scalar prefetch (serving hot path).
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

NEG = -1e30


def _flash_body(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                scale, causal, window, softcap, bq, bk, nk, kv_valid,
                q_offset, out_dtype):
    iq, kk = pl.program_id(2), pl.program_id(3)

    @pl.when(kk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # block-level skip: under causal/window masking many kv blocks are
    # entirely masked — skip their compute (real TPU savings; the paper's
    # analogue is not emitting instructions the generic tier would).
    q_lo = q_offset + iq * bq
    q_hi = q_lo + bq - 1
    k_lo = kk * bk
    k_hi = k_lo + bk - 1
    needed = k_lo < kv_valid
    if causal:
        needed = jnp.logical_and(needed, k_lo <= q_hi)
    if window is not None:
        needed = jnp.logical_and(needed, k_hi >= q_lo - window + 1)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < kv_valid
        if causal:
            mask = jnp.logical_and(mask, qpos >= kpos)
        if window is not None:
            mask = jnp.logical_and(mask, qpos - kpos < window)
        s = jnp.where(mask, s, NEG)
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kk == nk - 1)
    def _finish():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(out_dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "scale", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                    scale=None, bq=512, bk=512, interpret=False):
    """q:(B,H,Sq,D) k,v:(B,Hkv,Sk,D) -> (B,H,Sq,D).  H % Hkv == 0."""
    b, h, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    group = h // hkv
    scale = scale if scale is not None else float(d) ** -0.5
    tgt = compile_target()
    bq_ = min(bq, round_up(sq, tgt.sublane(q.dtype)))
    bk_ = min(bk, round_up(sk, tgt.lane))
    sqp, skp = round_up(sq, bq_), round_up(sk, bk_)
    dp = round_up(d, tgt.lane)
    q_p = masks.pad_to(q, (b, h, sqp, dp))
    k_p = masks.pad_to(k, (b, hkv, skp, dp))
    v_p = masks.pad_to(v, (b, hkv, skp, dp))
    nk = skp // bk_
    grid = (b, h, sqp // bq_, nk)
    out = pl.pallas_call(
        functools.partial(
            _flash_body, scale=scale, causal=causal, window=window,
            softcap=softcap, bq=bq_, bk=bk_, nk=nk, kv_valid=sk,
            q_offset=sk - sq, out_dtype=q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq_, dp), lambda bb, hh, iq, kk: (bb, hh, iq, 0)),
            pl.BlockSpec((1, 1, bk_, dp),
                         lambda bb, hh, iq, kk: (bb, hh // group, kk, 0)),
            pl.BlockSpec((1, 1, bk_, dp),
                         lambda bb, hh, iq, kk: (bb, hh // group, kk, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq_, dp),
                               lambda bb, hh, iq, kk: (bb, hh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sqp, dp), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq_, dp), jnp.float32),
            pltpu.VMEM((bq_, tgt.lane), jnp.float32),
            pltpu.VMEM((bq_, tgt.lane), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q_p, k_p, v_p)
    return out[:, :, :sq, :d]


# ---------------------------------------------------------------------------
# decode: one query against a long cache, dynamic valid length
# ---------------------------------------------------------------------------

def _decode_body(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                 *, scale, softcap, window, bk, nk, out_dtype):
    bb, kk = pl.program_id(0), pl.program_id(2)
    valid = len_ref[bb]

    @pl.when(kk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k_lo = kk * bk
    needed = k_lo < valid
    if window is not None:
        needed = jnp.logical_and(needed, k_lo + bk - 1 >= valid - window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (1-ish rows, D)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < valid
        if window is not None:
            mask = jnp.logical_and(mask, kpos >= valid - window)
        s = jnp.where(mask, s, NEG)
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kk == nk - 1)
    def _finish():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("softcap", "window", "scale",
                                             "bk", "interpret"))
def decode_attention(q, k, v, lengths, *, softcap=None, window=None,
                     scale=None, bk=1024, interpret=False):
    """q:(B,H,1,D) k,v:(B,Hkv,S,D) lengths:(B,) int32 -> (B,H,1,D)."""
    b, h, one, d = q.shape
    _, hkv, s, _ = k.shape
    group = h // hkv
    scale = scale if scale is not None else float(d) ** -0.5
    tgt = compile_target()
    bk_ = min(bk, round_up(s, tgt.lane))
    sp = round_up(s, bk_)
    dp = round_up(d, tgt.lane)
    rq = tgt.sublane(q.dtype)  # pad the single query row to a sublane tile
    q_p = masks.pad_to(q, (b, h, rq, dp))
    k_p = masks.pad_to(k, (b, hkv, sp, dp))
    v_p = masks.pad_to(v, (b, hkv, sp, dp))
    nk = sp // bk_
    out = pl.pallas_call(
        functools.partial(_decode_body, scale=scale, softcap=softcap,
                          window=window, bk=bk_, nk=nk, out_dtype=q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h, nk),
            in_specs=[
                pl.BlockSpec((1, 1, rq, dp), lambda bb, hh, kk, lr: (bb, hh, 0, 0)),
                pl.BlockSpec((1, 1, bk_, dp),
                             lambda bb, hh, kk, lr: (bb, hh // group, kk, 0)),
                pl.BlockSpec((1, 1, bk_, dp),
                             lambda bb, hh, kk, lr: (bb, hh // group, kk, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, rq, dp),
                                   lambda bb, hh, kk, lr: (bb, hh, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((rq, dp), jnp.float32),
                pltpu.VMEM((rq, tgt.lane), jnp.float32),
                pltpu.VMEM((rq, tgt.lane), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, rq, dp), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q_p, k_p, v_p)
    return out[:, :, :1, :d]


def supports(q, k, v, **kw) -> bool:
    return q.ndim == 4 and k.ndim == 4 and q.shape[1] % k.shape[1] == 0


def cost(q, k, v, *, causal=True, **kw) -> int:
    import math
    b, h, sq, d = q.shape
    sk = k.shape[2]
    tgt = current_target()
    frac = 0.5 if causal and sq == sk else 1.0
    if tgt.has_mxu:
        mx = tgt.mxu
        qk = b * h * math.ceil(sq / mx) * math.ceil(sk / mx) * math.ceil(d / mx)
        pv = b * h * math.ceil(sq / mx) * math.ceil(d / mx) * math.ceil(sk / mx)
    else:                        # vfma ladder at VLA width
        vreg = tgt.vreg_elems(q.dtype)
        qk = pv = b * h * math.ceil(sq * sk * d / vreg)
    soft = 6 * b * h * math.ceil(sq * sk / tgt.vreg_elems(q.dtype))
    return int(frac * (qk + pv + soft))
