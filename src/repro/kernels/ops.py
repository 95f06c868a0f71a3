"""Public kernel API — every op dispatches through the conversion ladder.

This is the framework's ``simde/arm/neon.h``: models import these
functions; the registry picks the lowering tier exactly like SIMDe's
preprocessor ladder picks an implementation (DESIGN.md §3).

  policy 'pallas' (default on TPU) — customized kernels (enhanced SIMDe)
  policy 'vector' (default on CPU) — whole-array jnp  (original SIMDe)
  policy 'generic'                 — scalar-emulation oracle tier

``repro.core.use_policy`` overrides per scope; ``repro.kernels.figure2``
runs both sides of the paper's Figure-2 comparison through this exact
dispatch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import trace
from repro.core.registry import register, dispatch
from . import conv as _conv
from . import elementwise as _ew
from . import flash_attention as _fa
from . import gemm as _gemm
from . import ibilinear as _ib
from . import pooling as _pool
from . import ref
from . import ssd as _ssd


def _interp() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# Cost models.  The generic tier counts the scalar loop's element ops
# (explicit shape formulas); the vector tier *analyzes its own generated
# code* against the active target (trace.traced_cost — the paper's §4
# methodology), including the original-SIMDe union round-trip and
# target-dependent scalarization of transcendentals; the pallas tier
# declares its kernel-structure count.  registry.select compares these
# per (op, shape, target) and picks the cheapest.
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# gemm
# ---------------------------------------------------------------------------

def _gemm_scalar_cost(a, b, *_, **__):
    m, k = a.shape
    return 2 * m * k * b.shape[1]


register("gemm", "generic", cost=_gemm_scalar_cost,
         doc="scalar MAC loop emulation")(ref.gemm)
register("gemm", "vector", cost=trace.traced_cost(ref.gemm),
         doc="jnp.dot (vector-attribute tier)")(ref.gemm)


@register("gemm", "pallas", cost=_gemm.cost, supports=_gemm.supports,
          doc="MXU-tiled fused bias+clamp GEMM")
def _gemm_pallas(a, b, bias=None, clamp_min=float("-inf"),
                 clamp_max=float("inf")):
    return _gemm.gemm(a, b, bias, clamp_min, clamp_max, interpret=_interp())


def gemm(a, b, bias=None, clamp_min=float("-inf"), clamp_max=float("inf"),
         *, policy=None, target=None):
    return dispatch("gemm", a, b, bias, clamp_min, clamp_max, policy=policy,
                    target=target)


# ---------------------------------------------------------------------------
# grouped gemm (rows sorted by group; one weight matrix per group)
# ---------------------------------------------------------------------------

def _grouped_gemm_cost(x, w, group_sizes, **_):
    return 2 * x.shape[0] * x.shape[1] * w.shape[2]


@register("grouped_gemm", "vector", cost=_grouped_gemm_cost,
          doc="jax.lax.ragged_dot (XLA's grouped matmul on TPU)")
def _grouped_gemm_vector(x, w, group_sizes):
    return jax.lax.ragged_dot(x, w, group_sizes)


def grouped_gemm(x, w, group_sizes, *, policy=None, target=None):
    """x:(M, K) rows sorted by group, w:(G, K, N), group_sizes:(G,) ->
    (M, N): rows of group g times w[g]; rows past sum(group_sizes) are 0."""
    return dispatch("grouped_gemm", x, w, group_sizes, policy=policy,
                    target=target)


# ---------------------------------------------------------------------------
# convolutions
# ---------------------------------------------------------------------------

def _conv_scalar_cost(x, w, bias=None, stride=(1, 1), **_):
    n, h, iw, ci = x.shape
    kh, kw_, _, co = w.shape
    sh, sw = stride
    oh, ow = (h - kh) // sh + 1, (iw - kw_) // sw + 1
    return 2 * n * oh * ow * co * kh * kw_ * ci


register("conv_hwc", "generic", cost=_conv_scalar_cost)(ref.conv_hwc)
register("conv_hwc", "vector",
         cost=trace.traced_cost(ref.conv_hwc))(ref.conv_hwc)


@register("conv_hwc", "pallas", cost=_conv.cost_conv,
          supports=_conv.supports_conv, doc="tap-unrolled MXU direct conv")
def _conv_pallas(x, w, bias=None, stride=(1, 1)):
    return _conv.conv_hwc(x, w, bias, stride, interpret=_interp())


def conv_hwc(x, w, bias=None, stride=(1, 1), *, policy=None):
    return dispatch("conv_hwc", x, w, bias, stride, policy=policy)


def _dwconv_scalar_cost(x, w, bias=None, stride=(1, 1), **_):
    n, h, iw, c = x.shape
    kh, kw_, _ = w.shape
    sh, sw = stride
    oh, ow = (h - kh) // sh + 1, (iw - kw_) // sw + 1
    return 2 * n * oh * ow * c * kh * kw_


register("dwconv", "generic", cost=_dwconv_scalar_cost)(ref.dwconv)
register("dwconv", "vector", cost=trace.traced_cost(ref.dwconv))(ref.dwconv)


@register("dwconv", "pallas", cost=_conv.cost_dwconv,
          supports=_conv.supports_dwconv, doc="VPU vfma-chain depthwise conv")
def _dwconv_pallas(x, w, bias=None, stride=(1, 1)):
    return _conv.dwconv(x, w, bias, interpret=_interp())


def dwconv(x, w, bias=None, stride=(1, 1), *, policy=None):
    return dispatch("dwconv", x, w, bias, stride, policy=policy)


# ---------------------------------------------------------------------------
# pooling
# ---------------------------------------------------------------------------

def _pool_scalar_cost(mult):
    def cost(x, window=(2, 2), stride=None, **_):
        return mult * x.size  # one compare/update per input element
    return cost


register("maxpool", "generic", cost=_pool_scalar_cost(1))(ref.maxpool)
register("maxpool", "vector",
         cost=trace.traced_cost(ref.maxpool))(ref.maxpool)


@register("maxpool", "pallas", cost=_pool.cost_maxpool,
          supports=_pool.supports, doc="reshape-decimation vmax pooling")
def _maxpool_pallas(x, window=(2, 2), stride=None):
    return _pool.maxpool(x, window, interpret=_interp())


def maxpool(x, window=(2, 2), stride=None, *, policy=None):
    return dispatch("maxpool", x, window, stride, policy=policy)


register("argmaxpool", "generic", cost=_pool_scalar_cost(2))(ref.argmaxpool)
register("argmaxpool", "vector",
         cost=trace.traced_cost(ref.argmaxpool))(ref.argmaxpool)


@register("argmaxpool", "pallas", cost=_pool.cost_argmaxpool,
          supports=_pool.supports, doc="select-ladder argmax pooling")
def _argmaxpool_pallas(x, window=(2, 2), stride=None):
    return _pool.argmaxpool(x, window, interpret=_interp())


def argmaxpool(x, window=(2, 2), stride=None, *, policy=None):
    return dispatch("argmaxpool", x, window, stride, policy=policy)


# ---------------------------------------------------------------------------
# elementwise
# ---------------------------------------------------------------------------

register("vrelu", "generic", cost=trace.scalar_cost(2))(ref.vrelu)
register("vrelu", "vector", cost=trace.traced_cost(ref.vrelu))(ref.vrelu)


@register("vrelu", "pallas", cost=_ew.cost_vrelu, supports=_ew.supports,
          doc="fused minmax clamp")
def _vrelu_pallas(x, clamp_min=0.0, clamp_max=float("inf")):
    return _ew.vrelu(x, clamp_min, clamp_max, interpret=_interp())


def vrelu(x, clamp_min=0.0, clamp_max=float("inf"), *, policy=None):
    return dispatch("vrelu", x, clamp_min, clamp_max, policy=policy)


# For the transcendentals the vector tier's true cost is target-dependent:
# with no vector libm (the baseline RVV toolchain) the call scalarizes —
# the paper's Figure-2 story.  traced_cost(transcendental=True) models
# exactly that via targets.Target.has_vector_libm.
register("vsqrt", "generic",
         cost=trace.scalar_cost(trace.PRIM_SCALAR_COST["sqrt"]))(ref.vsqrt)
register("vsqrt", "vector",
         cost=trace.traced_cost(ref.vsqrt, transcendental=True))(ref.vsqrt)


@register("vsqrt", "pallas", cost=_ew.cost_vsqrt, supports=_ew.supports,
          doc="vrsqrte + Newton ladder")
def _vsqrt_pallas(x):
    return _ew.vsqrt(x, interpret=_interp())


def vsqrt(x, *, policy=None):
    return dispatch("vsqrt", x, policy=policy)


register("vtanh", "generic",
         cost=trace.scalar_cost(trace.PRIM_SCALAR_COST["tanh"]))(ref.vtanh)
register("vtanh", "vector",
         cost=trace.traced_cost(ref.vtanh, transcendental=True))(ref.vtanh)


@register("vtanh", "pallas", cost=_ew.cost_vtanh, supports=_ew.supports,
          doc="exp2 range-reduction rational tanh")
def _vtanh_pallas(x):
    return _ew.vtanh(x, interpret=_interp())


def vtanh(x, *, policy=None):
    return dispatch("vtanh", x, policy=policy)


register("vsigmoid", "generic",
         cost=trace.scalar_cost(
             trace.PRIM_SCALAR_COST["logistic"]))(ref.vsigmoid)
register("vsigmoid", "vector",
         cost=trace.traced_cost(ref.vsigmoid,
                                transcendental=True))(ref.vsigmoid)


@register("vsigmoid", "pallas", cost=_ew.cost_vsigmoid, supports=_ew.supports,
          doc="exp2 reduction + vrecpe Newton sigmoid")
def _vsigmoid_pallas(x):
    return _ew.vsigmoid(x, interpret=_interp())


def vsigmoid(x, *, policy=None):
    return dispatch("vsigmoid", x, policy=policy)


# ---------------------------------------------------------------------------
# ibilinear
# ---------------------------------------------------------------------------

def _ibilinear_scalar_cost(img, iy, ix, wy, wx, **_):
    # per output element: 4 gathered loads + 8 mul/add
    return 12 * iy.shape[0] * img.shape[-1]


register("ibilinear", "generic", cost=_ibilinear_scalar_cost)(ref.ibilinear)
register("ibilinear", "vector",
         cost=trace.traced_cost(ref.ibilinear))(ref.ibilinear)


@register("ibilinear", "pallas", cost=_ib.cost, supports=_ib.supports,
          doc="scalar-prefetch corner loads, channel-lane bilinear")
def _ibilinear_pallas(img, iy, ix, wy, wx):
    return _ib.ibilinear(img, iy, ix, wy, wx, interpret=_interp())


def ibilinear(img, iy, ix, wy, wx, *, policy=None):
    return dispatch("ibilinear", img, iy, ix, wy, wx, policy=policy)


# ---------------------------------------------------------------------------
# attention (beyond-paper; model-facing layout (B, S, H, D))
# ---------------------------------------------------------------------------

def _attn_vector(q, k, v, causal=True, window=None, softcap=None, scale=None):
    if q.shape[1] * k.shape[1] > 2048 * 2048:
        return ref.attention_chunked(q, k, v, causal=causal, window=window,
                                     softcap=softcap, scale=scale)
    return ref.attention(q, k, v, causal=causal, window=window,
                         softcap=softcap, scale=scale)


register("attention", "vector", cost=trace.traced_cost(_attn_vector),
         doc="attention; chunked online-softmax beyond 2k seq")(_attn_vector)


def _attn_supports(q, k, v, causal=True, window=None, softcap=None,
                   scale=None):
    # a value head narrower than the query head (MLA: 128 under 192) is
    # zero-padded to it; both round up to the same lane multiple
    return (v.shape[-1] <= q.shape[-1] and
            _fa.supports(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v))


@register("attention", "pallas", supports=_attn_supports,
          cost=lambda q, k, v, causal=True, *_, **kw: _fa.cost(
              q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
              v.transpose(0, 2, 1, 3), causal=causal),
          doc="online-softmax flash attention, VMEM-resident stats")
def _attn_pallas(q, k, v, causal=True, window=None, softcap=None, scale=None):
    dv = v.shape[-1]
    if dv < q.shape[-1]:
        v = jnp.pad(v, [(0, 0)] * 3 + [(0, q.shape[-1] - dv)])
    out = _fa.flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal, window=window,
        softcap=softcap, scale=scale, interpret=_interp())
    return out.transpose(0, 2, 1, 3)[..., :dv]


def attention(q, k, v, *, causal=True, window=None, softcap=None, scale=None,
              policy=None, target=None):
    """q:(B,Sq,H,D) k,v:(B,Sk,Hkv,D) -> (B,Sq,H,D).

    ``target`` selects the lowering against an explicit machine model
    (multi-backend serving mixes targets per request); None uses the
    ambient thread-scoped target.
    """
    return dispatch("attention", q, k, v, causal, window, softcap, scale,
                    policy=policy, target=target)


def _dec_attn_vector(q, k, v, lengths, window=None, softcap=None, scale=None):
    # q:(B,1,H,D); mask cache positions >= per-row valid length
    return _dec_ref(q, k, v, lengths, window, softcap, scale)


register("decode_attention", "vector",
         cost=trace.traced_cost(_dec_attn_vector))(_dec_attn_vector)


def _dec_ref(q, k, v, lengths, window, softcap, scale):
    import numpy as np
    b, one, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    group = h // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    qf = q.astype(jnp.float32).reshape(b, one, hkv, group, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qf, k.astype(jnp.float32)) * scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    kpos = jnp.arange(sk)[None, :]
    mask = kpos < lengths[:, None]
    if window is not None:
        mask &= kpos >= (lengths[:, None] - window)
    logits = jnp.where(mask[:, None, None, None, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return out.reshape(b, one, h, d).astype(q.dtype)


@register("decode_attention", "pallas",
          supports=lambda q, k, v, lengths, *_, **kw: q.shape[1] == 1,
          cost=lambda q, k, v, lengths, *_, **kw: _fa.cost(
              q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
              v.transpose(0, 2, 1, 3), causal=False),
          doc="flash-decode with dynamic valid length (scalar prefetch)")
def _dec_attn_pallas(q, k, v, lengths, window=None, softcap=None, scale=None):
    out = _fa.decode_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), lengths, window=window, softcap=softcap,
        scale=scale, interpret=_interp())
    return out.transpose(0, 2, 1, 3)


def decode_attention(q, k, v, lengths, *, window=None, softcap=None,
                     scale=None, policy=None, target=None):
    """q:(B,1,H,D) k,v:(B,S,Hkv,D) lengths:(B,) -> (B,1,H,D)."""
    return dispatch("decode_attention", q, k, v, lengths, window, softcap,
                    scale, policy=policy, target=target)


# ---------------------------------------------------------------------------
# ssd (Mamba2)
# ---------------------------------------------------------------------------

def _ssd_vector(x, dt, A, B, C, D=None, *, chunk=128):
    if x.shape[1] > 256:
        return ref.ssd_chunked(x, dt, A, B, C, D, chunk=chunk)
    return ref.ssd(x, dt, A, B, C, D)


register("ssd", "vector", cost=trace.traced_cost(_ssd_vector),
         doc="chunked jnp SSD (sequential scan below 256 steps)")(_ssd_vector)


@register("ssd", "pallas", cost=_ssd.cost, supports=_ssd.supports,
          doc="chunked SSD, MXU block decomposition, VMEM-carried state")
def _ssd_pallas(x, dt, A, B, C, D=None, *, chunk=128):
    return _ssd.ssd(x, dt, A, B, C, D, chunk=chunk, interpret=_interp())


def ssd(x, dt, A, B, C, D=None, *, chunk=128, policy=None, target=None):
    return dispatch("ssd", x, dt, A, B, C, D, policy=policy, target=target)
