"""Pure-jnp oracles for every kernel — the paper's "original SIMDe" tier.

Each function is the straightforward whole-array translation a generic
portability layer produces (vector-attribute / auto-vectorized semantics):
op-by-op, no fusion, fp32 math.  These serve two roles:

  1. correctness oracle for the Pallas kernels (tests assert allclose),
  2. the *baseline* side of the paper's Figure-2 comparison
     (``repro.kernels.figure2`` counts their dynamic instructions).

The ten functions are the ten XNNPACK microkernels evaluated in the paper
(§4.2): gemm, convhwc, dwconv, maxpool, argmaxpool, vrelu, vsqrt, vtanh,
vsigmoid, ibilinear — plus the beyond-paper LM hot-spots (flash attention,
Mamba2 SSD) used by the model zoo.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# 1. gemm — XNNPACK f32-gemm with minmax (bias + clamp) epilogue
# ---------------------------------------------------------------------------

def gemm(a, b, bias=None, clamp_min=-jnp.inf, clamp_max=jnp.inf):
    """C = clamp(A @ B + bias).  a:(M,K) b:(K,N) bias:(N,)."""
    out = jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32))
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    out = jnp.clip(out, clamp_min, clamp_max)
    return out.astype(a.dtype)


# ---------------------------------------------------------------------------
# 2. conv_hwc — direct conv, NHWC input, HWIO weights, VALID padding
# ---------------------------------------------------------------------------

def conv_hwc(x, w, bias=None, stride=(1, 1)):
    """x:(N,H,W,Ci) w:(Kh,Kw,Ci,Co) -> (N,Ho,Wo,Co)."""
    out = jax.lax.conv_general_dilated(
        x.astype(jnp.float32), w.astype(jnp.float32),
        window_strides=stride, padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# 3. dwconv — depthwise conv, per-channel kernels, VALID padding
# ---------------------------------------------------------------------------

def dwconv(x, w, bias=None, stride=(1, 1)):
    """x:(N,H,W,C) w:(Kh,Kw,C) -> (N,Ho,Wo,C)."""
    kh, kw, c = w.shape
    out = jax.lax.conv_general_dilated(
        x.astype(jnp.float32), w.astype(jnp.float32).reshape(kh, kw, 1, c),
        window_strides=stride, padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=c)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# 4/5. maxpool / argmaxpool
# ---------------------------------------------------------------------------

def maxpool(x, window=(2, 2), stride=None):
    """x:(N,H,W,C), VALID padding."""
    stride = stride or window
    return jax.lax.reduce_window(
        x, -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min,
        jax.lax.max,
        (1, window[0], window[1], 1), (1, stride[0], stride[1], 1), "VALID")


def argmaxpool(x, window=(2, 2), stride=None):
    """Returns (max, flat-window-index-of-max).  x:(N,H,W,C)."""
    stride = stride or window
    n, h, w, c = x.shape
    kh, kw = window
    oh = (h - kh) // stride[0] + 1
    ow = (w - kw) // stride[1] + 1
    # Gather each window position, argmax over the window axis.
    cols = []
    for i in range(kh):
        for j in range(kw):
            cols.append(x[:, i:i + stride[0] * oh:stride[0],
                          j:j + stride[1] * ow:stride[1], :])
    stack = jnp.stack(cols, axis=-1)          # (N,oh,ow,C,kh*kw)
    idx = jnp.argmax(stack, axis=-1)
    mx = jnp.max(stack, axis=-1)
    return mx, idx.astype(jnp.int32)


# ---------------------------------------------------------------------------
# 6-9. elementwise: vrelu (clamp), vsqrt, vtanh, vsigmoid
# ---------------------------------------------------------------------------

def vrelu(x, clamp_min=0.0, clamp_max=jnp.inf):
    """XNNPACK vrelu is a minmax clamp."""
    return jnp.clip(x, jnp.asarray(clamp_min, x.dtype),
                    jnp.asarray(clamp_max, x.dtype))


def vsqrt(x):
    return jnp.sqrt(x.astype(jnp.float32)).astype(x.dtype)


def vtanh(x):
    return jnp.tanh(x.astype(jnp.float32)).astype(x.dtype)


def vsigmoid(x):
    return jax.nn.sigmoid(x.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# 10. ibilinear — bilinear interpolation with precomputed corners+weights
# ---------------------------------------------------------------------------

def ibilinear(img, iy, ix, wy, wx):
    """XNNPACK-style ibilinear.

    img:(H,W,C); iy,ix:(P,) int32 top-left corner per output pixel;
    wy,wx:(P,) fractional weights.  Returns (P,C).
    """
    tl = img[iy, ix]
    tr = img[iy, ix + 1]
    bl = img[iy + 1, ix]
    br = img[iy + 1, ix + 1]
    wy = wy[:, None].astype(jnp.float32)
    wx = wx[:, None].astype(jnp.float32)
    top = tl.astype(jnp.float32) * (1 - wx) + tr.astype(jnp.float32) * wx
    bot = bl.astype(jnp.float32) * (1 - wx) + br.astype(jnp.float32) * wx
    return (top * (1 - wy) + bot * wy).astype(img.dtype)


# ---------------------------------------------------------------------------
# Beyond-paper LM hot-spots (oracles)
# ---------------------------------------------------------------------------

def attention(q, k, v, *, causal=True, window=None, softcap=None, scale=None,
              kv_len_valid=None):
    """Reference multi-head attention.

    q:(B,Sq,H,D) k,v:(B,Sk,Hkv,D) with H a multiple of Hkv (GQA).
    window: sliding-window size (None = full); softcap: gemma2 logit cap.
    kv_len_valid: mask out kv positions >= this (decode with static cache).
    """
    b, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    dv = v.shape[-1]                 # value head dim may differ (MLA)
    group = h // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    qf = q.astype(jnp.float32).reshape(b, sq, hkv, group, d)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kf) * scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    q_pos = jnp.arange(sq)[:, None]
    k_pos = jnp.arange(sk)[None, :]
    offset = sk - sq  # q position i corresponds to absolute pos offset+i
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= (q_pos + offset) >= k_pos
    if window is not None:
        mask &= (q_pos + offset) - k_pos < window
    if kv_len_valid is not None:
        mask &= k_pos < kv_len_valid
    logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, vf)
    return out.reshape(b, sq, h, dv).astype(q.dtype)


def attention_chunked(q, k, v, *, causal=True, window=None, softcap=None,
                      scale=None, q_chunk=512):
    """Online-softmax attention in pure jnp (lax.scan over q chunks).

    The XLA-native flash formulation: never materializes the (Sq, Sk)
    logits, so 32k-prefill cells fit.  This is the vector-tier lowering
    for long sequences (the customized Pallas kernel additionally keeps
    the running stats in VMEM).
    """
    b, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    dv = v.shape[-1]
    group = h // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    qc = min(q_chunk, sq)
    pad = (-sq) % qc
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else q
    nq = qp.shape[1] // qc
    qs = qp.reshape(b, nq, qc, h, d).transpose(1, 0, 2, 3, 4)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    offset = sk - sq
    k_pos = jnp.arange(sk)

    def chunk_fn(carry, inp):
        qi, ci = inp
        qf = qi.astype(jnp.float32).reshape(b, qc, hkv, group, d)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kf) * scale
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        q_pos = ci * qc + jnp.arange(qc) + offset
        mask = jnp.ones((qc, sk), bool)
        if causal:
            mask &= q_pos[:, None] >= k_pos[None, :]
        if window is not None:
            mask &= q_pos[:, None] - k_pos[None, :] < window
        logits = jnp.where(mask[None, None, None], logits, -1e30)
        m = jnp.max(logits, axis=-1, keepdims=True)
        p = jnp.where(mask[None, None, None], jnp.exp(logits - m), 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p / jnp.maximum(l, 1e-30), vf)
        return carry, o.reshape(b, qc, h, dv)

    _, outs = jax.lax.scan(chunk_fn, (), (qs, jnp.arange(nq)))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, nq * qc, h, dv)
    return out[:, :sq].astype(q.dtype)


def ssd(x, dt, A, B, C, D=None, *, chunk=64):
    """Mamba2 SSD (state-space duality) reference — sequential scan.

    x:(b,s,h,p) dt:(b,s,h) A:(h,) B,C:(b,s,g,n) with h % g == 0.
    Returns y:(b,s,h,p).  Discretization: dA = exp(dt*A), dB = dt*B.
    """
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    Bh = jnp.repeat(B, rep, axis=2).astype(jnp.float32)   # (b,s,h,n)
    Ch = jnp.repeat(C, rep, axis=2).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    dA = jnp.exp(dtf * A[None, None, :])                  # (b,s,h)

    def step(state, inp):
        xa, dta, dAa, Ba, Ca = inp            # (b,h,p),(b,h),(b,h),(b,h,n),(b,h,n)
        state = state * dAa[..., None, None] + \
            (dta[..., None] * xa)[..., None] * Ba[..., None, :]  # (b,h,p,n)
        y = jnp.einsum("bhpn,bhn->bhp", state, Ca)
        return state, y

    init = jnp.zeros((b, h, p, n), jnp.float32)
    seq = (jnp.moveaxis(xf, 1, 0), jnp.moveaxis(dtf, 1, 0),
           jnp.moveaxis(dA, 1, 0), jnp.moveaxis(Bh, 1, 0),
           jnp.moveaxis(Ch, 1, 0))
    _, ys = jax.lax.scan(step, init, seq)
    y = jnp.moveaxis(ys, 0, 1)                            # (b,s,h,p)
    if D is not None:
        y = y + D[None, None, :, None] * xf
    return y.astype(x.dtype)


def ssd_chunked(x, dt, A, B, C, D=None, *, chunk=128):
    """Chunked SSD in pure jnp (scan over chunks) — the XLA-native block
    decomposition; same math as kernels/ssd.py without the VMEM-resident
    state.  Matches :func:`ssd` to fp tolerance."""
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    L = min(chunk, s)
    pad = (-s) % L
    xf = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, pad), (0, 0), (0, 0)))
    dtf = jnp.pad(dt.astype(jnp.float32), ((0, 0), (0, pad), (0, 0)))
    Bh = jnp.pad(jnp.repeat(B, rep, axis=2).astype(jnp.float32),
                 ((0, 0), (0, pad), (0, 0), (0, 0)))
    Ch = jnp.pad(jnp.repeat(C, rep, axis=2).astype(jnp.float32),
                 ((0, 0), (0, pad), (0, 0), (0, 0)))
    nch = (s + pad) // L
    # (nch, b, h, L, ...) chunk-major layout for the scan
    xs = xf.reshape(b, nch, L, h, p).transpose(1, 0, 3, 2, 4)
    dts = dtf.reshape(b, nch, L, h).transpose(1, 0, 3, 2)
    Bs = Bh.reshape(b, nch, L, h, n).transpose(1, 0, 3, 2, 4)
    Cs = Ch.reshape(b, nch, L, h, n).transpose(1, 0, 3, 2, 4)
    causal = jnp.tril(jnp.ones((L, L), jnp.float32))

    def chunk_fn(state, inp):
        xc, dtc, Bc, Cc = inp                      # (b,h,L,*)
        la = jnp.cumsum(dtc * A[None, :, None], axis=-1)        # (b,h,L)
        y_inter = jnp.exp(la)[..., None] * jnp.einsum(
            "bhln,bhpn->bhlp", Cc, state)
        w = jnp.exp(la[..., :, None] - la[..., None, :]) * causal * \
            dtc[..., None, :]
        gmat = jnp.einsum("bhln,bhmn->bhlm", Cc, Bc)
        y = y_inter + jnp.einsum("bhlm,bhmp->bhlp", gmat * w, xc)
        wj = jnp.exp(la[..., -1:] - la) * dtc                   # (b,h,L)
        state = jnp.exp(la[..., -1])[..., None, None] * state + jnp.einsum(
            "bhlp,bhln->bhpn", xc * wj[..., None], Bc)
        return state, y

    init = jnp.zeros((b, h, p, n), jnp.float32)
    _, ys = jax.lax.scan(chunk_fn, init, (xs, dts, Bs, Cs))
    y = ys.transpose(1, 0, 3, 2, 4).reshape(b, nch * L, h, p)[:, :s]
    if D is not None:
        y = y + D[None, None, :, None] * x.astype(jnp.float32)
    return y.astype(x.dtype)


def softmax_xent(logits, labels):
    """Cross-entropy over the vocab axis, fp32 accumulation."""
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    return lse - ll
