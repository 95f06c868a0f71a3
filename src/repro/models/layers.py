"""Primitive layers (pure-functional, params as pytrees of jnp arrays).

All heavy compute routes through :mod:`repro.kernels.ops` so the paper's
lowering ladder applies framework-wide.  Norm/softmax/router math stays
fp32; weights/activations default to bf16 per the config.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops


def dtype_of(cfg):
    return jnp.dtype(cfg.dtype)


def dense_init(key, d_in, d_out, dtype, scale=None):
    scale = scale if scale is not None else d_in ** -0.5
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def linear(w, x):
    """x:(..., d_in) @ w:(d_in, d_out) — dispatched through the gemm op."""
    lead = x.shape[:-1]
    out = ops.gemm(x.reshape(-1, x.shape[-1]), w)
    return out.reshape(*lead, w.shape[-1])


def linear_rp(w, x, cfg):
    """Row-parallel linear with the TP reduction in bf16 (§Perf iter 6).

    GSPMD reduces partitioned-dot partials in the f32 accumulator dtype;
    Megatron-style training reduces activations in the compute dtype.
    This shard_map does the local dot with f32 accumulation, casts the
    partial to bf16, and psums bf16 over 'model' — halving TP all-reduce
    volume.  Falls back to :func:`linear` without an active mesh, when
    the contraction dim doesn't divide, or under FSDP (where the weight
    would be re-gathered at the shard_map boundary).
    """
    from . import sharding as Sh
    mesh = Sh.current_mesh()
    dt = dtype_of(cfg)
    if (mesh is None or "model" not in mesh.axis_names or cfg.fsdp
            or dt != jnp.bfloat16):
        return linear(w, x)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    ba = Sh.batch_axes(mesh)
    nb = 1
    for a in ba:
        nb *= sizes[a]
    lead = x.shape[:-1]
    xf = x.reshape(-1, x.shape[-1])
    if w.shape[0] % sizes["model"] or xf.shape[0] % nb:
        return linear(w, x)   # validity rule: shard_map needs exact tiles
    from jax.sharding import PartitionSpec as P

    def local(xl, wl):
        out = jnp.dot(xl.astype(dt), wl,
                      preferred_element_type=jnp.float32)
        return jax.lax.psum(out.astype(dt), "model")

    out = jax.shard_map(local, mesh=mesh,
                        in_specs=(P(ba, "model"), P("model", None)),
                        out_specs=P(ba, None),
                        check_vma=False)(xf, w)
    return out.reshape(*lead, w.shape[-1])


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def norm_init(d, kind):
    if kind == "layernorm":
        return {"w": jnp.ones((d,), jnp.float32), "b": jnp.zeros((d,), jnp.float32)}
    return {"w": jnp.ones((d,), jnp.float32)}


def norm_apply(params, x, kind="rmsnorm", eps=1e-6):
    xf = x.astype(jnp.float32)
    if kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps) * params["w"] + params["b"]
    else:
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(ms + eps) * params["w"]
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# activations (through the lowering ladder)
# ---------------------------------------------------------------------------

def act_apply(x, kind):
    if kind == "silu":
        return x * ops.vsigmoid(x)
    if kind == "gelu":
        # tanh-approx gelu built from the vtanh lowering
        c = np.sqrt(2.0 / np.pi).astype(np.float32)
        inner = (c * (x.astype(jnp.float32) + 0.044715 * x.astype(jnp.float32) ** 3)).astype(x.dtype)
        return (0.5 * x.astype(jnp.float32) *
                (1.0 + ops.vtanh(inner).astype(jnp.float32))).astype(x.dtype)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature factor (DeepSeek's ``yarn_get_mscale``)."""
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * float(np.log(factor)) + 1.0


def rope_frequencies(dim: int, theta: float, scaling=None) -> np.ndarray:
    """Inverse frequencies of the ``dim // 2`` rotated pairs, float32.

    With ``scaling`` (a :class:`~repro.configs.base.YarnScaling`) the
    frequencies blend interpolation (``/ factor``) and extrapolation
    with a linear ramp between the correction dims of ``beta_fast`` and
    ``beta_slow`` rotations over the original context, as DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding`` computes them."""
    half = dim // 2
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    if scaling is None:
        return extra.astype(np.float32)
    inter = extra / scaling.factor
    orig = scaling.original_max_position_embeddings

    def corr_dim(rot):
        return dim * np.log(orig / (rot * 2 * np.pi)) / (2 * np.log(theta))

    low = max(int(np.floor(corr_dim(scaling.beta_fast))), 0)
    high = min(int(np.ceil(corr_dim(scaling.beta_slow))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float32) - low) / (high - low),
                   0.0, 1.0)
    keep = 1.0 - ramp                   # 1 = extrapolate (high frequency)
    return (inter * (1.0 - keep) + extra * keep).astype(np.float32)


def rope_apply(x, positions, theta, scaling=None):
    """x:(B, S, H, D) rotated with half-split RoPE at ``positions``:(B, S).
    ``scaling`` selects YaRN frequencies and multiplies cos/sin by
    ``mscale / mscale_all_dim``'s ratio (1 where the two are equal, as in
    DeepSeek-V2)."""
    b, s, h, d = x.shape
    half = d // 2
    freqs = jnp.asarray(rope_frequencies(d, theta, scaling))
    ang = positions.astype(jnp.float32)[..., None] * freqs          # (B,S,half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    if scaling is not None:
        m = (yarn_mscale(scaling.factor, scaling.mscale) /
             yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(positions, d):
    """Whisper-style absolute sinusoidal embeddings.  positions:(B,S)->(B,S,d)."""
    half = d // 2
    freqs = jnp.exp(-np.log(10000.0) * jnp.arange(half, dtype=jnp.float32) /
                    max(1, half - 1))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# MLP (gated or plain)
# ---------------------------------------------------------------------------

def mlp_init(key, cfg, d_in=None, d_ff=None, d_out=None):
    d = d_in or cfg.d_model
    f = d_ff or cfg.d_ff
    o = d_out or cfg.d_model
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 3)
    p = {"wu": dense_init(ks[1], d, f, dt), "wd": dense_init(ks[2], f, o, dt)}
    if cfg.gated_mlp:
        p["wg"] = dense_init(ks[0], d, f, dt)
    return p


def mlp_apply(params, x, cfg):
    up = linear(params["wu"], x)
    if cfg.gated_mlp:
        gate = act_apply(linear(params["wg"], x), cfg.act)
        h = gate * up
    else:
        h = act_apply(up, cfg.act)
    return linear_rp(params["wd"], h, cfg)


# ---------------------------------------------------------------------------
# embeddings / lm head
# ---------------------------------------------------------------------------

def padded_vocab(cfg) -> int:
    """Megatron-style vocab padding so TP always divides the vocab dim."""
    return -(-cfg.vocab_size // 256) * 256


def embed_init(key, cfg):
    dt = dtype_of(cfg)
    vp = padded_vocab(cfg)
    p = {"emb": (jax.random.normal(key, (vp, cfg.d_model),
                                   jnp.float32) * 0.02).astype(dt)}
    if not cfg.tie_embeddings:
        p["head"] = dense_init(jax.random.fold_in(key, 1), cfg.d_model, vp, dt)
    return p


def embed_apply(params, tokens, cfg):
    x = params["emb"][tokens]
    if cfg.scale_embeddings:
        x = (x.astype(jnp.float32) * np.sqrt(cfg.d_model)).astype(x.dtype)
    return x


def head_apply(params, x, cfg):
    logits = linear(params["head"], x) if not cfg.tie_embeddings else \
        jnp.einsum("bsd,vd->bsv", x, params["emb"]).astype(x.dtype)
    if cfg.final_softcap is not None:
        lf = logits.astype(jnp.float32) / cfg.final_softcap
        logits = (cfg.final_softcap *
                  ops.vtanh(lf).astype(jnp.float32)).astype(x.dtype)
    vp = padded_vocab(cfg)
    if vp != cfg.vocab_size:  # mask padded vocab rows out of the softmax
        pad_mask = jnp.arange(vp) >= cfg.vocab_size
        logits = jnp.where(pad_mask, jnp.asarray(-1e30, logits.dtype), logits)
    return logits
