"""Attention blocks: GQA (+ sliding window / softcap / qk-norm), MLA,
cross-attention, with train/prefill/decode cache handling.

Cache layouts (static shapes; ``lengths`` tracks the valid prefix):
  gqa global : k, v (B, S_max, Hkv, hd)
  gqa local  : ring buffer of ``window`` slots (slot = pos % window);
               softmax is permutation-invariant over kv so slot order is
               irrelevant once keys carry RoPE.
  mla        : kv (B, S_max, W), one row a position: c_kv (kv_lora),
               k_rope (rope_dim), zeros up to W, a whole number of lane
               tiles — decode uses the *absorbed* form (q into W_uk, out
               through W_uv) so the compressed cache is attended directly.

Inside the model's scan over layers (``layer`` given) a cache arrives as
the stack of every scanned layer's, (L, B, ...): a step writes only its
new entries into the layer's slice, in place, and reads the slice where
it lies (:func:`cache_write`, :func:`cache_view`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.vtypes import round_up
from repro.kernels import ops
from . import layers as L


def cache_view(buf, layer):
    """This layer's entries of ``buf``: the buffer itself, or (``layer``
    given) its slice of the stack of every scanned layer's."""
    if layer is None:
        return buf
    return jax.lax.dynamic_index_in_dim(buf, layer, keepdims=False)


def cache_write(buf, layer, value, index=None):
    """``buf`` with ``value`` written into this layer's entries (its slice
    ``layer`` of a stack) and nothing else moved, so that the update
    aliases the buffer: at ``index``, per-row integer arrays over the
    leading axes (an index past the end writes nothing), or else from the
    start of every axis."""
    if index is not None:
        index = index if layer is None else (layer,) + index
        return buf.at[index].set(value, mode="drop")
    start = (0,) * value.ndim
    if layer is not None:
        value, start = value[None], (layer,) + start
    return jax.lax.dynamic_update_slice(buf, value.astype(buf.dtype), start)


def _entry_index(slot, valid, slots):
    """Each row's entry at ``slot``:(B,), or past the end (no write) for a
    row that ``valid``:(B, 1) marks inert."""
    if valid is not None:
        slot = jnp.where(valid[:, 0], slot, slots)
    return jnp.arange(slot.shape[0]), slot


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

def gqa_init(key, cfg, d_in=None):
    d = d_in or cfg.d_model
    dt = L.dtype_of(cfg)
    hd, h, hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    p = {
        "wq": L.dense_init(ks[0], d, h * hd, dt),
        "wk": L.dense_init(ks[1], d, hkv * hd, dt),
        "wv": L.dense_init(ks[2], d, hkv * hd, dt),
        "wo": L.dense_init(ks[3], h * hd, cfg.d_model, dt),
    }
    if cfg.qk_norm:
        p["qn"] = L.norm_init(hd, "rmsnorm")
        p["kn"] = L.norm_init(hd, "rmsnorm")
    return p


def gqa_cache_init(cfg, batch, s_max, window=None, dtype=None):
    dt = dtype or L.dtype_of(cfg)
    slots = min(window, s_max) if window else s_max
    shape = (batch, slots, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def gqa_apply(params, x, cfg, *, positions, mode, cache=None, lengths=None,
              window=None, memory=None, causal=True, target=None,
              layer=None, valid=None):
    """x:(B,S,d).  mode in train|prefill|decode.  memory: cross-attn kv.
    ``target`` pins the attention lowering selection to an explicit
    machine model (per-request multi-backend serving).  ``layer``: the
    cache is the stack of the scanned layers' and this is this layer's
    index in it.  ``valid``:(B, 1) at decode marks the live rows; an
    inert row writes no entry."""
    b, s, _ = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = L.linear(params["wq"], x).reshape(b, s, h, hd)
    if memory is None:
        k = L.linear(params["wk"], x).reshape(b, s, hkv, hd)
        v = L.linear(params["wv"], x).reshape(b, s, hkv, hd)
    else:  # cross attention: kv from encoder memory (cached at prefill)
        k, v = memory
    if cfg.qk_norm:
        q = L.norm_apply(params["qn"], q)
        if memory is None:
            k = L.norm_apply(params["kn"], k)
    if cfg.rope_theta and memory is None:
        q = L.rope_apply(q, positions, cfg.rope_theta)
        k = L.rope_apply(k, positions, cfg.rope_theta)

    if memory is not None:
        out = ops.attention(q, k, v, causal=False, softcap=cfg.softcap,
                            target=target)
        return L.linear_rp(params["wo"], out.reshape(b, s, h * hd), cfg), cache

    if mode == "train":
        out = ops.attention(q, k, v, causal=causal, window=window,
                            softcap=cfg.softcap, target=target)
        return L.linear_rp(params["wo"], out.reshape(b, s, h * hd), cfg), cache

    if mode == "prefill":
        slots = cache["k"].shape[-3]
        if window and slots < s:  # ring: keep the last ``window`` positions
            # write positions p in [s-slots, s) at slot p % slots
            ppos = jnp.arange(s - slots, s)
            idx = (jnp.arange(b)[:, None], (ppos % slots)[None])
            kw, vw = k[:, s - slots:], v[:, s - slots:]
        else:
            idx, kw, vw = None, k, v
        cache = {"k": cache_write(cache["k"], layer, kw, idx),
                 "v": cache_write(cache["v"], layer, vw, idx)}
        out = ops.attention(q, k, v, causal=True, window=window,
                            softcap=cfg.softcap, target=target)
        return L.linear_rp(params["wo"], out.reshape(b, s, h * hd), cfg), cache

    # decode: s == 1, write at pos = lengths (per row), attend valid prefix
    slots = cache["k"].shape[-3]
    slot = (lengths % slots) if window else lengths
    idx = _entry_index(slot, valid, slots)
    cache = {"k": cache_write(cache["k"], layer, k[:, 0], idx),
             "v": cache_write(cache["v"], layer, v[:, 0], idx)}
    out = ops.decode_attention(q, cache_view(cache["k"], layer),
                               cache_view(cache["v"], layer),
                               jnp.minimum(lengths + 1, slots),
                               softcap=cfg.softcap, target=target)
    return L.linear_rp(params["wo"], out.reshape(b, s, h * hd), cfg), cache


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention)
# ---------------------------------------------------------------------------

def mla_init(key, cfg, d_in=None):
    d = d_in or cfg.d_model
    dt = L.dtype_of(cfg)
    h = cfg.n_heads
    r, nd, vd = cfg.qk_rope_dim, cfg.qk_nope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 8)
    p = {
        "w_dkv": L.dense_init(ks[0], d, cfg.kv_lora_rank + r, dt),
        "kv_norm": L.norm_init(cfg.kv_lora_rank, "rmsnorm"),
        "w_uk": L.dense_init(ks[1], cfg.kv_lora_rank, h * nd, dt),
        "w_uv": L.dense_init(ks[2], cfg.kv_lora_rank, h * vd, dt),
        "wo": L.dense_init(ks[3], h * vd, cfg.d_model, dt),
    }
    if cfg.q_lora_rank:
        p["w_dq"] = L.dense_init(ks[4], d, cfg.q_lora_rank, dt)
        p["q_norm"] = L.norm_init(cfg.q_lora_rank, "rmsnorm")
        p["w_uq"] = L.dense_init(ks[5], cfg.q_lora_rank, h * (nd + r), dt)
    else:
        p["wq"] = L.dense_init(ks[6], d, h * (nd + r), dt)
    return p


# lanes of a TPU vreg tile.  The latent cache row is a whole number of
# them: XLA:TPU lays a narrower cache (a 64-wide rope key on its own)
# out position-minor, where one position's write wants it lane-minor, and
# then relays it out around every step.
LANES = 128


def mla_cache_init(cfg, batch, s_max, dtype=None):
    dt = dtype or L.dtype_of(cfg)
    width = round_up(cfg.kv_lora_rank + cfg.qk_rope_dim, LANES)
    return {"kv": jnp.zeros((batch, s_max, width), dt)}


def _mla_row(c_kv, k_rope, width):
    """(..., width): ``c_kv``, then ``k_rope``, then zeros — a latent
    cache row, or a query laid out against one."""
    pad = width - c_kv.shape[-1] - k_rope.shape[-1]
    return jnp.concatenate(
        [c_kv, k_rope, jnp.zeros(c_kv.shape[:-1] + (pad,), c_kv.dtype)], -1)


def mla_softmax_scale(cfg) -> float:
    """``(qk_nope + qk_rope) ** -0.5``, times ``yarn_mscale(factor,
    mscale_all_dim) ** 2`` under YaRN scaling with ``mscale_all_dim`` set
    (DeepSeek-V2's ``DeepseekV2Attention.softmax_scale``)."""
    scale = float(cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    ys = cfg.rope_scaling
    if ys is not None and ys.mscale_all_dim:
        scale *= L.yarn_mscale(ys.factor, ys.mscale_all_dim) ** 2
    return scale


def _rope(cfg, x, positions):
    return L.rope_apply(x, positions, cfg.rope_theta, cfg.rope_scaling)


def _mla_q(params, x, cfg, positions):
    b, s, _ = x.shape
    h = cfg.n_heads
    r, nd = cfg.qk_rope_dim, cfg.qk_nope_dim
    if cfg.q_lora_rank:
        cq = L.norm_apply(params["q_norm"], L.linear(params["w_dq"], x))
        q = L.linear(params["w_uq"], cq)
    else:
        q = L.linear(params["wq"], x)
    q = q.reshape(b, s, h, nd + r)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    q_rope = _rope(cfg, q_rope, positions)
    return q_nope, q_rope


def _mla_ckv(params, x, cfg, positions):
    b, s, _ = x.shape
    r = cfg.qk_rope_dim
    dkv = L.linear(params["w_dkv"], x)
    c_kv = L.norm_apply(params["kv_norm"], dkv[..., :cfg.kv_lora_rank])
    k_rope = _rope(cfg, dkv[..., cfg.kv_lora_rank:][:, :, None, :],
                   positions)[:, :, 0]
    return c_kv, k_rope


def mla_apply(params, x, cfg, *, positions, mode, cache=None, lengths=None,
              target=None, layer=None, valid=None, **_):
    with jax.named_scope("mla"):
        return _mla_apply(params, x, cfg, positions=positions, mode=mode,
                          cache=cache, lengths=lengths, target=target,
                          layer=layer, valid=valid)


def _mla_apply(params, x, cfg, *, positions, mode, cache, lengths, target,
               layer, valid):
    b, s, _ = x.shape
    h = cfg.n_heads
    r, nd, vd = cfg.qk_rope_dim, cfg.qk_nope_dim, cfg.v_head_dim
    scale = mla_softmax_scale(cfg)
    q_nope, q_rope = _mla_q(params, x, cfg, positions)

    if mode in ("train", "prefill"):
        c_kv, k_rope = _mla_ckv(params, x, cfg, positions)
        k_nope = L.linear(params["w_uk"], c_kv).reshape(b, s, h, nd)
        v = L.linear(params["w_uv"], c_kv).reshape(b, s, h, vd)
        q = jnp.concatenate([q_nope, q_rope], -1)
        k = jnp.concatenate([k_nope,
                             jnp.broadcast_to(k_rope[:, :, None, :],
                                              (b, s, h, r))], -1)
        out = ops.attention(q, k, v, causal=True, scale=scale,
                            target=target)
        if mode == "prefill":
            row = _mla_row(c_kv, k_rope, cache["kv"].shape[-1])
            cache = {"kv": cache_write(cache["kv"], layer, row)}
        return L.linear_rp(params["wo"], out.reshape(b, s, h * vd), cfg), cache

    # decode: absorbed attention over the compressed cache; both products
    # read whole cache rows (a slice of one would be copied)
    slots, width = cache["kv"].shape[-2:]
    c_kv_new, k_rope_new = _mla_ckv(params, x, cfg, positions)
    cache = {"kv": cache_write(cache["kv"], layer,
                               _mla_row(c_kv_new, k_rope_new, width)[:, 0],
                               _entry_index(lengths, valid, slots))}
    kv = cache_view(cache["kv"], layer).astype(jnp.float32)
    w_uk = params["w_uk"].reshape(cfg.kv_lora_rank, h, nd)
    # absorb: q_eff[h] = q_nope[h] @ W_uk[:, h, :].T  -> kv_lora dims
    q_eff = jnp.einsum("bqhn,rhn->bqhr", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    q_row = _mla_row(q_eff, q_rope.astype(jnp.float32), width)
    logits = jnp.einsum("bqhr,bkr->bhqk", q_row, kv) * scale
    kpos = jnp.arange(kv.shape[1])[None, None, None, :]
    logits = jnp.where(kpos <= lengths[:, None, None, None], logits, -1e30)
    p_attn = jax.nn.softmax(logits, axis=-1)
    ctx = jnp.einsum("bhqk,bkr->bqhr", p_attn, kv)[..., :cfg.kv_lora_rank]
    w_uv = params["w_uv"].reshape(cfg.kv_lora_rank, h, vd)
    out = jnp.einsum("bqhr,rhv->bqhv", ctx, w_uv.astype(jnp.float32))
    out = out.astype(x.dtype).reshape(b, s, h * vd)
    return L.linear_rp(params["wo"], out, cfg), cache
