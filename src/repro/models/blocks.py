"""Layer blocks: one (init, cache_init, apply) triple per layer kind.

Kinds: attn | local | moe | moe_dense | mamba | mamba_shared | enc | dec.
Blocks are pure functions over (params, x, ctx) where ctx carries mode,
positions, lengths, encoder memory and the zamba shared-block closure.

Inside the model's scan over layers a block's cache is split
(:func:`split_indexed`): the caches indexed by position (attention keys
and values, the latent cache, cross-attention memory) stay stacked over
the scanned layers and the block writes its entries into its slice
(``ctx.layer``) in place; a recurrent state is handed over per layer and
rewritten whole.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from . import attention as A
from . import layers as L
from . import moe as M
from . import ssm as S


@dataclasses.dataclass
class Ctx:
    cfg: Any
    mode: str                      # train | prefill | decode
    positions: jnp.ndarray         # (B, S)
    lengths: Optional[jnp.ndarray] = None   # (B,) decode valid lengths
    memory: Any = None             # encoder (k, v) memory for cross attn
    emb0: Any = None               # zamba2: initial embedding stream
    shared: Any = None             # zamba2: shared block params
    target: Any = None             # explicit lowering target (per-request
                                   # multi-backend serving); None = ambient
    valid: Optional[jnp.ndarray] = None     # (B, S) live tokens; None = all
    dropless: bool = False         # MoE keeps every assignment (serving)
    layer: Optional[jnp.ndarray] = None     # this layer in stacked caches


def no_aux(cfg):
    """A block's auxiliary outputs, summed over layers by the model: the
    MoE load-balance loss, live assignments per expert, assignments
    dropped and experts given any (zeros for blocks without experts), and
    whether a decode step wrote the block's position-indexed cache."""
    return {"lb": jnp.zeros((), jnp.float32),
            "expert_tokens": jnp.zeros((cfg.n_experts,), jnp.int32),
            "dropped": jnp.zeros((), jnp.int32),
            "experts_touched": jnp.zeros((), jnp.int32),
            "cache_writes": jnp.zeros((), jnp.int32)}


def _attn_impl(cfg):
    return (A.mla_init, A.mla_apply, A.mla_cache_init) \
        if cfg.attn_kind == "mla" else \
        (A.gqa_init, A.gqa_apply,
         lambda cfg, b, s, window=None: A.gqa_cache_init(cfg, b, s, window))


# ---------------------------------------------------------------------------
# transformer block (attn/local x dense/moe ffn)
# ---------------------------------------------------------------------------

def _tblock_init(key, cfg, *, ffn: str, d_ff=None):
    ks = jax.random.split(key, 2)
    init, _, _ = _attn_impl(cfg)
    p = {
        "ln1": L.norm_init(cfg.d_model, cfg.norm),
        "attn": init(ks[0], cfg),
        "ln2": L.norm_init(cfg.d_model, cfg.norm),
    }
    if cfg.sandwich_norm:
        p["ln1p"] = L.norm_init(cfg.d_model, cfg.norm)
        p["ln2p"] = L.norm_init(cfg.d_model, cfg.norm)
    if ffn == "moe":
        p["ffn"] = M.moe_init(ks[1], cfg)
    else:
        p["ffn"] = L.mlp_init(ks[1], cfg, d_ff=d_ff or cfg.d_ff)
    return p


def _tblock_cache(cfg, batch, s_max, *, window=None):
    if cfg.attn_kind == "mla":
        return A.mla_cache_init(cfg, batch, s_max)
    return A.gqa_cache_init(cfg, batch, s_max, window)


def _tblock_apply(params, x, cache, ctx: Ctx, *, ffn: str, window=None):
    cfg = ctx.cfg
    _, apply, _ = _attn_impl(cfg)
    h = L.norm_apply(params["ln1"], x, cfg.norm)
    h, cache = apply(params["attn"], h, cfg, positions=ctx.positions,
                     mode=ctx.mode, cache=cache, lengths=ctx.lengths,
                     window=window, target=ctx.target, layer=ctx.layer,
                     valid=ctx.valid)
    if cfg.sandwich_norm:
        h = L.norm_apply(params["ln1p"], h, cfg.norm)
    x = x + h
    h = L.norm_apply(params["ln2"], x, cfg.norm)
    aux = no_aux(cfg)
    if ffn == "moe":
        h, lb, st = M.moe_apply(params["ffn"], h, cfg,
                                dropless=ctx.dropless, valid=ctx.valid,
                                stats=True)
        aux = {**aux, "lb": lb, **st}
    else:
        with jax.named_scope("dense_mlp"):
            h = L.mlp_apply(params["ffn"], h, cfg)
    if cfg.sandwich_norm:
        h = L.norm_apply(params["ln2p"], h, cfg.norm)
    return x + h, cache, aux


# ---------------------------------------------------------------------------
# mamba (+ shared attention) blocks
# ---------------------------------------------------------------------------

def _mamba_init(key, cfg):
    return {"ln": L.norm_init(cfg.d_model, cfg.norm),
            "mamba": S.mamba_init(key, cfg)}


def _mamba_apply(params, x, cache, ctx: Ctx):
    h = L.norm_apply(params["ln"], x, ctx.cfg.norm)
    h, cache = S.mamba_apply(params["mamba"], h, ctx.cfg, mode=ctx.mode,
                             cache=cache, target=ctx.target)
    return x + h, cache, no_aux(ctx.cfg)


def shared_block_init(key, cfg):
    """zamba2 shared attention+MLP block over concat width 2d."""
    d2 = 2 * cfg.d_model
    ks = jax.random.split(key, 3)
    return {
        "ln1": L.norm_init(d2, cfg.norm),
        "attn": A.gqa_init(ks[0], cfg, d_in=d2),
        "ln2": L.norm_init(d2, cfg.norm),
        "mlp": L.mlp_init(ks[1], cfg, d_in=d2, d_ff=cfg.d_ff,
                          d_out=cfg.d_model),
    }


def _shared_apply(shared, x, cache, ctx: Ctx):
    cfg = ctx.cfg
    cat = jnp.concatenate([x, ctx.emb0], axis=-1)
    h = L.norm_apply(shared["ln1"], cat, cfg.norm)
    h, cache = A.gqa_apply(shared["attn"], h, cfg, positions=ctx.positions,
                           mode=ctx.mode, cache=cache, lengths=ctx.lengths,
                           target=ctx.target, layer=ctx.layer,
                           valid=ctx.valid)
    x = x + h
    m = L.mlp_apply(shared["mlp"],
                    L.norm_apply(shared["ln2"], cat, cfg.norm), cfg)
    return x + m, cache


def _mamba_shared_apply(params, x, cache, ctx: Ctx):
    mc = None if cache is None else cache["mamba"]
    ac = None if cache is None else cache["attn"]
    x, mcache, aux = _mamba_apply(params, x, mc, ctx)
    x, acache = _shared_apply(ctx.shared, x, ac, ctx)
    if cache is None:
        return x, None, aux
    return x, {"mamba": mcache, "attn": acache}, aux


# ---------------------------------------------------------------------------
# whisper encoder / decoder blocks
# ---------------------------------------------------------------------------

def _enc_init(key, cfg):
    ks = jax.random.split(key, 2)
    return {"ln1": L.norm_init(cfg.d_model, cfg.norm),
            "attn": A.gqa_init(ks[0], cfg),
            "ln2": L.norm_init(cfg.d_model, cfg.norm),
            "mlp": L.mlp_init(ks[1], cfg)}


def _enc_apply(params, x, cache, ctx: Ctx):
    cfg = ctx.cfg
    h = L.norm_apply(params["ln1"], x, cfg.norm)
    h, _ = A.gqa_apply(params["attn"], h, cfg, positions=ctx.positions,
                       mode="train", causal=False, target=ctx.target)
    x = x + h
    h = L.norm_apply(params["ln2"], x, cfg.norm)
    return x + L.mlp_apply(params["mlp"], h, cfg), cache, no_aux(cfg)


def _dec_init(key, cfg):
    ks = jax.random.split(key, 3)
    return {"ln1": L.norm_init(cfg.d_model, cfg.norm),
            "attn": A.gqa_init(ks[0], cfg),
            "lnx": L.norm_init(cfg.d_model, cfg.norm),
            "xattn": A.gqa_init(ks[1], cfg),
            "ln2": L.norm_init(cfg.d_model, cfg.norm),
            "mlp": L.mlp_init(ks[2], cfg)}


def _dec_cache(cfg, batch, s_max):
    return {"self": A.gqa_cache_init(cfg, batch, s_max),
            "xk": jnp.zeros((batch, cfg.n_frames, cfg.n_kv_heads,
                             cfg.head_dim), L.dtype_of(cfg)),
            "xv": jnp.zeros((batch, cfg.n_frames, cfg.n_kv_heads,
                             cfg.head_dim), L.dtype_of(cfg))}


def _dec_apply(params, x, cache, ctx: Ctx):
    cfg = ctx.cfg
    b, s, _ = x.shape
    h = L.norm_apply(params["ln1"], x, cfg.norm)
    h, self_cache = A.gqa_apply(params["attn"], h, cfg,
                                positions=ctx.positions, mode=ctx.mode,
                                cache=None if cache is None else cache["self"],
                                lengths=ctx.lengths, target=ctx.target,
                                layer=ctx.layer, valid=ctx.valid)
    x = x + h
    # cross attention over encoder memory (cached at prefill, read at decode)
    h = L.norm_apply(params["lnx"], x, cfg.norm)
    if ctx.mode == "decode":
        xk, xv = (A.cache_view(cache[n], ctx.layer) for n in ("xk", "xv"))
    else:
        mem = ctx.memory  # (B, F, d) encoder output
        f = mem.shape[1]
        xk = L.linear(params["xattn"]["wk"], mem).reshape(
            b, f, cfg.n_kv_heads, cfg.head_dim)
        xv = L.linear(params["xattn"]["wv"], mem).reshape(
            b, f, cfg.n_kv_heads, cfg.head_dim)
    h, _ = A.gqa_apply(params["xattn"], h, cfg, positions=ctx.positions,
                       mode="train", memory=(xk, xv), target=ctx.target)
    x = x + h
    h = L.norm_apply(params["ln2"], x, cfg.norm)
    x = x + L.mlp_apply(params["mlp"], h, cfg)
    if cache is not None:
        cache = {**cache, "self": self_cache}
        if ctx.mode != "decode":
            cache.update((n, A.cache_write(cache[n], ctx.layer, m))
                         for n, m in (("xk", xk), ("xv", xv)))
    return x, cache, no_aux(cfg)


# ---------------------------------------------------------------------------
# kind registry
# ---------------------------------------------------------------------------

def block_init(kind, key, cfg):
    if kind in ("attn", "local"):
        return _tblock_init(key, cfg, ffn="dense")
    if kind == "moe":
        return _tblock_init(key, cfg, ffn="moe")
    if kind == "moe_dense":
        return _tblock_init(key, cfg, ffn="dense",
                            d_ff=cfg.d_ff_dense or cfg.d_ff)
    if kind == "mamba" or kind == "mamba_shared":
        return _mamba_init(key, cfg)
    if kind == "enc":
        return _enc_init(key, cfg)
    if kind == "dec":
        return _dec_init(key, cfg)
    raise ValueError(kind)


def block_cache_init(kind, cfg, batch, s_max):
    if kind == "local":
        return _tblock_cache(cfg, batch, s_max, window=cfg.window)
    if kind in ("attn", "moe", "moe_dense"):
        return _tblock_cache(cfg, batch, s_max)
    if kind == "mamba":
        return S.mamba_cache_init(cfg, batch)
    if kind == "mamba_shared":
        return {"mamba": S.mamba_cache_init(cfg, batch),
                "attn": A.gqa_cache_init(cfg, batch, s_max)}
    if kind == "dec":
        return _dec_cache(cfg, batch, s_max)
    if kind == "enc":
        return None
    raise ValueError(kind)


def split_indexed(kind, cache):
    """(indexed, rest) of a ``kind`` block's cache: the part indexed by
    position, which a step writes entries of in place, and the recurrent
    state, which it rewrites whole (None where there is none)."""
    if cache is None or kind == "mamba":
        return None, cache
    if kind == "mamba_shared":
        return cache["attn"], cache["mamba"]
    return cache, None


def join_indexed(kind, indexed, rest):
    """The cache that :func:`split_indexed` split."""
    if kind == "mamba":
        return rest
    if kind == "mamba_shared":
        return None if rest is None else {"mamba": rest, "attn": indexed}
    return indexed


def block_apply(kind, params, x, cache, ctx: Ctx):
    """(x, cache, aux) of a ``kind`` block; ``aux["cache_writes"]`` is 1
    where a decode step wrote entries of a position-indexed cache."""
    x, cache, aux = _block_apply(kind, params, x, cache, ctx)
    if ctx.mode == "decode" and split_indexed(kind, cache)[0] is not None:
        aux = {**aux, "cache_writes": jnp.ones((), jnp.int32)}
    return x, cache, aux


def _block_apply(kind, params, x, cache, ctx: Ctx):
    if kind == "attn":
        return _tblock_apply(params, x, cache, ctx, ffn="dense")
    if kind == "local":
        return _tblock_apply(params, x, cache, ctx, ffn="dense",
                             window=ctx.cfg.window)
    if kind == "moe":
        return _tblock_apply(params, x, cache, ctx, ffn="moe")
    if kind == "moe_dense":
        return _tblock_apply(params, x, cache, ctx, ffn="dense")
    if kind == "mamba":
        return _mamba_apply(params, x, cache, ctx)
    if kind == "mamba_shared":
        return _mamba_shared_apply(params, x, cache, ctx)
    if kind == "enc":
        return _enc_apply(params, x, cache, ctx)
    if kind == "dec":
        return _dec_apply(params, x, cache, ctx)
    raise ValueError(kind)
