"""Model assembly: pattern-scan transformer/SSM/hybrid/enc-dec LMs.

Layers are grouped by the config's periodic pattern into
(prefix, unit x repeats, remainder); the repeated unit is stacked and
executed under ``lax.scan`` (+ per-unit ``jax.checkpoint``), keeping HLO
size O(1) in depth — required for 512-device dry-run compiles and the
remat policy attachment point.

API (pure functions):
  init(cfg, key)                                -> params
  init_cache(cfg, batch, s_max)                 -> cache
  forward(params, cfg, batch, mode, ...)        -> (logits, cache, aux)
  forward(..., with_stats=True)                 -> (logits, cache, aux, stats)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from . import blocks as B
from . import layers as L
from . import moe as MoE
from . import sharding as Sh


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def init(cfg, key) -> Dict[str, Any]:
    prefix, unit, reps, rem = cfg.pattern_unit()
    keys = iter(jax.random.split(key, 8 + len(prefix) + len(unit) * reps +
                                 len(rem) + cfg.n_enc_layers))
    params: Dict[str, Any] = {"embed": L.embed_init(next(keys), cfg)}
    params["final_norm"] = L.norm_init(cfg.d_model, cfg.norm)
    params["prefix"] = [B.block_init(k, next(keys), cfg) for k in prefix]
    params["unit"] = [
        _stack([B.block_init(kind, next(keys), cfg) for _ in range(reps)])
        for kind in unit] if reps else []
    params["rem"] = [B.block_init(k, next(keys), cfg) for k in rem]
    if cfg.shared_attn_every:
        params["shared"] = B.shared_block_init(next(keys), cfg)
    if cfg.family == "encdec":
        params["enc"] = _stack([B.block_init("enc", next(keys), cfg)
                                for _ in range(cfg.n_enc_layers)])
        params["enc_norm"] = L.norm_init(cfg.d_model, cfg.norm)
    return params


def init_cache(cfg, batch: int, s_max: int):
    prefix, unit, reps, rem = cfg.pattern_unit()
    cache = {
        "prefix": [B.block_cache_init(k, cfg, batch, s_max) for k in prefix],
        "unit": [
            _stack([B.block_cache_init(kind, cfg, batch, s_max)
                    for _ in range(reps)])
            for kind in unit] if reps else [],
        "rem": [B.block_cache_init(k, cfg, batch, s_max) for k in rem],
    }
    return cache


def _embed_inputs(params, cfg, batch, mode, lengths):
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = L.embed_apply(params["embed"], tokens, cfg)
    if cfg.family == "vlm" and mode != "decode":
        patches = batch["patches"].astype(x.dtype)        # (B, P, d) stub
        x = jnp.concatenate([patches, x], axis=1)
    if mode == "decode":
        positions = lengths[:, None]
    else:
        positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
    if cfg.name.startswith("whisper"):
        pos_emb = L.sinusoidal_positions(positions, cfg.d_model)
        x = (x.astype(jnp.float32) + pos_emb).astype(x.dtype)
    return x, positions


def _encode(params, cfg, frames, target=None):
    """Whisper encoder over stub frame embeddings (B, F, d)."""
    x = frames.astype(L.dtype_of(cfg))
    pos = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
    x = (x.astype(jnp.float32) +
         L.sinusoidal_positions(pos, cfg.d_model)).astype(x.dtype)
    ctx = B.Ctx(cfg=cfg, mode="train", positions=pos, target=target)

    def body(carry, p):
        y, _, _ = B.block_apply("enc", p, carry, None, ctx)
        return y, None

    body = jax.checkpoint(body) if cfg.remat else body
    x, _ = jax.lax.scan(body, x, params["enc"])
    return L.norm_apply(params["enc_norm"], x, cfg.norm)


def forward(params, cfg, batch, *, mode: str, cache=None,
            lengths: Optional[jnp.ndarray] = None, sp_spec=None,
            target=None, valid: Optional[jnp.ndarray] = None,
            logits_at: Optional[jnp.ndarray] = None,
            with_stats: bool = False, dropless: Optional[bool] = None):
    """Returns (logits, new_cache, aux_loss), and with ``with_stats`` a
    fourth value: ``{"expert_tokens": (E,), "dropped": (),
    "experts_touched": (), "cache_writes": ()}``, the MoE layers' live
    assignments per expert, assignments dropped and experts given any,
    summed over layers, and the layers whose position-indexed cache a
    decode step wrote its new entries into in place.

    ``target`` pins every attention/ssd lowering selection in this
    forward to an explicit machine model, so a multi-backend server can
    mix targets per request instead of relying on the ambient
    thread-scoped target.  ``valid``:(B, S) marks the live tokens (None =
    all); it only selects what the stats count.  ``logits_at``:(B,) asks
    for each row's logits at that token position alone: (B, 1, V).
    ``dropless`` (None = every mode but 'train') keeps every MoE
    assignment; otherwise the capacity dispatch drops past capacity.
    """
    if dropless is None:
        dropless = mode != "train"
    prefix, unit, reps, rem = cfg.pattern_unit()
    x, positions = _embed_inputs(params, cfg, batch, mode, lengths)
    memory = None
    if cfg.family == "encdec" and mode != "decode":
        memory = _encode(params, cfg, batch["frames"], target=target)
    ctx = B.Ctx(cfg=cfg, mode=mode, positions=positions, lengths=lengths,
                memory=memory, emb0=x if cfg.shared_attn_every else None,
                shared=params.get("shared"), target=target, valid=valid,
                dropless=dropless)
    aux = B.no_aux(cfg)
    new_cache = {"prefix": [], "unit": [], "rem": []}

    def constrain(h):
        if sp_spec is not None:
            h = jax.lax.with_sharding_constraint(h, sp_spec)
        return h

    for i, kind in enumerate(prefix):
        c = None if cache is None else cache["prefix"][i]
        x, c, a = B.block_apply(kind, params["prefix"][i], x, c, ctx)
        new_cache["prefix"].append(c)
        aux = _add(aux, a)

    if reps:
        unit_params = tuple(params["unit"])
        experts = (None,) * len(unit)
        if dropless and Sh.current_mesh() is None:
            # the routed experts stay stacked outside the scan: the grouped
            # products read them in place (a scanned slice is a copy)
            unit_params, experts = zip(*map(MoE.split_stacked, unit_params))
        unit_cache = tuple(cache["unit"]) if cache is not None else \
            tuple(None for _ in unit)
        # the position-indexed caches ride the carry whole, each layer
        # writing its entries into its own slice in place; a recurrent
        # state is scanned over and rewritten whole (xs -> ys)
        held, states = zip(*map(B.split_indexed, unit, unit_cache))

        def body(carry, xs):
            h, a, held = carry
            ps, states, layer = xs
            h = constrain(h)
            ps = tuple(MoE.join_stacked(Sh.gather_layer_params(p, cfg), e,
                                        layer)
                       for p, e in zip(ps, experts))
            lctx = dataclasses.replace(ctx, layer=layer)
            out = []
            for j, kind in enumerate(unit):
                h, cj, aj = B.block_apply(
                    kind, ps[j], h, B.join_indexed(kind, held[j], states[j]),
                    lctx)
                out.append(B.split_indexed(kind, cj))
                a = _add(a, aj)
            held, states = zip(*out)
            return (h, a, held), states

        body_fn = jax.checkpoint(body) if cfg.remat else body
        (x, aux, held), states = jax.lax.scan(
            body_fn, (x, aux, held), (unit_params, states, jnp.arange(reps)))
        if cache is not None:
            new_cache["unit"] = list(map(B.join_indexed, unit, held, states))

    for i, kind in enumerate(rem):
        c = None if cache is None else cache["rem"][i]
        x, c, a = B.block_apply(kind, params["rem"][i], x, c, ctx)
        new_cache["rem"].append(c)
        aux = _add(aux, a)

    x = L.norm_apply(params["final_norm"], x, cfg.norm)
    if cfg.family == "vlm" and mode != "decode":
        x = x[:, -batch["tokens"].shape[1]:]     # logits on token positions
    if logits_at is not None:
        x = x[jnp.arange(x.shape[0]), logits_at][:, None]
    logits = L.head_apply(params["embed"] if cfg.tie_embeddings else
                          {**params["embed"]}, x, cfg)
    out = (logits, (new_cache if cache is not None else None), aux["lb"])
    if with_stats:
        out += ({k: v for k, v in aux.items() if k != "lb"},)
    return out


def _add(a, b):
    return jax.tree.map(jnp.add, a, b)


def count_params(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))
