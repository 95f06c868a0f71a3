"""Mixture-of-Experts with capacity-bounded dispatch (EP-shardable).

Dispatch is one-hot/cumsum based (no data-dependent shapes):
  1. router top-k per token (fp32),
  2. position-in-expert via exclusive cumsum over the (T*k, E) one-hot,
  3. scatter into an (E, C, d) buffer (capacity drops — ``mode='drop'``),
  4. per-expert gated MLP as a single (E, C, d) x (E, d, f) einsum,
  5. gather back and combine with gate weights.

Sharding: experts (leading E axis of the weights and the buffer) ride
the 'model' mesh axis (expert parallelism); tokens stay on 'data'.  The
(T*k, E) cumsum is the paper-faithful baseline; a shard_map all-to-all
variant is a §Perf hillclimb candidate (EXPERIMENTS.md).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.vtypes import round_up
from . import layers as L
from . import sharding as Sh


def moe_init(key, cfg):
    dt = L.dtype_of(cfg)
    d, f, e = cfg.d_model, cfg.d_expert, cfg.n_experts
    ks = jax.random.split(key, 5)
    scale = d ** -0.5
    p = {
        "router": (jax.random.normal(ks[0], (d, e), jnp.float32) * 0.02),
        "we_g": (jax.random.normal(ks[1], (e, d, f), jnp.float32) * scale).astype(dt),
        "we_u": (jax.random.normal(ks[2], (e, d, f), jnp.float32) * scale).astype(dt),
        "we_d": (jax.random.normal(ks[3], (e, f, d), jnp.float32) * f ** -0.5).astype(dt),
    }
    if cfg.n_shared_experts:
        p["shared"] = L.mlp_init(ks[4], cfg,
                                 d_ff=cfg.n_shared_experts * cfg.d_expert)
    return p


def capacity(cfg, n_tokens: int) -> int:
    c = int(n_tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor)
    return max(8, round_up(c, 8))


def _route(params, xt, cfg):
    """Router: (gates, idx, aux) in fp32.  xt:(T, d)."""
    e, k = cfg.n_experts, cfg.top_k
    logits = (xt.astype(jnp.float32) @ params["router"])          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)                          # (T, k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(me * ce)                  # Switch-style load balance
    return gates, idx, aux


def _dispatch_compute(params, xt, gates, idx, cfg, cap, e_lo, e_local):
    """Capacity dispatch + expert MLP for experts [e_lo, e_lo+e_local).

    Pure local math (no collectives): the one-hot/cumsum runs over the
    caller's token shard only.  Returns the partial output (T, d) —
    tokens whose choice landed on other ranks' experts contribute 0.
    """
    t, d = xt.shape
    k = cfg.top_k
    e_flat = idx.reshape(-1) - e_lo                               # (T*k,)
    mine = (e_flat >= 0) & (e_flat < e_local)
    e_loc = jnp.where(mine, e_flat, 0)
    onehot = jax.nn.one_hot(e_loc, e_local, dtype=jnp.int32) * \
        mine[:, None].astype(jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot                     # exclusive
    pos_flat = jnp.take_along_axis(pos, e_loc[:, None], axis=1)[:, 0]
    keep = mine & (pos_flat < cap)
    pos_flat = jnp.where(keep, pos_flat, cap)                     # drop slot

    x_rep = jnp.repeat(xt, k, axis=0)                             # (T*k, d)
    buf = jnp.zeros((e_local, cap, d), xt.dtype).at[e_loc, pos_flat].set(
        jnp.where(keep[:, None], x_rep, 0), mode="drop")

    h_g = jnp.einsum("ecd,edf->ecf", buf, params["we_g"])
    h_u = jnp.einsum("ecd,edf->ecf", buf, params["we_u"])
    h = L.act_apply(h_g, cfg.act) * h_u
    y_buf = jnp.einsum("ecf,efd->ecd", h, params["we_d"])

    y_flat = y_buf.at[e_loc, pos_flat].get(mode="fill", fill_value=0)
    w = (gates.reshape(-1) * keep.astype(jnp.float32)).astype(xt.dtype)
    return jnp.sum((y_flat * w[:, None]).reshape(t, k, d), axis=1)


def moe_apply(params, x, cfg):
    """x:(B, S, d) -> (y, aux_loss).

    With an active mesh the dispatch runs inside ``shard_map``: tokens
    stay on their data shard, experts live on their 'model' rank, the
    only collective is one activation-sized psum over 'model' for the
    combine (§Perf iteration 1 — the global cumsum/scatter formulation
    made GSPMD all-gather GB-scale dispatch tensors per layer).
    """
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    gates, idx, aux = _route(params, xt, cfg)
    mesh = Sh.current_mesh()

    if mesh is not None and "model" in mesh.axis_names:
        from jax.sharding import PartitionSpec as P
        ba = Sh.batch_axes(mesh)
        n_b = max(1, int(np.prod([dict(zip(mesh.axis_names,
                                           mesh.devices.shape))[a]
                                  for a in ba])))
        n_m = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
        e_local = max(1, cfg.n_experts // n_m)
        cap = capacity(cfg, max(1, t // n_b))

        def local(xt_l, gates_l, idx_l, wg, wu, wd):
            r = jax.lax.axis_index("model")
            p = {"we_g": wg, "we_u": wu, "we_d": wd}
            y = _dispatch_compute(p, xt_l, gates_l, idx_l, cfg, cap,
                                  r * e_local, e_local)
            return jax.lax.psum(y, "model")

        y = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(ba, None), P(ba, None), P(ba, None),
                      P("model", None, None), P("model", None, None),
                      P("model", None, None)),
            out_specs=P(ba, None),
            check_vma=False,
        )(xt, gates.astype(jnp.float32), idx,
          params["we_g"], params["we_u"], params["we_d"])
    else:
        cap = capacity(cfg, t)
        y = _dispatch_compute(params, xt, gates, idx, cfg, cap,
                              0, cfg.n_experts)

    if cfg.n_shared_experts:
        y = y + L.mlp_apply(params["shared"], xt, cfg)
    return y.reshape(b, s, d), aux
