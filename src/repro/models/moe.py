"""Mixture-of-Experts: dropless grouped experts for serving, capacity-
bounded dispatch for training (EP-shardable).

Routing (fp32): softmax over the experts, greedy top-k; the k gates are
divided by their sum only where the config says so (``norm_topk_prob``),
as DeepSeek-V2 (``routed_scaling_factor`` 1) and granite apply them.

Serving (``dropless``, the serving modes' default): every token's k
experts contribute at any routing imbalance.  The T*k assignments are
sorted by expert and the expert MLP runs as three grouped products
(``ops.grouped_gemm``), over blocks of :data:`TOKEN_BLOCK` tokens so that
a long prefill's transients stay bounded; then the outputs are unsorted
and combined with the gates in fp32.  A few tokens (a decode step) run
every expert densely instead, each weighted by its gate or by 0.  Inside
the model's scan over layers the experts of every scanned layer are one
stack of groups, of which the layer uses its own
(``split_stacked``/``join_stacked``): a scanned slice of the weights
would be copied at every step.

Training: one-hot/cumsum dispatch (no data-dependent shapes):
  1. position-in-expert via exclusive cumsum over the (T*k, E) one-hot,
  2. scatter into an (E, C, d) buffer (capacity drops — ``mode='drop'``),
  3. per-expert gated MLP as a single (E, C, d) x (E, d, f) einsum,
  4. gather back and combine with gate weights.

Sharding: experts (leading E axis of the weights and the buffer) ride
the 'model' mesh axis (expert parallelism); tokens stay on 'data'.

``moe_apply(..., stats=True)`` also returns, over the live tokens
(``valid``), the assignments per expert and the assignments dropped.  The
dropless path counts a drop from what its products consume: an
assignment whose sorted row lies outside its expert's group, or whose
gate is missing from the dense weights.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.vtypes import round_up
from repro.kernels import ops
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


# tokens per grouped-product block in the dropless path
TOKEN_BLOCK = 16384
# rows per tile of XLA:TPU's ragged dot (its compiled metadata), to which
# each group's rows are padded
GROUPED_TILE_ROWS = 512
EXPERT_KEYS = ("we_g", "we_u", "we_d")


def capacity(cfg, n_tokens: int) -> int:
    c = int(n_tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor)
    return max(8, round_up(c, 8))


def _route(params, xt, cfg):
    """Router: (gates, idx, aux) in fp32.  xt:(T, d)."""
    e, k = cfg.n_experts, cfg.top_k
    logits = jnp.dot(xt.astype(jnp.float32), params["router"],
                     precision=jax.lax.Precision.HIGHEST)         # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)                          # (T, k)
    if cfg.norm_topk_prob:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(me * ce)                  # Switch-style load balance
    return gates, idx, aux


def _dispatch_compute(params, xt, gates, idx, cfg, cap, e_lo, e_local,
                      valid):
    """Capacity dispatch + expert MLP for experts [e_lo, e_lo+e_local).

    Pure local math (no collectives): the one-hot/cumsum runs over the
    caller's token shard only.  Returns the partial output (T, d) —
    tokens whose choice landed on other ranks' experts contribute 0 —
    and how many of the live (``valid``:(T,)) assignments were dropped.
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
    dropped = jnp.sum(mine & ~keep & jnp.repeat(valid, k), dtype=jnp.int32)

    x_rep = jnp.repeat(xt, k, axis=0)                             # (T*k, d)
    buf = jnp.zeros((e_local, cap, d), xt.dtype).at[e_loc, pos_flat].set(
        jnp.where(keep[:, None], x_rep, 0), mode="drop")

    h_g = jnp.einsum("ecd,edf->ecf", buf, params["we_g"])
    h_u = jnp.einsum("ecd,edf->ecf", buf, params["we_u"])
    h = L.act_apply(h_g, cfg.act) * h_u
    y_buf = jnp.einsum("ecf,efd->ecd", h, params["we_d"])

    y_flat = y_buf.at[e_loc, pos_flat].get(mode="fill", fill_value=0)
    w = (gates.reshape(-1) * keep.astype(jnp.float32)).astype(xt.dtype)
    return jnp.sum((y_flat * w[:, None]).reshape(t, k, d), axis=1), dropped


def _group_sizes(key, e_local, n_groups, first):
    """Rows of each of ``n_groups`` groups: the assignments of expert
    ``e`` (``key`` == e; ``e_local`` = none of these) in group first+e."""
    sizes = jnp.sum(jax.nn.one_hot(key, e_local, dtype=jnp.int32), axis=0)
    return jax.lax.dynamic_update_slice(
        jnp.zeros((n_groups,), jnp.int32), sizes, (first,))


def _grouped_block(w, xt, gates, idx, cfg, e_lo, e_local, first, valid):
    """Every assignment of ``xt``'s tokens to experts [e_lo, e_lo+e_local),
    sorted by expert and run as grouped products over ``w``'s groups, of
    which these experts are [first, first+e_local); the others give 0.
    Also returns the live assignments whose sorted row the groups do not
    cover (a drop)."""
    t, d = xt.shape
    k = cfg.top_k
    e_flat = idx.reshape(-1) - e_lo                               # (T*k,)
    mine = (e_flat >= 0) & (e_flat < e_local)
    key = jnp.where(mine, e_flat, e_local)                        # others last
    order = jnp.argsort(key, stable=True)
    sizes = _group_sizes(key, e_local, w["we_g"].shape[0], first)
    xs = xt[order // k]                                           # (T*k, d)
    h_g = ops.grouped_gemm(xs, w["we_g"], sizes)
    h_u = ops.grouped_gemm(xs, w["we_u"], sizes)
    h = L.act_apply(h_g, cfg.act) * h_u
    ys = ops.grouped_gemm(h, w["we_d"], sizes)
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    y = ys[inv].reshape(t, k, d).astype(jnp.float32)
    g = jnp.where(mine, gates.reshape(-1), 0.0).reshape(t, k, 1)
    y = jnp.where(g != 0.0, y * g, 0.0)      # rows past the groups: no value
    # row j of the sorted assignments is in group first + key[order[j]]
    ends = jnp.cumsum(sizes)
    grp = first + key[order]
    j = jnp.arange(order.shape[0])
    inside = ((j >= jnp.take(ends - sizes, grp, mode="fill",
                             fill_value=j.shape[0])) &
              (j < jnp.take(ends, grp, mode="fill", fill_value=0)))
    lost = jnp.sum(mine[order] & jnp.repeat(valid, k)[order] & ~inside,
                   dtype=jnp.int32)
    return jnp.sum(y, axis=1).astype(xt.dtype), lost


def _dense_block(w, xt, gates, idx, cfg, e_lo, e_local, first, valid):
    """The same sum with every expert of [e_lo, e_lo+e_local) run on every
    token and weighted by the token's gate for it (0 where unchosen); a
    live gate missing from those weights is a drop."""
    t = xt.shape[0]
    e_flat = idx - e_lo                                           # (T, k)
    mine = (e_flat >= 0) & (e_flat < e_local)
    g = jnp.where(mine, gates, 0.0)
    weight = jnp.zeros((t, e_local), jnp.float32).at[
        jnp.arange(t)[:, None], jnp.where(mine, e_flat, e_local)].add(
        g, mode="drop")
    lost = (jnp.sum((g != 0.0) & valid[:, None], dtype=jnp.int32) -
            jnp.sum((weight != 0.0) & valid[:, None], dtype=jnp.int32))
    wg, wu, wd = (jax.lax.dynamic_slice_in_dim(w[n], first, e_local)
                  for n in EXPERT_KEYS)
    h = L.act_apply(jnp.einsum("td,edf->etf", xt, wg), cfg.act) * \
        jnp.einsum("td,edf->etf", xt, wu)
    y = jnp.einsum("etf,efd->etd", h, wd).astype(jnp.float32)
    return jnp.einsum("etd,te->td", y, weight).astype(xt.dtype), lost


def _dropless_compute(w, xt, gates, idx, cfg, e_lo, e_local, valid,
                      first=0):
    """Dropless expert output (T, d) for experts [e_lo, e_lo+e_local), whose
    weights are ``w``'s groups [first, first+e_local), and the live
    assignments lost on the way.  Few tokens run dense, every expert on
    every token: the grouped product pads each
    expert's rows to a tile of ``GROUPED_TILE_ROWS``, so below
    E * tile / (E - k) tokens it does more work than the dense sum.  Many
    tokens run grouped, over blocks of ``TOKEN_BLOCK`` tokens (same math,
    bounded memory)."""
    t, d = xt.shape
    args = (cfg, e_lo, e_local, first)
    if t * e_local <= t * cfg.top_k + e_local * GROUPED_TILE_ROWS:
        return _dense_block(w, xt, gates, idx, *args, valid)
    if t <= TOKEN_BLOCK:
        return _grouped_block(w, xt, gates, idx, *args, valid)
    n = -(-t // TOKEN_BLOCK)
    pad = n * TOKEN_BLOCK - t
    xb = jnp.pad(xt, ((0, pad), (0, 0))).reshape(n, TOKEN_BLOCK, d)
    gb = jnp.pad(gates, ((0, pad), (0, 0))).reshape(n, TOKEN_BLOCK, -1)
    ib = jnp.pad(idx, ((0, pad), (0, 0)), constant_values=-1
                 ).reshape(n, TOKEN_BLOCK, -1)      # pad: no expert's
    vb = jnp.pad(valid, (0, pad)).reshape(n, TOKEN_BLOCK)
    yb, lost = jax.lax.map(
        lambda a: _grouped_block(w, a[0], a[1], a[2], *args, a[3]),
        (xb, gb, ib, vb))
    return yb.reshape(n * TOKEN_BLOCK, d)[:t], jnp.sum(lost)


def split_stacked(p):
    """A scanned block's stacked params without its routed experts, and
    those experts (None for a block without them)."""
    ffn = p.get("ffn") if isinstance(p, dict) else None
    if not isinstance(ffn, dict) or "we_g" not in ffn:
        return p, None
    rest = {k: v for k, v in ffn.items() if k not in EXPERT_KEYS}
    return {**p, "ffn": rest}, {k: ffn[k] for k in EXPERT_KEYS}


def join_stacked(p, experts, layer):
    """One scanned layer's params with the routed experts of every
    scanned layer, stacked, and its own index among them."""
    if experts is None:
        return p
    return {**p, "ffn": {**p["ffn"], "stacked": experts, "layer": layer}}


def _expert_part(params, xt, gates, idx, cfg, dropless, valid):
    """Routed experts' output (T, d) and the live assignments dropped."""
    t = xt.shape[0]
    if "stacked" in params:       # dropless on one device, in a scan
        st, e = params["stacked"], cfg.n_experts
        w = {k: v.reshape((-1,) + v.shape[2:]) for k, v in st.items()}
        return _dropless_compute(w, xt, gates, idx, cfg, 0, e, valid,
                                 params["layer"] * e)
    mesh = Sh.current_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        if dropless:
            return _dropless_compute(params, xt, gates, idx, cfg, 0,
                                     cfg.n_experts, valid)
        return _dispatch_compute(params, xt, gates, idx, cfg,
                                 capacity(cfg, t), 0, cfg.n_experts, valid)

    from jax.sharding import PartitionSpec as P
    ba = Sh.batch_axes(mesh)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_b = max(1, int(np.prod([sizes[a] for a in ba])))
    e_local = max(1, cfg.n_experts // sizes["model"])
    cap = capacity(cfg, max(1, t // n_b))

    def local(xt_l, gates_l, idx_l, valid_l, wg, wu, wd):
        r = jax.lax.axis_index("model")
        p = {"we_g": wg, "we_u": wu, "we_d": wd}
        if dropless:
            y, dropped = _dropless_compute(p, xt_l, gates_l, idx_l, cfg,
                                           r * e_local, e_local, valid_l)
        else:
            y, dropped = _dispatch_compute(p, xt_l, gates_l, idx_l, cfg,
                                           cap, r * e_local, e_local,
                                           valid_l)
        return jax.lax.psum(y, "model"), jax.lax.psum(dropped[None], "model")

    y, dropped = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(ba, None), P(ba, None), P(ba, None), P(ba),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(ba, None), P(ba)),
        check_vma=False,
    )(xt, gates.astype(jnp.float32), idx, valid,
      params["we_g"], params["we_u"], params["we_d"])
    return y, jnp.sum(dropped)


def moe_apply(params, x, cfg, *, dropless=False, valid=None, stats=False):
    """x:(B, S, d) -> (y, aux_loss), and with ``stats`` a third value:
    ``{"expert_tokens": (E,), "dropped": (), "experts_touched": ()}``,
    int32 counts over the live tokens (``valid``:(B, S) bool; None = all):
    assignments per expert, assignments dropped, experts with any.
    ``dropless`` keeps every assignment (serving); otherwise the capacity
    dispatch drops past capacity (training).

    With an active mesh the dispatch runs inside ``shard_map``: tokens
    stay on their data shard, experts live on their 'model' rank, the
    only collective is one activation-sized psum over 'model' for the
    combine (§Perf iteration 1 — the global cumsum/scatter formulation
    made GSPMD all-gather GB-scale dispatch tensors per layer).
    """
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    live = (jnp.ones((t,), bool) if valid is None else valid.reshape(t))
    with jax.named_scope("moe.route"):
        gates, idx, aux = _route(params, xt, cfg)
    with jax.named_scope("moe.experts"):
        y, dropped = _expert_part(params, xt, gates, idx, cfg, dropless,
                                  live)
    if cfg.n_shared_experts:
        with jax.named_scope("moe.shared"):
            y = y + L.mlp_apply(params["shared"], xt, cfg)
    y = y.reshape(b, s, d)
    if not stats:
        return y, aux
    counts = jnp.sum(jax.nn.one_hot(idx, cfg.n_experts, dtype=jnp.int32) *
                     live[:, None, None].astype(jnp.int32), axis=(0, 1))
    return y, aux, {"expert_tokens": counts, "dropped": dropped,
                    "experts_touched": jnp.sum(counts > 0, dtype=jnp.int32)}
