"""Plain float32 DeepSeek-V2 language model (arXiv:2405.04434), for
checking a served model's logits.

Written from DeepSeek's own ``modeling_deepseek.py`` (the one published
beside DeepSeek-V2-Lite's ``config.json``).  Each decoder layer:

    h        = rmsnorm(x) * input_norm
    q        = h @ q_proj                     (no q-LoRA in the Lite model)
    q_nope, q_pe = split(q per head, [qk_nope, qk_rope])
    c, k_pe  = split(h @ kv_a_proj, [kv_lora, qk_rope])
    k_nope, v = split((rmsnorm(c) * kv_a_norm) @ kv_b_proj per head,
                      [qk_nope, v_head])
    q_pe, k_pe = rope(q_pe, k_pe)             (k_pe shared by all heads)
    a        = softmax(causal(q . k * softmax_scale)) @ v
    x        = x + a @ o_proj
    h        = rmsnorm(x) * post_norm
    x        = x + mlp(h)                     (dense, or MoE below)

MoE: ``p = softmax(h @ router)`` in float32, greedy top-k of p, the k
weights divided by their sum only if ``norm_topk_prob``, times
``routed_scaling_factor``; ``y = sum_k w_k expert_k(h) + shared(h)``, each
expert and the shared experts a SiLU-gated MLP.  Then
``logits = rmsnorm(x) * final_norm @ lm_head``.

RoPE and YaRN as ``DeepseekV2YarnRotaryEmbedding``: frequencies blend
``freq_inter = freq_extra / factor`` and ``freq_extra`` with
``1 - linear_ramp(low, high)``, where low and high are the correction
dims of ``beta_fast`` and ``beta_slow``; cos and sin are scaled by
``yarn_get_mscale(factor, mscale) / yarn_get_mscale(factor,
mscale_all_dim)``; the softmax scale is ``q_head_dim ** -0.5 *
yarn_get_mscale(factor, mscale_all_dim) ** 2``.  ``apply_rotary_pos_emb``
regroups each rope vector's interleaved pairs (2i, 2i + 1) into halves
and rotates the halves; queries and keys are regrouped alike, so their
dot products are those of rotated interleaved pairs.

Departures from DeepSeek's code, none of which changes a number it
computes beyond float32 rounding:
- one request at a time, positions 0..S-1, no cache: every logit is a
  full causal forward (teacher forcing);
- every expert runs on every token and is weighted by 0 where it is not
  among the token's top-k, which is the same sum as running only the k;
- logits only at the positions asked for;
- weights arrive as arrays in (in, out) layout (``x @ w``), the router as
  (hidden, experts), and are upcast to float32; every matrix product runs
  at ``highest`` precision.

``low`` (a dtype name) rounds every matrix product's operands to that
dtype first: the reference one precision down, for setting limits.
Nothing here imports the program.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Iterable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

ATTN_KEYS = ("input_norm", "q_proj", "kv_a_proj", "kv_a_norm", "kv_b_proj",
             "o_proj", "post_norm")
MLP_KEYS = ("gate", "up", "down")
MOE_KEYS = ("router", "experts_gate", "experts_up", "experts_down",
            "shared_gate", "shared_up", "shared_down")


# --- YaRN, as modeling_deepseek.py writes it -------------------------------

def yarn_find_correction_dim(num_rotations, dim, base, max_pos):
    return (dim * math.log(max_pos / (num_rotations * 2 * math.pi))) / \
        (2 * math.log(base))


def yarn_find_correction_range(low_rot, high_rot, dim, base, max_pos):
    low = math.floor(yarn_find_correction_dim(low_rot, dim, base, max_pos))
    high = math.ceil(yarn_find_correction_dim(high_rot, dim, base, max_pos))
    return max(low, 0), min(high, dim - 1)


def yarn_get_mscale(scale=1.0, mscale=1.0):
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def yarn_linear_ramp_mask(lo, hi, dim):
    if lo == hi:
        hi += 0.001
    return np.clip((np.arange(dim, dtype=np.float32) - lo) / (hi - lo), 0, 1)


def inv_freq(dim: int, base: float, rope_scaling: Optional[Dict]) -> np.ndarray:
    """Inverse frequencies of the rope part's dim // 2 rotations."""
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    if rope_scaling is None:
        return freq_extra.astype(np.float32)
    s = rope_scaling
    freq_inter = 1.0 / (s["factor"] *
                        base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    lo, hi = yarn_find_correction_range(
        s["beta_fast"], s["beta_slow"], dim, base,
        s["original_max_position_embeddings"])
    mask = 1.0 - yarn_linear_ramp_mask(lo, hi, dim // 2)
    return (freq_inter * (1 - mask) + freq_extra * mask).astype(np.float32)


def rope_mscale(rope_scaling: Optional[Dict]) -> float:
    """The factor on cos and sin (1 where mscale == mscale_all_dim)."""
    if rope_scaling is None:
        return 1.0
    s = rope_scaling
    return (yarn_get_mscale(s["factor"], s.get("mscale", 1.0)) /
            yarn_get_mscale(s["factor"], s.get("mscale_all_dim", 0.0)))


def softmax_scale(cfg: Dict) -> float:
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    s = cfg.get("rope_scaling")
    if s is not None and s.get("mscale_all_dim", 0):
        m = yarn_get_mscale(s["factor"], s["mscale_all_dim"])
        scale = scale * m * m
    return scale


# --- layers ----------------------------------------------------------------

def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _mm(a, b, low):
    if low is not None:
        a = a.astype(low).astype(jnp.float32)
        b = b.astype(low).astype(jnp.float32)
    return a @ b


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _apply_rope(x, cos, sin):
    """x:(S, H, D) with cos/sin:(S, D); DeepSeek's regroup, then rotate."""
    s, h, d = x.shape
    x = x.reshape(s, h, d // 2, 2).swapaxes(-1, -2).reshape(s, h, d)
    return x * cos[:, None] + _rotate_half(x) * sin[:, None]


def _mlp(x, wg, wu, wd, low):
    return _mm(jax.nn.silu(_mm(x, wg, low)) * _mm(x, wu, low), wd, low)


def _attention(x, w, cfg, low):
    s = x.shape[0]
    h = cfg["num_attention_heads"]
    nd, r = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, kvr = cfg["v_head_dim"], cfg["kv_lora_rank"]
    eps = cfg["rms_norm_eps"]
    q = _mm(x, _f32(w["q_proj"]), low).reshape(s, h, nd + r)
    q_nope, q_pe = q[..., :nd], q[..., nd:]
    ckv = _mm(x, _f32(w["kv_a_proj"]), low)
    c = _rmsnorm(ckv[:, :kvr], _f32(w["kv_a_norm"]), eps)
    k_pe = ckv[:, None, kvr:]                                # (S, 1, r)
    kv = _mm(c, _f32(w["kv_b_proj"]), low).reshape(s, h, nd + vd)
    k_nope, v = kv[..., :nd], kv[..., nd:]
    t = jnp.arange(s, dtype=jnp.float32)
    freqs = jnp.outer(t, jnp.asarray(inv_freq(r, cfg["rope_theta"],
                                              cfg.get("rope_scaling"))))
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    m = rope_mscale(cfg.get("rope_scaling"))
    cos, sin = jnp.cos(emb) * m, jnp.sin(emb) * m
    q_pe, k_pe = _apply_rope(q_pe, cos, sin), _apply_rope(k_pe, cos, sin)
    query = jnp.concatenate([q_nope, q_pe], axis=-1)
    key = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (s, h, r))], -1)
    if low is not None:
        query = query.astype(low).astype(jnp.float32)
        key = key.astype(low).astype(jnp.float32)
    scores = jnp.einsum("qhd,khd->hqk", query, key) * softmax_scale(cfg)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    a = jnp.einsum("hqk,khd->qhd", p, v).reshape(s, h * vd)
    return _mm(a, _f32(w["o_proj"]), low)


def _moe(x, w, cfg, low):
    e, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(x @ _f32(w["router"]), axis=-1)   # float32
    top_w, top_i = jax.lax.top_k(probs, k)
    if cfg["norm_topk_prob"]:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    top_w = top_w * cfg["routed_scaling_factor"]
    weight = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], top_i].set(top_w)   # (S, E)

    def expert(y, i):
        out = _mlp(x, _f32(w["experts_gate"][i]), _f32(w["experts_up"][i]),
                   _f32(w["experts_down"][i]), low)
        return y + weight[:, i, None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(e))
    return y + _mlp(x, _f32(w["shared_gate"]), _f32(w["shared_up"]),
                    _f32(w["shared_down"]), low)


@functools.partial(jax.jit, static_argnames=("cfg_items", "moe", "low"))
def layer(x, w: Dict, *, cfg_items: Tuple, moe: bool, low=None):
    """One decoder layer on x:(S, hidden) float32."""
    cfg = _unfreeze(cfg_items)
    low = None if low is None else jnp.dtype(low)
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = x + _attention(_rmsnorm(x, _f32(w["input_norm"]), eps), w, cfg,
                           low)
        h = _rmsnorm(x, _f32(w["post_norm"]), eps)
        if moe:
            return x + _moe(h, w, cfg, low)
        return x + _mlp(h, _f32(w["gate"]), _f32(w["up"]), _f32(w["down"]),
                        low)


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _head(x, norm, lm_head, *, eps, low=None):
    low = None if low is None else jnp.dtype(low)
    with jax.default_matmul_precision("highest"):
        return _mm(_rmsnorm(x, _f32(norm), eps), _f32(lm_head), low)


def _freeze(cfg: Dict) -> Tuple:
    out = []
    for k, v in sorted(cfg.items()):
        if isinstance(v, dict):
            v = ("__dict__",) + tuple(sorted(v.items()))
        out.append((k, v))
    return tuple(out)


def _unfreeze(items: Tuple) -> Dict:
    cfg = {}
    for k, v in items:
        if isinstance(v, tuple) and v and v[0] == "__dict__":
            v = dict(v[1:])
        cfg[k] = v
    return cfg


def forward(tokens: Sequence[int], embed, final_norm, lm_head,
            layers: Iterable[Tuple[str, Dict]], cfg: Dict,
            at: Sequence[int], low: Optional[str] = None,
            width: Optional[int] = None) -> np.ndarray:
    """Logits (len(at), vocab) of one request's ``tokens`` at positions
    ``at``.  ``layers`` yields ("dense" | "moe", weights) in order, so a
    caller can hand over one layer's weights at a time.  ``width`` pads
    the tokens at the end to that many (causal: no logit asked for moves),
    so that requests of any length share one compiled layer."""
    items = _freeze(cfg)
    tokens = np.asarray(tokens)
    if width is not None:
        tokens = np.pad(tokens, (0, width - len(tokens)))
    x = _f32(jnp.asarray(embed)[jnp.asarray(tokens)])
    if low is not None:
        x = x.astype(low).astype(jnp.float32)
    for kind, w in layers:
        x = layer(x, w, cfg_items=items, moe=kind == "moe", low=low)
    x = x[jnp.asarray(np.asarray(at))]
    out = _head(x, final_norm, lm_head, eps=cfg["rms_norm_eps"], low=low)
    return np.asarray(out)[:, :cfg["vocab_size"]]


def rel_err(got, want) -> float:
    """Relative L2 error of ``got`` against ``want`` (float64)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))
