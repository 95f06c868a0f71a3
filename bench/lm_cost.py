"""Operations and bytes of a multi-head-latent-attention MoE language
model's serving steps, computed from its configuration and the batch's
shapes.

These are the yardstick's own numbers: ``model_mfu`` and
``model_hbm_share`` divide them by measured device time.  They count the
live rows' work only, so padding and inert rows show as lost time:

- a matrix product of (m, k) by (k, n) is 2mkn operations;
- prefill attention is causal and unabsorbed: per layer and head, a
  query at position p scores p + 1 keys over the query-key width and
  sums as many values over the value width;
- decode attention is absorbed, as the program computes it over the
  latent cache: per layer and head, the query is taken into the latent
  width and the latent context out to the value width (together the
  operations of the key-value up-projection, which the per-token count
  already holds), and L + 1 latent and rope entries are scored and
  summed;
- a decode step must read every weight it uses once (the routed experts
  it touches, the rest whole), its live rows' cache prefixes, and write
  one cache entry per live row and layer.

The configuration is read by attribute, under the program's field names
(``d_model``, ``kv_lora_rank``, ...), at published widths.  Norm gains
are left out: a few thousand parameters read once.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np

BF16 = 2
F32 = 4


def _attn_matrix_params(c) -> int:
    h = c.n_heads
    qk = c.qk_nope_dim + c.qk_rope_dim
    return (c.d_model * h * qk                                  # q
            + c.d_model * (c.kv_lora_rank + c.qk_rope_dim)      # kv down
            + c.kv_lora_rank * h * (c.qk_nope_dim + c.v_head_dim)  # kv up
            + h * c.v_head_dim * c.d_model)                     # out


def _expert_params(c) -> int:
    return 3 * c.d_model * c.d_expert


def _n_moe(c) -> int:
    return c.n_layers - c.first_dense_layers


def matrix_params(c) -> int:
    """Every matrix parameter: embedding, untied head, attention, the
    dense layers' FFN, and per MoE layer the router, the routed and the
    shared experts."""
    d = c.d_model
    emb = c.vocab_size * d * (1 if c.tie_embeddings else 2)
    dense = c.first_dense_layers * 3 * d * c.d_ff_dense
    moe = _n_moe(c) * (d * c.n_experts + c.n_experts * _expert_params(c)
                       + c.n_shared_experts * _expert_params(c))
    return emb + c.n_layers * _attn_matrix_params(c) + dense + moe


def active_params_per_token(c) -> int:
    """Matrix parameters one token multiplies by: attention, the dense
    layers' FFN, the router, top-k routed and the shared experts per MoE
    layer, and the head."""
    d = c.d_model
    dense = c.first_dense_layers * 3 * d * c.d_ff_dense
    moe = _n_moe(c) * (d * c.n_experts
                       + (c.top_k + c.n_shared_experts) * _expert_params(c))
    return c.n_layers * _attn_matrix_params(c) + dense + moe + d * c.vocab_size


def prefill_flops(c, lengths: Sequence[int]) -> int:
    """Operations of a prefill over rows of ``lengths`` live tokens."""
    per_token = 2 * active_params_per_token(c)
    qk = c.qk_nope_dim + c.qk_rope_dim
    per_pair = 2 * c.n_heads * (qk + c.v_head_dim) * c.n_layers
    total = 0
    for n in lengths:
        n = int(n)
        total += n * per_token + per_pair * n * (n + 1) // 2
    return total


def decode_flops(c, lengths: Sequence[int]) -> int:
    """Operations of one decode step for live rows holding ``lengths``
    tokens before it (each attends to that many plus its own)."""
    per_token = 2 * active_params_per_token(c)
    h, r = c.n_heads, c.kv_lora_rank
    per_key = 2 * h * ((r + c.qk_rope_dim) + r)
    total = 0
    for n in lengths:
        total += per_token + c.n_layers * per_key * (int(n) + 1)
    return total


def cache_entry_bytes(c) -> int:
    """One token's latent cache entry in one layer (bfloat16)."""
    return (c.kv_lora_rank + c.qk_rope_dim) * BF16


def decode_weight_bytes(c, experts_touched: float, rows: int) -> float:
    """Weights one decode step reads: all but the routed experts whole,
    ``experts_touched`` routed experts summed over the MoE layers, the
    embedding rows of ``rows`` tokens.  The router is float32."""
    d = c.d_model
    dense = c.first_dense_layers * 3 * d * c.d_ff_dense
    shared = _n_moe(c) * c.n_shared_experts * _expert_params(c)
    head = d * c.vocab_size
    fixed = (c.n_layers * _attn_matrix_params(c) + dense + shared + head
             + rows * d) * BF16 + _n_moe(c) * d * c.n_experts * F32
    return fixed + experts_touched * _expert_params(c) * BF16


def decode_bytes(c, lengths: Sequence[int], experts_touched: float) -> float:
    """Bytes one decode step must move for live rows holding ``lengths``
    tokens: the weights read, each row's cache prefix read (with the
    entry it writes) and the entries written, per layer."""
    lengths = np.asarray(lengths, np.int64)
    entry = cache_entry_bytes(c) * c.n_layers
    cache = int(np.sum(lengths + 1)) * entry + len(lengths) * entry
    return decode_weight_bytes(c, experts_touched, len(lengths)) + cache
