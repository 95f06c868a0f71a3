"""Serving engine: batched prefill + decode over static-shape caches.

The engine owns a fixed-capacity request batch: prefill fills every
row's cache, decode advances every row one token per step (one
``serve_step`` — the function the decode-shape dry-run cells lower).
Greedy or temperature sampling.

Both steps donate the cache.  A cache indexed by position (attention
keys and values, the latent cache) is read where it lies and receives
only its new entries, in place, inside the model's scan over layers; a
recurrent state is carried and rewritten whole
(``models/blocks.split_indexed``).

Rows may hold prompts of different lengths, right-padded: the prefill
takes each row's logits at its own last token and decode writes each
row's cache at its own length.  A row of length 0 is inert: it runs with
the batch, counts as no work and writes no cache entry at decode.  A
family whose cache is a recurrent state (ssm, hybrid) would take the pad
tokens into that state, so it refuses rows of different lengths.

Each host-side stage is a ``jax.profiler.TraceAnnotation`` on the device
trace's clock (no flag, no device sync of its own): ``engine.prefill``
(args ``rows``, ``width``, ``tokens`` live), ``engine.decode_step``
(``step``, ``rows`` live) and ``engine.fetch``, the blocking copy of a
step's tokens to the host.  ``stats()`` returns the counters, among them
``decode_cache_writes_in_place``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as M

RECURRENT_FAMILIES = ("ssm", "hybrid")


def _new_counts(cfg):
    """Zeroed running counts that the steps add to (``acc``): live MoE
    assignments per expert, assignments dropped, and (decode steps only)
    experts given a live assignment and layers whose cache took its new
    entries in place."""
    return {"expert_tokens": jnp.zeros((cfg.n_experts,), jnp.int32),
            "dropped": jnp.zeros((), jnp.int32),
            "decode_experts_touched": jnp.zeros((), jnp.int32),
            "decode_cache_writes": jnp.zeros((), jnp.int32)}


def _add_counts(acc, st, decode=False):
    return {"expert_tokens": acc["expert_tokens"] + st["expert_tokens"],
            "dropped": acc["dropped"] + st["dropped"],
            "decode_experts_touched": acc["decode_experts_touched"] +
            (st["experts_touched"] if decode else 0),
            "decode_cache_writes": acc["decode_cache_writes"] +
            (st["cache_writes"] if decode else 0)}


def make_prefill_step(cfg, target=None, dropless=True):
    """Prefill: (params, cache, batch, lengths=None, acc=None) ->
    (logits (B, V), cache), and ``acc`` plus this step's counts when given.

    ``lengths``:(B,) holds each row's tokens (None = every row the full
    width); the logits are each row's at its last token.  ``dropless``
    False runs the MoE's training dispatch, which drops past capacity.
    """
    def prefill(params, cache, batch, lengths=None, acc=None):
        b, s = batch["tokens"].shape
        if lengths is None:
            lengths = jnp.full((b,), s, jnp.int32)
        valid = jnp.arange(s)[None, :] < lengths[:, None]
        logits, cache, _, st = M.forward(
            params, cfg, batch, mode="prefill", cache=cache, target=target,
            valid=valid, logits_at=jnp.maximum(lengths - 1, 0),
            with_stats=True, dropless=dropless)
        if acc is None:
            return logits[:, 0], cache
        return logits[:, 0], cache, _add_counts(acc, st)
    return prefill


def make_serve_step(cfg, target=None, dropless=True):
    """One decode step: (params, cache, tokens, lengths, live=None,
    acc=None) -> (logits (B, V), cache), and ``acc`` plus this step's
    counts when given.

    ``live``:(B,) marks the rows the counts take in (None = all).
    ``target`` pins every lowering selection in the step to an explicit
    machine model — a multi-backend deployment builds one jitted step
    per backend and routes requests between them.
    """
    def serve_step(params, cache, tokens, lengths, live=None, acc=None):
        logits, cache, _, st = M.forward(
            params, cfg, {"tokens": tokens}, mode="decode", cache=cache,
            lengths=lengths, target=target,
            valid=None if live is None else live[:, None],
            with_stats=True, dropless=dropless)
        if acc is None:
            return logits[:, 0], cache
        return logits[:, 0], cache, _add_counts(acc, st, decode=True)
    return serve_step


@dataclasses.dataclass
class Engine:
    cfg: Any
    params: Any
    max_batch: int
    max_seq: int
    temperature: float = 0.0
    target: Any = None             # explicit lowering target (None=ambient)
    dropless: bool = True          # False: the MoE's capacity dispatch

    def __post_init__(self):
        p_off = self.cfg.n_patches if self.cfg.family == "vlm" else 0
        self.cache = M.init_cache(self.cfg, self.max_batch,
                                  self.max_seq + p_off)
        self.lengths = jnp.zeros((self.max_batch,), jnp.int32)
        self.logits = None         # the last step's logits (B, V), on device
        self._live = np.ones((self.max_batch,), bool)
        self._live_dev = jnp.asarray(self._live)
        self._prefill = jax.jit(
            make_prefill_step(self.cfg, self.target, self.dropless),
            donate_argnums=(1, 4))
        self._step = jax.jit(
            make_serve_step(self.cfg, self.target, self.dropless),
            donate_argnums=(1, 5))
        self._acc = _new_counts(self.cfg)
        self._counts = {"prefill_tokens": 0, "prefill_padded_tokens": 0,
                        "decode_steps": 0, "decode_rows_live": 0}

    def prefill(self, prompts: jnp.ndarray, lengths=None,
                extra: Optional[dict] = None):
        """prompts:(B, S_prompt), right-padded; ``lengths``:(B,) tokens of
        each row (None = every row is S_prompt long; 0 = an inert row).
        Fills the cache and returns each row's first token."""
        b, s = prompts.shape
        ragged = lengths is not None
        lengths = (np.full((b,), s, np.int32) if lengths is None
                   else np.asarray(lengths, np.int32))
        if lengths.shape != (b,) or lengths.min() < 0 or lengths.max() > s:
            raise ValueError(f"lengths {lengths.tolist()} do not fit "
                             f"prompts of shape {(b, s)}")
        if (ragged and self.cfg.family in RECURRENT_FAMILIES
                and np.any(lengths != s)):
            raise ValueError(
                f"{self.cfg.name}: a {self.cfg.family} cache is a recurrent "
                f"state that would take the pad tokens in; prompts of "
                f"different lengths cannot share a batch")
        live_tokens = int(lengths.sum())
        with jax.profiler.TraceAnnotation("engine.prefill", rows=b, width=s,
                                          tokens=live_tokens):
            batch = {"tokens": prompts, **(extra or {})}
            self.logits, self.cache, self._acc = self._prefill(
                self.params, self.cache, batch, jnp.asarray(lengths),
                self._acc)
            p_off = self.cfg.n_patches if self.cfg.family == "vlm" else 0
            self.lengths = jnp.asarray(lengths + p_off)
            self._live = lengths > 0
            self._live_dev = jnp.asarray(self._live)
            first = self._sample(self.logits)
        self._counts["prefill_tokens"] += live_tokens
        self._counts["prefill_padded_tokens"] += b * s
        return first

    def decode(self, tokens: jnp.ndarray, steps: int,
               rng: Optional[jax.Array] = None,
               on_step: Optional[Callable[[int], None]] = None
               ) -> np.ndarray:
        """Advance ``steps`` tokens for the whole batch; returns (B, steps).
        ``on_step(i)`` runs after step ``i``'s tokens are on the host."""
        out = []
        cur = tokens
        live = int(self._live.sum())
        for i in range(steps):
            with jax.profiler.TraceAnnotation("engine.decode_step", step=i,
                                              rows=live):
                self.logits, self.cache, self._acc = self._step(
                    self.params, self.cache, cur[:, None], self.lengths,
                    self._live_dev, self._acc)
                self.lengths = self.lengths + 1
                cur = self._sample(self.logits)
            with jax.profiler.TraceAnnotation("engine.fetch"):
                out.append(np.asarray(cur))
            self._counts["decode_steps"] += 1
            self._counts["decode_rows_live"] += live
            if on_step is not None:
                on_step(i)
        return np.stack(out, axis=1)

    def stats(self) -> Dict[str, Any]:
        """Counters since the engine was built: live and padded prefill
        tokens, decode steps, live rows summed over decode steps, live
        MoE assignments per expert (summed over layers and steps),
        assignments dropped (0 on the serving path), experts given a live
        assignment, summed over MoE layers and decode steps, and layers
        whose position-indexed cache a decode step wrote its new entries
        into in place, summed over steps (0 for a recurrent state).
        Reading the device's counts waits for the device."""
        out: Dict[str, Any] = dict(self._counts)
        out["moe_expert_tokens"] = np.asarray(
            self._acc["expert_tokens"]).astype(np.int64)
        out["moe_dropped"] = int(self._acc["dropped"])
        out["decode_experts_touched"] = int(
            self._acc["decode_experts_touched"])
        out["decode_cache_writes_in_place"] = int(
            self._acc["decode_cache_writes"])
        return out

    def _sample(self, logits):
        if self.temperature <= 0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        key = jax.random.PRNGKey(int(np.sum(np.asarray(self.lengths))))
        return jax.random.categorical(
            key, logits.astype(jnp.float32) / self.temperature).astype(jnp.int32)

    def generate(self, prompts: jnp.ndarray, steps: int,
                 extra: Optional[dict] = None, lengths=None) -> np.ndarray:
        first = self.prefill(prompts, lengths, extra)
        rest = self.decode(first, steps - 1) if steps > 1 else \
            np.zeros((prompts.shape[0], 0), np.int32)
        return np.concatenate([np.asarray(first)[:, None], rest], axis=1)
