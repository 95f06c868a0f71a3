"""The one traffic generator: turns a mix's data file into requests.

One kind of mix so far:

``open_loop``  requests that arrive on a schedule, whatever the server
               does: ``rate_per_s``, sizes ``n`` log-uniform over
               [``n_min``, ``n_max``], items (kernels) drawn Zipf(``zipf_s``)
               over the configuration's ranked list, targets in equal
               shares.  Every seed gets the same multiset of gaps, sizes,
               items and targets, each in its own seeded order, so that
               the seed changes the order of the work and not its amount.
               A mix that names a ``schedule_seed`` draws that order from
               it instead, so every run replays one schedule and the
               run's seed draws only the data: where a window holds few
               long requests, the order alone moves its tail by more than
               a bound may allow (PERF.md, section 2).
"""
from __future__ import annotations

from typing import Dict

import numpy as np

KINDS = ("open_loop",)


def _largest_remainder(weights: np.ndarray, total: int) -> np.ndarray:
    """Whole counts summing to ``total`` in the proportions of ``weights``."""
    share = weights / weights.sum() * total
    counts = np.floor(share).astype(np.int64)
    rest = total - int(counts.sum())
    order = np.argsort(-(share - counts), kind="stable")
    counts[order[:rest]] += 1
    return counts


def open_loop(spec: Dict, n_items: int, seed: int,
              seconds: float) -> Dict[str, np.ndarray]:
    """Arrival schedule of one run: ``due_s`` (sorted, inside
    [0, seconds)), and per request its ``item`` index, ``target`` index and
    size ``n``."""
    rate = float(spec["rate_per_s"])
    total = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng(spec.get("schedule_seed", seed))
    u = (np.arange(total) + 0.5) / total          # stratified quantiles

    gaps = -np.log1p(-u) / rate                   # exponential quantiles
    gaps = gaps[rng.permutation(total)]
    due = (np.cumsum(gaps) - gaps) * (seconds / gaps.sum())

    lo, hi = np.log(spec["n_min"]), np.log(spec["n_max"])
    n = np.rint(np.exp(lo + u * (hi - lo))).astype(np.int64)
    n = n[rng.permutation(total)]

    ranks = np.arange(1, n_items + 1, dtype=np.float64)
    item = np.repeat(np.arange(n_items),
                     _largest_remainder(ranks ** -float(spec["zipf_s"]),
                                        total))
    item = item[rng.permutation(total)]

    n_targets = len(spec["targets"])
    target = np.repeat(np.arange(n_targets),
                       _largest_remainder(np.ones(n_targets), total))
    target = target[rng.permutation(total)]
    return {"due_s": due, "item": item, "target": target, "n": n}


def validate(spec: Dict) -> Dict:
    kind = spec.get("kind")
    if kind not in KINDS:
        raise ValueError(f"traffic kind {kind!r} is not one of {KINDS}")
    need = {"open_loop": ("rate_per_s", "n_min", "n_max", "zipf_s",
                          "targets")}[kind]
    missing = [k for k in need if k not in spec]
    if missing:
        raise ValueError(f"{kind} traffic lacks {missing}")
    return spec
