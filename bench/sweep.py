"""Offer an open-loop mix at several fixed rates and report how the
served latency and the backlog respond, to find the knee: the highest rate
whose backlog does not grow over the window.

    python3 bench/sweep.py --workload <name> --rates 1,2,4 --seconds 20 --seed <n>

One process: set up the cell once, then for each rate draw a new schedule
and serve it for ``--seconds``.  Per rate it prints one JSON line: requests,
slates, latency p50 and p95, the mean queue wait of the requests due in the
first and in the last third of the window, and the drain (how long after
the window's close the last result came).  A growing backlog shows as a
last-third wait well above the first third's and a drain of more than one
slate.  The benchmark's own runs never call this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    spec = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = {w["name"]: w for w in spec["workloads"]}[args.workload]
    cfg = harness.load_json(os.path.join(harness.BENCH, "configs",
                                         cell["config"] + ".json"))
    mix = harness.load_json(os.path.join(harness.BENCH, "traffic",
                                         cell["traffic"] + ".json"))
    device = harness.check_device(cell["chips"])
    harness.enable_compile_cache()
    driver = harness.load_module(os.path.join(harness.BENCH, "drivers",
                                              cfg["driver"] + ".py"))
    rates = [float(r) for r in args.rates.split(",")]
    state = driver.setup(cfg, dict(mix, rate_per_s=rates[0]), args.seed,
                         args.seconds)
    tracer = harness.Tracer(False, "", args.seconds)
    import numpy as np
    for i, rate in enumerate(rates):
        if i:
            driver.schedule(state, dict(mix, rate_per_s=rate),
                            args.seed + i, args.seconds)
        rec = driver.window(state, args.seconds, tracer)
        due = state["due"]
        wait = rec["queue_wait_ms"]
        first = wait[due < args.seconds / 3]
        last = wait[due >= 2 * args.seconds / 3]
        print(json.dumps({
            "rate_per_s": rate, "requests": rec["attempted"],
            "slates": len(rec["slates"]), "failed": rec["failed"],
            "p50_ms": harness.percentile(rec["latency_ms"], 50),
            "p95_ms": harness.percentile(rec["latency_ms"], 95),
            "wait_first_third_ms": float(np.mean(first)) if len(first) else None,
            "wait_last_third_ms": float(np.mean(last)) if len(last) else None,
            "drain_s": rec["elapsed_s"] - args.seconds,
            "served_per_s": rec["attempted"] / rec["elapsed_s"],
            "device": device["kind"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
