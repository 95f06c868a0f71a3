"""Readings from which a cell's correctness limits are set: the program's
compared numbers and its control's, on several seeds in one process.

    python3 bench/control.py --config <name> --traffic <name> --seeds 1,2,3 --seconds 10

The configuration and the mix are found by name, as ``run.py`` finds a
cell's, so that a pairing without a cell can be read too.  For each seed:
set it up at its own size, run a window of
``--seconds`` at its own load, then print one JSON line with the driver's
``readings`` (the numbers the check compares, for the program and for the
control, the plain reference one precision below the configuration's in
the program's place).  The benchmark's own runs never call this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    cfg = harness.load_json(os.path.join(harness.BENCH, "configs",
                                         args.config + ".json"))
    mix = traffic.validate(harness.load_json(
        os.path.join(harness.BENCH, "traffic", args.traffic + ".json")))
    device = harness.check_device(1)
    harness.enable_compile_cache()
    driver = harness.load_module(os.path.join(harness.BENCH, "drivers",
                                              cfg["driver"] + ".py"))
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        state = driver.setup(cfg, mix, seed, args.seconds)
        t1 = time.perf_counter()
        record = driver.window(state, args.seconds,
                               harness.Tracer(False, "", args.seconds))
        t2 = time.perf_counter()
        out = driver.readings(state, record)
        print(json.dumps({"config": args.config, "traffic": args.traffic,
                          "seed": seed,
                          "device": device["kind"],
                          "attempted": record["attempted"],
                          "setup_s": t1 - t0, "window_s": t2 - t1,
                          "readings_s": time.perf_counter() - t2,
                          **out}), flush=True)
        del state, record
    return 0


if __name__ == "__main__":
    sys.exit(main())
