"""Run one cell of the benchmark and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
the names in ``BENCHMARK.json``:

    bench/configs/<config>.json    sizes, settings and the driver's name
    bench/traffic/<traffic>.json   the mix's parameters
    bench/drivers/<driver>.py      set-up, timed window and check
    bench/metrics/<metric>.py      read(record, trace, ctx) -> number | None

One process: check the device (a TPU whose kind ``bench/peaks.json``
lists, with as many chips as the cell asks for), set up, measure for
``--seconds``, compare the outputs with the plain reference, print the
result.  With ``--trace 1`` the last seconds of the window are traced and
the metrics are the cell's per-layer metrics; otherwise its end-to-end
metrics.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, trace, traffic  # noqa: E402


def cell_metrics(spec, workload: str, traced: bool):
    """The metrics this cell reports in this kind of run."""
    group = spec["per_layer"] if traced else spec["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"unknown workload {args.workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[args.workload]
    bench = harness.BENCH
    cfg = harness.load_json(os.path.join(bench, "configs",
                                         cell["config"] + ".json"))
    mix = traffic.validate(harness.load_json(
        os.path.join(bench, "traffic", cell["traffic"] + ".json")))

    device = harness.check_device(cell["chips"])
    harness.enable_compile_cache()
    compiles = harness.CompileCounter()
    peaks = harness.peaks_for(device["kind"])
    driver = harness.load_module(os.path.join(bench, "drivers",
                                              cfg["driver"] + ".py"))
    log_dir = os.path.join(ROOT, ".bench_trace", args.workload)
    shutil.rmtree(log_dir, ignore_errors=True)
    tracer = harness.Tracer(bool(args.trace), log_dir, args.seconds)

    state = driver.setup(cfg, mix, args.seed, args.seconds)
    setup_s = time.perf_counter() - T_PROCESS
    before = compiles.count
    record = driver.window(state, args.seconds, tracer)
    record["compiles_in_window"] = compiles.count - before
    record["setup_s"] = setup_s
    traced = tracer.finish()
    device["memory_peak_bytes"] = harness.memory_peak_bytes()
    harness.log(f"set-up {setup_s:.3f} s; programs compiled or loaded "
                f"inside the window: {record['compiles_in_window']}")

    summary = None
    if traced:
        summary = trace.reduce(trace.load(traced))
        shutil.rmtree(log_dir, ignore_errors=True)
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        harness.log(f"trace: busy {summary['busy_s']:.6f} s of "
                    f"{summary['window_s']:.6f} s; programs "
                    f"{sorted(summary['programs_s'].items(), key=lambda kv: -kv[1])[:5]}")

    checks, correct = driver.check(state, record)

    ctx = {"cell": cell, "config": cfg, "traffic": mix, "peaks": peaks,
           "seconds": args.seconds}
    metrics = {}
    for m in cell_metrics(spec, args.workload, bool(args.trace)):
        reader = harness.load_module(os.path.join(bench, "metrics",
                                                  m["name"] + ".py"))
        value = reader.read(record, summary, ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": int(record["attempted"]),
              "failed": int(record["failed"]), "metrics": metrics,
              "device": device}
    if summary is not None:
        result["breakdown"] = trace.breakdown(summary)
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
