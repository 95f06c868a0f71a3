"""Driver for serving migrated NEON kernels through ``PortEngine``.

Set-up ports the configuration's kernels from the C sources copied into
``bench/refs``, builds the engine with the configuration's settings, draws
the run's open-loop schedule and arguments from the seed, and compiles one
program per (kernel, target, bucket) the mix can draw.  The window serves
the schedule as it falls due: each pass of the loop submits, as one slate,
every request due by then.  A request is timed from its due time until
its result is on the host; requests due in the window and not yet served
when it closes are served after it and counted.  The check compares every
served answer with the kernel's plain reference.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from bench import cost, traffic as traffic_gen
from bench.harness import log
from bench.refs import neon_corpus as _refs

# PortEngine counters of work that did not run as the batched program the
# configuration asks for: a batch that faulted, a row served by the ladder
# (narrow program or host interpreter) instead, a program built on a lower
# rung, an error returned, a deadline missed.  Each must stay 0.
DEGRADATION = ("batch_faults", "row_fallbacks", "program_fallbacks",
               "errors_returned", "deadline_misses")


def _buckets(policy, lo: int, hi: int) -> List[int]:
    out, b = [], policy.bucket(lo)
    while b < hi:
        out.append(b)
        b *= policy.growth
    out.append(policy.bucket(hi))
    return sorted(set(out))


def setup(cfg: Dict, mix: Dict, seed: int, seconds: float) -> Dict:
    from repro import port
    from repro.serve import PortEngine

    names = cfg["kernels"]
    refs = [_refs.KERNELS[k] for k in names]
    if mix["n_max"] > cfg["max_request_elems"]:
        raise ValueError(f"mix n_max {mix['n_max']} is over the "
                         f"configuration's {cfg['max_request_elems']}")
    kernels = [port.compile_file(r.path, name=k) for k, r in zip(names, refs)]
    state = {"engine": PortEngine(**cfg["engine"]), "kernels": kernels,
             "refs": refs, "names": names}
    warm_up(state, mix)
    schedule(state, mix, seed, seconds)
    return state


def warm_up(state: Dict, mix: Dict) -> None:
    """One program per (kernel, target, bucket) of the mix: an n = 0 row
    over bucket-long buffers selects the bucket's shape and runs no trip."""
    from repro.serve import Request
    eng = state["engine"]
    warm = []
    wrng = np.random.default_rng(0)
    for k, r in zip(state["kernels"], state["refs"]):
        for b in _buckets(eng.bucket_policy, mix["n_min"], mix["n_max"]):
            a = list(r.make_args(wrng, b))
            a[0] = 0
            for tgt in mix["targets"]:
                warm.append(Request(k, tuple(a), target=tgt))
    for out in eng.submit(warm):
        if isinstance(out, Exception):
            raise RuntimeError(f"warm-up request failed: {out!r}")
    log(f"port_serve: {len(state['kernels'])} kernels x "
        f"{len(mix['targets'])} targets, {len(warm)} warm-up programs")


def schedule(state: Dict, mix: Dict, seed: int, seconds: float) -> None:
    """The run's requests: the open-loop schedule and every argument,
    drawn from the seed."""
    from repro.serve import Request
    sched = traffic_gen.open_loop(mix, len(state["kernels"]), seed, seconds)
    rng = np.random.default_rng([seed, 1])
    requests, args = [], []
    for item, tgt, n in zip(sched["item"], sched["target"], sched["n"]):
        a = state["refs"][item].make_args(rng, int(n))
        args.append(a)
        requests.append(Request(state["kernels"][item], a,
                                target=mix["targets"][tgt]))
    state.update(requests=requests, args=args, items=sched["item"],
                 due=sched["due_s"])
    log(f"port_serve: {len(requests)} requests due in {seconds} s")


def window(state: Dict, seconds: float, tracer) -> Dict:
    eng, reqs, due = state["engine"], state["requests"], state["due"]
    total = len(reqs)
    lat = np.zeros(total)
    wait = np.zeros(total)
    outputs = [None] * total
    slates = []                       # (submit time, first, last + 1)
    s0 = eng.stats()
    t0 = time.perf_counter()
    i = 0
    while i < total:
        now = time.perf_counter() - t0
        tracer.tick(now)
        if due[i] > now:
            with tracer.span("bench.wait"):
                time.sleep(due[i] - now)
            continue
        j = int(np.searchsorted(due, now, side="right"))
        t_sub = time.perf_counter() - t0
        with tracer.span("bench.submit"):
            res = eng.submit(reqs[i:j])
        t_done = time.perf_counter() - t0
        wait[i:j] = t_sub - due[i:j]
        lat[i:j] = t_done - due[i:j]
        outputs[i:j] = res
        slates.append((t_sub, i, j))
        i = j
    while time.perf_counter() - t0 < seconds:   # the window's full length
        tracer.tick(time.perf_counter() - t0)
        with tracer.span("bench.wait"):
            time.sleep(min(0.05, seconds - (time.perf_counter() - t0)))
    elapsed = time.perf_counter() - t0
    s1 = eng.stats()
    state["outputs"] = outputs
    keys = ("padded_elems", "payload_elems", "batches", "inert_rows")
    delta = {k: s1[k] - s0[k] for k in keys}
    degraded = {k: s1[k] for k in DEGRADATION}
    log(f"port_serve: served {total} requests in {len(slates)} slates over "
        f"{elapsed:.3f} s; engine counters over the window {delta}; "
        f"degradation counters since the engine was built {degraded}")
    return {"attempted": total, "elapsed_s": elapsed,
            "latency_ms": lat * 1e3, "queue_wait_ms": wait * 1e3,
            "slates": [(t, sum(cost.port_payload_bytes(a)
                               for a in state["args"][i:j]))
                       for t, i, j in slates],
            "engine": delta, "degraded": degraded,
            "failed": sum(isinstance(o, Exception) for o in outputs),
            "trace_start_s": (None if tracer.t_start is None
                              else tracer.t_start - t0)}


def answers(state: Dict, outputs) -> Dict[str, Dict]:
    """Every answer of the window against its kernel's plain reference,
    and the worst float reduction's error."""
    wrong = unanswered = 0
    worst_u = 0.0
    for idx, out in enumerate(outputs):
        ref = state["refs"][state["items"][idx]]
        if out is None or isinstance(out, Exception):
            unanswered += 1
            continue
        args = state["args"][idx]
        want = ref.reference(*args)
        e = _refs.reduction_error_u(out, want, ref, args)
        if e is not None and not e <= worst_u:     # a NaN sticks
            worst_u = e
        ok, why = _refs.conforms(out, want, ref, args)
        if not ok:
            wrong += 1
            if wrong <= 5:
                log(f"wrong answer: request {idx} "
                    f"{state['names'][state['items'][idx]]} "
                    f"n={args[0]}: {why}")
    return {"wrong_answers": {"value": wrong, "limit": 0},
            "unanswered": {"value": unanswered, "limit": 0},
            "reduction_error_u": {"value": worst_u,
                                  "limit": _refs.REDUCTION_BUDGET_U}}


def check(state: Dict, record: Dict):
    checks = answers(state, state["outputs"])
    checks["degraded"] = {"value": sum(record["degraded"].values()),
                          "limit": 0}
    return checks, all(c["value"] <= c["limit"] for c in checks.values())


def control_outputs(state: Dict) -> List:
    """The reference in the program's place, one precision down: float32
    kernels computed on bfloat16-rounded inputs with the result rounded to
    bfloat16; int8 kernels on inputs rounded to int4 (multiples of 16)."""
    import ml_dtypes
    outs = []
    for idx, a in enumerate(state["args"]):
        ref = state["refs"][state["items"][idx]]
        low = []
        for v in a:
            if isinstance(v, np.ndarray) and v.dtype == np.float32:
                v = v.astype(ml_dtypes.bfloat16).astype(np.float32)
            elif isinstance(v, np.ndarray) and v.dtype == np.int8:
                v = ((v.astype(np.int16) // 16) * 16).clip(-128, 127
                                                           ).astype(np.int8)
            low.append(v)
        out = ref.reference(*low)
        if out.dtype == np.float32:
            out = out.astype(ml_dtypes.bfloat16).astype(np.float32)
        outs.append(out)
    return outs


def readings(state: Dict, record: Dict) -> Dict:
    """For setting and checking the limits: the program's numbers and the
    control's (the reference one precision down in the program's place)."""
    out = {k: c["value"] for k, c in check(state, record)[0].items()}
    ctl = answers(state, control_outputs(state))
    out.update({"control." + k: c["value"] for k, c in ctl.items()})
    return out
