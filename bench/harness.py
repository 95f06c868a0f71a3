"""Shared pieces of the benchmark: files found by name, the device check,
the compile cache, spans and the profiler window, and the result line.

Nothing here names a cell, a configuration, a traffic mix or a metric:
``run.py`` finds each of those by the name ``BENCHMARK.json`` gives it.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
import time
from typing import Any, Dict, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

# seconds of the measured window that a traced run records, at its end
# (and the drain after it): one rvv-128 slate of the long mix writes
# millions of op events, one per strip-loop trip and op
TRACE_SECONDS = 4.0


class NoDevice(SystemExit):
    """The run cannot measure on this machine; exits non-zero."""


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: Optional[str] = None):
    """Import a file of the benchmark by its path (names may hold dots)."""
    name = name or "bench_" + os.path.relpath(path, BENCH).replace(
        os.sep, "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def peaks_for(kind: str) -> Dict[str, float]:
    """Published peaks of one chip of ``kind``; a kind missing from
    ``peaks.json`` is an error, never a default."""
    table = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if kind not in table:
        raise NoDevice(f"device kind {kind!r} is not in bench/peaks.json "
                       f"(known: {sorted(table)})")
    return table[kind]


def check_device(chips: int) -> Dict[str, Any]:
    """The TPU this run measures on, or exit non-zero before any work."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoDevice(f"found platform {d.platform!r} ({d.device_kind}); "
                       f"the benchmark measures only on a TPU")
    if len(devs) < chips:
        raise NoDevice(f"{len(devs)} TPU devices, the cell needs {chips}")
    peaks_for(d.device_kind)
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def enable_compile_cache() -> str:
    """JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR``, else at a
    fixed directory inside the checkout; every program is written to it,
    however fast it compiled, so a warm run loads the port programs too."""
    import jax
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


class CompileCounter:
    """Counts the programs JAX compiles or loads from its cache."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1


def profile_options():
    """No Python call tracing (it slows the host and the spans come from
    ``TraceAnnotation``)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


class Tracer:
    """Host spans, and the profiler over the last ``TRACE_SECONDS`` of a
    window of ``seconds``.  Spans are named ``bench.<what>``; the window
    itself is the span ``bench.window``, which the trace reduction uses
    as the traced window's bounds."""

    def __init__(self, enabled: bool, log_dir: str, seconds: float):
        self.enabled = enabled
        self.log_dir = log_dir
        self.start_at = max(0.0, seconds - TRACE_SECONDS)
        self.started = False
        self._window = None
        self.t_start = None     # host clock (perf_counter) at trace start

    def span(self, name: str):
        if not self.started:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def tick(self, t_rel: float) -> None:
        """Called by a driver's loop with the seconds since the window
        opened; starts the profiler once the traced part is reached."""
        if self.enabled and not self.started and t_rel >= self.start_at:
            import jax
            jax.profiler.start_trace(self.log_dir,
                                     profiler_options=profile_options())
            self.started = True
            self.t_start = time.perf_counter()
            self._window = jax.profiler.TraceAnnotation("bench.window")
            self._window.__enter__()

    def finish(self):
        """Stop the profiler (after the window has closed) and return the
        trace directory, or None when nothing was traced."""
        if not self.started:
            return None
        import jax
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.started = False
        return self.log_dir


def percentile(values, q: float) -> Optional[float]:
    """The ``q``-th percentile (linear between ranks); None if empty."""
    import numpy as np
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def memory_peak_bytes() -> Optional[int]:
    import jax
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def log(msg: str) -> None:
    """Progress and diagnostics go to standard error."""
    print(msg, file=sys.stderr, flush=True)


def emit(result: Dict[str, Any], checks: Dict[str, Dict[str, float]]) -> None:
    """The numbers compared for ``correct``, each beside its limit, as the
    last lines of standard error; then the result as the last line of
    standard output, with the same numbers under ``checks``, last."""
    for name, c in checks.items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    line = dict(result)
    line["checks"] = checks
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
