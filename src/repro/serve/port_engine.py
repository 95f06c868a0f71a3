"""serve.port — batched, bucketed serving tier for compiled ported kernels.

A migrated NEON kernel compiled through :meth:`PortedKernel.compile`
answers one request per call: one XLA executable launch for one ``n``.
A serving process sees thousands of small independent requests — vadd
over a few hundred elements, a qs8 dot-product per feature row — and
per-request launch overhead dominates.  This engine batches them:

* **vmap batching** — requests for the same (kernel, target) run as one
  jitted ``jax.vmap`` of the *eager* compiled kernel.  Every argument is
  mapped over the batch axis: scalar params become ``(B,)`` vectors (the
  closed-form ``fori_loop`` trip counts become traced per-row values,
  which JAX's while_loop batching rule handles), pointer params become
  ``(B, L)`` buffers.

* **geometric shape buckets** — XLA specializes per shape, so free-form
  ``n`` would recompile per distinct length.  Buffer lengths are padded
  up to per-bucket canonical shapes (``BucketPolicy``: base x growth^k)
  and the batch axis is padded to a fixed ``max_batch`` with inert
  ``n = 0`` rows, so the executable count is bounded by
  buckets x targets x kernels per engine.  Padding is legal for the
  same reason the re-vectorizer's masked tails are: trip counts derive
  from the *actual* per-row ``n``, so padded regions are never read and
  never written; outputs are sliced back to request length.

* **shape model from the IR** — how long must a padded buffer be for a
  given ``n``?  The strip-loop matcher (:func:`repro.port.revec.strip_loops`)
  already proves each pointer's affine walk; ``ptr_step / step`` is its
  element stride per unit ``n``.  Buffers the strip does not walk (the
  length-1 ``sum`` output of a dot kernel, packed weights) keep their
  exact length and join the group key instead.

* **chip-width strips** — the batched program runs on a fixed-tile
  machine, not on the RVV machine a request names, so its first rung
  re-tiles each strip that has a provable masked tail to the executing
  chip's register tile (:func:`repro.core.targets.compile_target`;
  8 x 128 f32 or 32 x 128 int8 elements a trip on v5e) while the
  request's target still selects every intrinsic's lowering.  One trip
  then covers 1,024-4,096 elements instead of 4-128, and the masked
  tail becomes the padding mask.  ``stats()["chip_width_programs"]``
  counts the programs built so, and ``port.chunk``'s ``strip`` arg
  gives a program's elements per trip.

* **compile reuse** — all compilation goes through the process-wide
  bounded CompiledKernel LRU (:func:`repro.port.compiled_cache_info`);
  :meth:`PortEngine.warmup` pre-populates it from a corpus with eager
  (``jit=False``) compiles, the deploy-time shape probe.

* **spans** — each stage of :meth:`PortEngine.submit` is a
  ``jax.profiler.TraceAnnotation`` (``port.submit`` > ``port.plan``,
  ``port.chunk`` > ``port.pad``, ``port.h2d``, ``port.launch``,
  ``port.fetch``, ``port.slice``; ``port.fallback``), recorded on the
  profiler's host plane, on the device planes' clock, whenever a
  profiler runs.  Each batched program is named
  ``port_<kernel>_<target>``, so the trace shows it as
  ``jit_port_<kernel>_<target>``.

Mixed fleets route per request: ``Request(target="rvv-1024")`` overrides
the engine default, so rvv-128 and rvv-1024 traffic batch side by side
in one :meth:`submit` call (grouped separately, like
:class:`repro.serve.engine.Engine`'s per-target jitted steps).
"""
from __future__ import annotations

import dataclasses
import itertools
import math
import re
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation as _span

from repro.core import targets as _targets
from repro.port import PortedKernel, revec
from repro.port import faultinject as _fi
from repro.port import resilience as _resilience
from repro.port.ir import PtrType, ScalarType
from repro.port.resilience import DeadlineExceeded, LadderExhausted, PortError

__all__ = ["BucketPolicy", "Request", "PortEngine"]


@dataclasses.dataclass(frozen=True)
class BucketPolicy:
    """Geometric length buckets: ``base * growth^k`` for k = 0, 1, ...

    Finer buckets waste less padding per request but admit more shapes
    (more XLA executables); coarser buckets bound compiles harder at
    higher padding waste.  ``bucket(n)`` returns the smallest bucket
    holding ``n``.
    """

    name: str
    base: int = 64
    growth: int = 2

    def bucket(self, n: int) -> int:
        n = max(1, int(n))
        b = self.base
        while b < n:
            b *= self.growth
        return b

    @staticmethod
    def preset(name: str) -> "BucketPolicy":
        try:
            return _BUCKET_PRESETS[name]
        except KeyError:
            raise KeyError(f"unknown bucket policy {name!r}; "
                           f"known: {sorted(_BUCKET_PRESETS)}")


_BUCKET_PRESETS = {
    "fine": BucketPolicy("fine", base=64, growth=2),
    "coarse": BucketPolicy("coarse", base=64, growth=4),
}


@dataclasses.dataclass
class Request:
    """One kernel invocation: args follow the PortedKernel calling
    convention (ints for scalar params, 1-D arrays for pointers).
    ``target=None`` uses the engine's default target.

    ``deadline_s`` is a per-request budget in seconds, measured from
    :meth:`PortEngine.submit` entry: a request whose deadline has
    passed before its chunk launches (or before per-row recovery work
    starts) resolves to a typed :class:`DeadlineExceeded` instead of
    consuming more engine time."""

    kernel: PortedKernel
    args: Sequence[Any]
    target: Any = None
    deadline_s: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class _ShapeModel:
    """Per-kernel padding rules derived from the strip-loop IR.

    ``strides[i]`` is the element stride per unit ``n`` for pointer
    param ``i`` (padded length = bucket(n) * stride); pointer params
    absent from ``strides`` keep their exact length in the group key.
    ``counter`` is the scalar param index driving the strip (None when
    no strip loop matched — every buffer then keys on exact length and
    batching still works, just without length bucketing).
    """

    counter: Optional[int]
    strides: Tuple[Tuple[int, int], ...]

    @staticmethod
    def derive(kernel: PortedKernel) -> "_ShapeModel":
        fn = kernel.fn
        pindex = {p: i for i, p in enumerate(fn.params)}
        counter: Optional[int] = None
        strides: Dict[int, int] = {}
        for info in revec.strip_loops(fn):
            loop = info.loop
            init = loop.init[loop.phis.index(info.counter)]
            ci = pindex.get(init)
            if ci is None or not isinstance(fn.params[ci].type, ScalarType):
                continue
            if counter is None:
                counter = ci
            elif counter != ci:
                continue            # second strip on a different counter
            for pphi, d in info.ptr_steps.items():
                pinit = loop.init[loop.phis.index(pphi)]
                pi = pindex.get(pinit)
                if pi is None or d <= 0 or d % info.step != 0:
                    continue
                strides.setdefault(pi, d // info.step)
        return _ShapeModel(counter, tuple(sorted(strides.items())))


def _program_name(kernel: str, target: str) -> str:
    """``port_<kernel>_<target>`` cut down to ``[A-Za-z0-9_]``: the name
    of the batched program in HLO and in the profiler's trace."""
    return re.sub(r"[^A-Za-z0-9_]", "_", f"port_{kernel}_{target}")


class PortEngine:
    """Batched, bucketed, cache-managed serving of ported kernels.

    Hardened for mixed production slates: engine state is guarded by an
    RLock; batched-executable failures degrade to per-row recovery down
    the ladder (:func:`repro.port.resilience.run_resilient` — compiled
    narrow, then the interpreter, conformance-identical results); a
    failing request resolves to its typed :class:`PortError` in the
    results list (``on_error="return"``, the default) instead of
    aborting the slate; compile attempts retry ``compile_retries``
    times on transient errors and share the process-wide circuit
    breaker, so a persistently poisoned (kernel, target) is quarantined
    and fails fast without stalling its batch-mates.
    """

    def __init__(self, *, target: Any = None, policy: str = "pallas",
                 revec: bool = True, bucket_policy: Any = "fine",
                 max_batch: int = 32, compile_retries: int = 1,
                 on_error: str = "return", tuned: bool = False):
        self.target = target            # engine default; per-request override
        self.policy = policy
        self.revec = bool(revec)
        # consult the persisted autotuning cache on every compile: a
        # deploy that ran (or shipped) a tuning pass starts with the
        # tuned LMUL regrouping + retile knobs instead of the static
        # defaults (repro.port.autotune; decisions survive restarts)
        self.tuned = bool(tuned)
        self.bucket_policy = (BucketPolicy.preset(bucket_policy)
                              if isinstance(bucket_policy, str)
                              else bucket_policy)
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if on_error not in ("return", "raise"):
            raise ValueError(f"on_error must be 'return' or 'raise', "
                             f"got {on_error!r}")
        self.max_batch = int(max_batch)
        self.compile_retries = int(compile_retries)
        self.on_error = on_error
        self._lock = threading.RLock()
        self._models: Dict[int, _ShapeModel] = {}
        self._programs: Dict[Tuple[int, Any], Any] = {}
        # (kernel, target) -> elements per trip of its program's widest
        # strip (the ``port.chunk`` span's ``strip``)
        self._strips: Dict[Tuple[int, Any], int] = {}
        self._shapes_seen: set = set()
        self._slates = itertools.count(1)   # the spans' ``slate`` arg
        self._stats = {"requests": 0, "batches": 0, "inert_rows": 0,
                       "padded_elems": 0, "payload_elems": 0,
                       "h2d_bytes": 0, "d2h_bytes": 0,
                       "batch_faults": 0, "row_fallbacks": 0,
                       "errors_returned": 0, "deadline_misses": 0,
                       "program_fallbacks": 0, "chip_width_programs": 0}

    def _bump(self, key: str, n: int = 1) -> None:
        with self._lock:
            self._stats[key] += n

    # -- shape model -------------------------------------------------------

    def _model(self, kernel: PortedKernel) -> _ShapeModel:
        with self._lock:
            m = self._models.get(id(kernel))
            if m is None:
                m = self._models[id(kernel)] = _ShapeModel.derive(kernel)
            return m

    def _plan(self, req: Request):
        """Group key + padded buffer lengths for one request."""
        kernel, args = req.kernel, req.args
        if len(args) != len(kernel.fn.params):
            raise ValueError(
                f"{kernel.name} takes {len(kernel.fn.params)} args, "
                f"got {len(args)}")
        tgt = _targets.resolve_target(
            req.target if req.target is not None else self.target)
        model = self._model(kernel)
        strides = dict(model.strides)
        bucket = 0
        if model.counter is not None:
            # the bucket must hold both the request's n and every
            # strip-walked buffer the caller handed us (a buffer longer
            # than n*stride promotes the bucket so padding never
            # truncates untouched caller bytes)
            need = int(args[model.counter])
            for pi, s in strides.items():
                need = max(need, math.ceil(len(args[pi]) / s))
            bucket = self.bucket_policy.bucket(need)
        lens = []
        for i, p in enumerate(kernel.fn.params):
            if not isinstance(p.type, PtrType):
                lens.append(None)
            elif i in strides:
                lens.append(bucket * strides[i])
            else:
                lens.append(len(args[i]))
        # exact-length (non-strip) buffers join the key so every row in
        # a group shares one canonical shape tuple
        extras = tuple(lens[i] for i, p in enumerate(kernel.fn.params)
                       if isinstance(p.type, PtrType) and i not in strides)
        key = (id(kernel), tgt, bucket, extras)
        return key, tgt, lens

    # -- batch programs ----------------------------------------------------

    def _eager(self, kernel: PortedKernel, tgt, revec: bool):
        """The eager (``jit=False``) compile of one batched rung, from
        the process-wide LRU; the revec rung re-tiles at the executing
        chip's tile."""
        return kernel.compile(
            target=tgt, policy=self.policy, revec=revec, jit=False,
            tuned=self.tuned,
            tile=_targets.compile_target() if revec else None)

    def _program(self, kernel: PortedKernel, tgt):
        """The jitted vmapped executable for (kernel, target).

        Compiles down the batched rungs (revec at the chip's tile
        first, then narrow) with bounded transient retry and the
        process-wide breaker: a rung whose breaker is open is skipped
        without an attempt, and a success closes it again.  Raises a
        typed :class:`PortError` only when every batched rung is out —
        the caller then degrades to per-row recovery."""
        pk = (id(kernel), tgt)
        with self._lock:
            prog = self._programs.get(pk)
        if prog is not None:
            return prog
        brk = _resilience.breaker()
        rungs = (["compiled+revec", "compiled"] if self.revec
                 else ["compiled"])
        last_err: Optional[PortError] = None
        for rung in rungs:
            bkey = (kernel.fn.name, tgt.name, rung)
            if brk.is_open(bkey):
                continue
            retries = 0
            while True:
                try:
                    # eager (jit=False) compile from the process-wide
                    # LRU; the jit wraps the *vmapped* callable so one
                    # executable serves the whole batch
                    eager = self._eager(kernel, tgt,
                                        rung == "compiled+revec")
                    batched = jax.vmap(eager)
                    batched.__name__ = _program_name(kernel.fn.name,
                                                     tgt.name)
                    prog = jax.jit(batched)
                except Exception as exc:    # noqa: BLE001 — serve seam
                    err = _resilience.wrap_error(
                        exc, stage="compile", kernel=kernel.fn.name,
                        target=tgt.name)
                    if err.transient and retries < self.compile_retries:
                        retries += 1
                        continue
                    brk.failure(bkey)
                    last_err = err
                    break
                brk.success(bkey)
                rt = eager.retiling
                strip = (rt.strip_elems if rt is not None else
                         max((s.step for s in revec.strip_loops(kernel.fn)),
                             default=0))
                with self._lock:
                    self._programs[pk] = prog
                    self._strips[pk] = strip
                    if rung != rungs[0]:
                        self._stats["program_fallbacks"] += 1
                    if rt is not None and rt.strips and \
                            rt.tiled == rt.strips:
                        self._stats["chip_width_programs"] += 1
                return prog
        if last_err is not None:
            raise last_err
        raise LadderExhausted(
            "every batched compile rung is quarantined",
            kernel=kernel.fn.name, target=tgt.name)

    # -- serving -----------------------------------------------------------

    def submit(self, requests: Sequence[Request]) -> List[Any]:
        """Run a slate of requests; returns results in request order,
        each exactly what calling the kernel directly would return (one
        array, or a tuple for multi-output kernels).

        A request that cannot be served — its deadline passed, or every
        ladder rung failed — resolves to its typed :class:`PortError`
        in the results list (``on_error="return"``); the rest of the
        slate is unaffected."""
        t0 = time.monotonic()
        slate = next(self._slates)
        with _span("port.submit", slate=slate,
                   requests=len(requests)) as span:
            groups: Dict[Any, List[int]] = {}
            plans = []
            with _span("port.plan"):
                for idx, req in enumerate(requests):
                    key, tgt, lens = self._plan(req)
                    plans.append((key, tgt, lens))
                    groups.setdefault(key, []).append(idx)
            span.set_metadata(groups=len(groups))
            results: List[Any] = [None] * len(requests)
            for key, members in groups.items():
                for lo in range(0, len(members), self.max_batch):
                    chunk = members[lo:lo + self.max_batch]
                    self._run_chunk(requests, plans, chunk, results, t0,
                                    slate)
        self._bump("requests", len(requests))
        return results

    def __call__(self, requests: Sequence[Request]) -> List[Any]:
        return self.submit(requests)

    def _deadline_missed(self, req: Request, t0: float) -> bool:
        return (req.deadline_s is not None and
                time.monotonic() - t0 >= req.deadline_s)

    def _run_chunk(self, requests, plans, chunk, results, t0, slate):
        # Expired requests resolve before any compile/launch work; they
        # never hold up their batch-mates.
        live = []
        for idx in chunk:
            if self._deadline_missed(requests[idx], t0):
                self._bump("deadline_misses")
                err = DeadlineExceeded(
                    f"deadline of {requests[idx].deadline_s}s passed "
                    f"before the batch launched",
                    kernel=requests[idx].kernel.fn.name)
                results[idx] = self._resolve_error(err)
            else:
                live.append(idx)
        chunk = live
        if not chunk:
            return
        kernel = requests[chunk[0]].kernel
        key, tgt, lens = plans[chunk[0]]
        with _span("port.chunk", slate=slate, kernel=kernel.fn.name,
                   target=tgt.name, bucket=key[2],
                   rows=len(chunk)) as span:
            self._run_live_chunk(requests, chunk, kernel, tgt, lens,
                                 results, t0, span)

    def _run_live_chunk(self, requests, chunk, kernel, tgt, lens,
                        results, t0, span):
        """Pad, transfer, launch, fetch and slice back one chunk whose
        rows are all live; ``span`` (the chunk's) gets the program's
        ``strip``."""
        model = self._model(kernel)
        params = kernel.fn.params
        B = self.max_batch

        with _span("port.pad"):
            host = []
            for i, p in enumerate(params):
                if isinstance(p.type, PtrType):
                    dt = np.asarray(requests[chunk[0]].args[i]).dtype
                    col = np.zeros((B, lens[i]), dtype=dt)
                    for r, idx in enumerate(chunk):
                        a = np.asarray(requests[idx].args[i])
                        col[r, :len(a)] = a
                    host.append(col)
                else:
                    vals = [requests[idx].args[i] for idx in chunk]
                    # inert padding rows: n = 0 makes every trip count
                    # zero, so the zero buffers are never touched
                    pad_val = 0 if i == model.counter else vals[0]
                    vals = vals + [pad_val] * (B - len(chunk))
                    host.append(np.asarray(vals))
        with _span("port.h2d"):
            cols = [jnp.asarray(c) for c in host]

        shape_sig = (id(kernel), tgt, tuple(lens))
        with self._lock:
            new_program = shape_sig not in self._shapes_seen
            self._shapes_seen.add(shape_sig)
            self._stats["batches"] += 1
            self._stats["inert_rows"] += B - len(chunk)
            self._stats["h2d_bytes"] += sum(c.nbytes for c in cols)

        try:
            # dispatch only: the program runs on the device while the
            # host waits in port.fetch
            with _span("port.launch", new_program=int(new_program)):
                _fi.fault_point("engine.batch", kernel=kernel.fn.name,
                                target=tgt.name)
                prog = self._program(kernel, tgt)
                span.set_metadata(strip=self._strips[(id(kernel), tgt)])
                outs = prog(*cols)
        except Exception as exc:    # noqa: BLE001 — degrade, never corrupt
            self._bump("batch_faults")
            err = _resilience.wrap_error(
                exc, stage="execute", kernel=kernel.fn.name,
                target=tgt.name)
            self._fallback_rows(requests, chunk, tgt, results, t0, err)
            return
        writes = kernel.fn.writes
        if len(writes) == 1:
            outs = (outs,)
        # one device->host transfer per output column; per-row numpy
        # slices are free views (vs 32 traced jax slice dispatches)
        with _span("port.fetch"):
            outs = tuple(np.asarray(o) for o in outs)
        with _span("port.slice"):
            out_params = [i for i, p in enumerate(params)
                          if isinstance(p.type, PtrType) and p.hint in writes]
            payload = 0
            for r, idx in enumerate(chunk):
                per_req = []
                for out, pi in zip(outs, out_params):
                    orig_len = len(requests[idx].args[pi])
                    per_req.append(out[r, :orig_len])
                    payload += orig_len
                results[idx] = (per_req[0] if len(per_req) == 1
                                else tuple(per_req))
            with self._lock:
                self._stats["payload_elems"] += payload
                self._stats["padded_elems"] += len(chunk) * sum(
                    o.shape[1] for o in outs)
                self._stats["d2h_bytes"] += sum(o.nbytes for o in outs)

    def _fallback_rows(self, requests, chunk, tgt, results, t0, batch_err):
        """Per-row recovery when the batched executable is unavailable:
        each live request descends the full degradation ladder on its
        own (conformance-identical output, just slower).  A row whose
        ladder also exhausts resolves to its typed error."""
        with _span("port.fallback", rows=len(chunk)):
            for idx in chunk:
                req = requests[idx]
                if self._deadline_missed(req, t0):
                    self._bump("deadline_misses")
                    err = DeadlineExceeded(
                        f"deadline of {req.deadline_s}s passed during "
                        f"batch-fault recovery", kernel=req.kernel.fn.name)
                    err.__cause__ = batch_err
                    results[idx] = self._resolve_error(err)
                    continue
                remaining = None
                if req.deadline_s is not None:
                    remaining = max(0.0, req.deadline_s -
                                    (time.monotonic() - t0))
                try:
                    out, _rec = _resilience.run_resilient(
                        req.kernel, *req.args, target=tgt, policy=self.policy,
                        revec=self.revec, jit=False, deadline_s=remaining,
                        compile_retries=self.compile_retries)
                except PortError as err:
                    results[idx] = self._resolve_error(err)
                    continue
                self._bump("row_fallbacks")
                if isinstance(out, tuple):
                    results[idx] = tuple(np.asarray(o) for o in out)
                else:
                    results[idx] = np.asarray(out)

    def _resolve_error(self, err: PortError):
        self._bump("errors_returned")
        if self.on_error == "raise":
            raise err
        return err

    # -- deploy hooks ------------------------------------------------------

    def warmup(self, corpus, targets: Sequence[Any] = ()) -> Dict[str, int]:
        """Pre-populate the compile cache for a deploy: eager
        (``jit=False``) compiles of every corpus kernel for every
        target — the cheap shape-probing pass that burns in lowering
        selections without paying XLA compiles up front.

        ``corpus`` is a dict (name -> PortedKernel, as returned by
        :func:`repro.port.load_corpus`) or an iterable of kernels;
        ``targets`` defaults to the engine's own target.

        On a ``tuned=True`` engine every warmup compile consults the
        persisted autotuning cache, so the deploy's executables start
        at the tuned (LMUL, retile-factor, tail) configuration without
        re-measuring anything.
        """
        kernels = (corpus.values() if isinstance(corpus, dict) else corpus)
        kernels = list(kernels)
        tgts = [_targets.resolve_target(t) for t in targets] or \
               [_targets.resolve_target(self.target)]
        n = 0
        for k in kernels:
            self._model(k)          # derive the padding rules up front
            for t in tgts:
                self._eager(k, t, self.revec)
                n += 1
        return {"kernels": len(kernels), "targets": len(tgts),
                "compiles": n}

    # -- observability -----------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        """Serving counters.  ``batch_programs`` counts distinct
        (kernel, target, canonical shape) signatures — the number of
        XLA executables this engine has demanded, bounded by
        buckets x targets x kernels.  ``chip_width_programs`` counts
        the batched (kernel, target) programs whose strips all run at
        the chip's tile."""
        from repro import port as _port
        with self._lock:
            s = dict(self._stats)
            s["batch_programs"] = len(self._shapes_seen)
        s["pad_overhead"] = (
            0.0 if s["payload_elems"] == 0
            else s["padded_elems"] / s["payload_elems"] - 1.0)
        s["compile_cache"] = _port.compiled_cache_info()
        s["resilience"] = {
            "batch_faults": s["batch_faults"],
            "row_fallbacks": s["row_fallbacks"],
            "errors_returned": s["errors_returned"],
            "deadline_misses": s["deadline_misses"],
            "program_fallbacks": s["program_fallbacks"],
            "breaker_open": [list(k) for k in
                             _resilience.breaker().open_keys()],
            "ladder": _resilience.resilience_stats(),
        }
        return s

    def cache_info(self) -> Dict[str, int]:
        """The process-wide CompiledKernel LRU counters (shared across
        engines — see :func:`repro.port.compiled_cache_info`)."""
        from repro import port as _port
        return _port.compiled_cache_info()
