"""Engine performance introspection + cluster-wide on-demand XProf capture.

Three concerns the serving runtime attributes ITSELF (the MegaScale
argument: in-situ diagnostics are a precondition for operating a fleet —
offline benching found the 138 ms/step residual cost, production needs the
runtime to find the next one):

- **Phase spans** (`EngineProfiler.span`): the one way the engine loop
  times a phase. Every span feeds a bounded ring, a tagged Histogram and
  a running total (seconds, count) per phase; while an XProf capture is
  active in this process it is also a `jax.profiler.TraceAnnotation`
  named `rt/<phase>` with the span's arguments, so host phases land in
  the trace's host plane on the clock the device ops are on. Dispatch
  phases measure host-side dispatch cost (the loop never blocks on the
  device) and say whether the device had run `dry` before them;
  `harvest` is the WAIT for the device and nothing else
  (`block_until_ready` on the oldest in-flight entry, the GIL released),
  so device slowness shows up there, attributed, instead of smeared
  across the loop; `fetch`, its sibling, is what the host does next with
  the result (`np.asarray`, the routed counts); `loop_wait` is the loop
  parked with nothing to do.
- **Host stalls**: a span of host work (any but `loop_pass`, `loop_wait`,
  `harvest`) whose OWN time, what no child span covers, reaches
  `STALL_S` is a stall of the loop thread: `host_stall_s_total` /
  `host_stall_n`, a warning and a `loop_stall` journal event with the
  phase, its `seq`, the seconds and how many of them a full garbage
  collection took.
- **The garbage collector** (`watch_gc`): one process-wide `gc.callbacks`
  watch. Seconds and counts by generation always (`gc_pause_*` for full
  collections, `gc_young_*` for the rest); under a capture a full
  collection is also an `rt/gc` span on the line of the thread it ran in
  (any thread: a collection holds the GIL, so the loop stands still
  wherever it is); one of `STALL_S` or more is a `loop_stall` event of
  phase `gc`.
- **Compile-event tracking** (`compile_scope`): every jit entry point's
  first dispatch per static signature (prefill bucket, chunk length,
  decode width, verify width) is timed as a compile event and
  split into tracing, lowering and the backend's compile or, on a hit of
  the persistent cache, load (`_Parts`: jax's own monitoring events,
  attributed to the scope open on the thread that fired them).
  Compiles while traffic is in flight are the documented loop-stall
  failure class (engine.py `_warmup_decode_programs`): they're flagged
  `mid_traffic`, logged as warnings, and counted — a regression here is
  a serving-latency regression.
- **The start-up ledger** (`startup`): one a process. The stages from
  the process's creation to the replica ready (`STARTUP_STAGES`, each
  stamped where its work happens), every first dispatch's record, what
  compiled under no scope, and the thread that built the engine: "why
  did this replica take 140 s to come up", in `/v1/stats` `startup`.
- **Device-memory accounting**: weights / KV-pool byte gauges computed
  from array layouts, KV page occupancy, and the backend allocator's
  live/peak bytes when the platform reports them (`device.memory_stats()`
  — absent on the cpu backend, surfaced as None rather than guessed).

Plus the **capture controller**: a process-wide start/stop pair around
`jax.profiler` XPlane tracing, callable from an RPC handler, so
`ray-tpu profile --node <id>` captures a trace on any live worker and the
dashboard serves the artifact. The local context-manager helpers
(`profile_trace` / `annotate` / `dump_thread_stacks`) live here too.
"""

from __future__ import annotations

import collections
import contextlib
import gc
import logging
import os
import sys
import threading
import time
from typing import Optional

from ray_tpu.util import metrics as _metrics

logger = logging.getLogger(__name__)

# engine phases (the drift-guard test, the controller's key list and the
# README table key off this tuple — extend it and all follow).
# `queue_wait` is a per-request duration fed through `record`; the rest
# are spans on the engine-loop thread, nested by containment under
# `loop_pass` (`patch_flush` inside a dispatch; `restore`, `kv_tier_flush`
# only with the kv tier on; `harvest`, `fetch`, `emit` siblings, in that
# order, for one entry of the pipeline).
PHASES = ("queue_wait", "loop_pass", "admit", "restore", "prefill",
          "chunk_prefill", "decode_dispatch", "block_dispatch",
          "verify_dispatch", "patch_flush", "harvest", "fetch", "emit",
          "kv_tier_flush", "loop_wait")
# the stages of a replica's start, in order (README's table and PERF.md's
# layer row key off this tuple): the worker PROCESS's creation to its
# registration with the node agent (core/worker_main.py), that to the
# deployment's constructor (serve/llm/llm_server.py), then inside
# `LLMEngine.__init__` the first `import jax` + `jax.devices()`, the
# weights, their served form, the page pool and the device state beside
# it, `_warmup_decode_programs`, and the mark `LLMServer.__init__`
# returned. An engine built outside a worker has no first two.
STARTUP_STAGES = ("worker_boot", "actor_wait", "backend", "weights",
                  "serve_form", "pool", "warm_decode", "ready")
# the ledger's flat totals in `engine_stats()`, for exporters and the
# controller's and dashboard's kept keys (the nested `startup` is not)
STARTUP_TOTALS = ("startup_s", "startup_programs", "startup_cache_hits",
                  "startup_cache_misses", "startup_trace_s",
                  "startup_lower_s", "startup_load_s",
                  "startup_backend_compile_s")
# span names in the profiler's trace: "rt/<phase>"
SPAN_PREFIX = "rt/"
# host work on the loop thread takes microseconds to a few ms: a span
# whose own time reaches this, or a full collection that long on any
# thread, is a stall (a constant on purpose: one definition fleet-wide)
STALL_S = 0.050
# spans that wait by design, or whose time is their children's
_NOT_HOST_WORK = frozenset(("loop_pass", "loop_wait", "harvest"))

_PHASE_BOUNDS = (0.0001, 0.0003, 0.001, 0.003, 0.01, 0.03,
                 0.1, 0.3, 1.0, 3.0, 10.0)
_ITL_BOUNDS = (0.001, 0.003, 0.01, 0.03, 0.1, 0.3, 1.0, 3.0, 10.0)
_COMPILE_BOUNDS = (0.01, 0.03, 0.1, 0.3, 1.0, 3.0, 10.0, 30.0, 100.0)

PHASE_SECONDS = _metrics.Histogram(
    "ray_tpu_llm_engine_phase_seconds",
    "Engine loop time per phase (dispatch phases are host cost; harvest "
    "carries the device sync)", boundaries=_PHASE_BOUNDS,
    tag_keys=("phase",))
ITL_SECONDS = _metrics.Histogram(
    "ray_tpu_llm_itl_seconds",
    "Inter-token latency (host record-time gaps; pipelined harvests land "
    "in bursts of decode_block)", boundaries=_ITL_BOUNDS)
COMPILE_EVENTS = _metrics.Counter(
    "ray_tpu_llm_compile_events_total",
    "XLA compilations by jit entry point; mid_traffic=true ones stalled "
    "live requests", tag_keys=("kind", "mid_traffic"))
COMPILE_SECONDS = _metrics.Histogram(
    "ray_tpu_llm_compile_seconds",
    "Wall time of first-dispatch-per-signature (≈ trace+compile)",
    boundaries=_COMPILE_BOUNDS, tag_keys=("kind",))
DEVICE_MEMORY = _metrics.Gauge(
    "ray_tpu_llm_device_memory_bytes",
    "Device/HBM bytes by component (weights, kv_pool, in_use, peak)",
    tag_keys=("component",))
KV_OCCUPANCY = _metrics.Gauge(
    "ray_tpu_llm_kv_page_occupancy",
    "Fraction of KV pool pages held by live sequences (evictable cached "
    "prefix pages count as free — an alloc can reclaim them)")


def _pct(sorted_vals: list, q: float) -> float:
    """Interpolated percentile of an ascending list (non-empty)."""
    if len(sorted_vals) == 1:
        return sorted_vals[0]
    pos = q * (len(sorted_vals) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(sorted_vals) - 1)
    frac = pos - lo
    return sorted_vals[lo] * (1 - frac) + sorted_vals[hi] * frac


class _Noop:
    """Reusable no-op context manager (compile_scope fast path: the
    signature was already seen, so the per-dispatch cost is one set
    lookup and no allocation)."""

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NOOP = _Noop()


class _GcWatch:
    """The process's garbage collector, watched through `gc.callbacks`
    (`watch_gc` installs the one instance, once). Both callbacks of a
    collection run in the thread that tripped the threshold, holding the
    GIL, and collections do not nest: the totals have one writer at a
    time. They are not `EngineProfiler.record`'s (a collection runs in
    whichever thread allocates: an HTTP handler as soon as the loop).

    Nothing here logs or emits: a collection can start under any lock of
    the process (the journal's own among them). A full collection of
    `STALL_S` or more is left in `stall` for the engine loop's next span
    to report (`EngineProfiler._close`)."""

    __slots__ = ("pause_s", "pause_n", "young_s", "young_n", "pause_max_s",
                 "stall", "_t0", "_ann")

    def __init__(self):
        self.pause_s = 0.0        # full (gen-2) collections
        self.pause_n = 0
        self.young_s = 0.0        # gen 0 and 1
        self.young_n = 0
        self.pause_max_s = 0.0
        self.stall: Optional[tuple] = None    # (seconds, thread name)
        self._t0 = 0.0
        self._ann = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            if info["generation"] == 2 and _capture.active:
                # on the collecting thread's line of the host plane, on
                # the device ops' clock. Young collections get none:
                # thousands a trace. No import statement here (a capture
                # is jax's, so the module is loaded): a collection can
                # start in a thread that holds the import lock
                self._ann = sys.modules["jax"].profiler.TraceAnnotation(
                    SPAN_PREFIX + "gc", generation=2)
                self._ann.__enter__()
            self._t0 = time.perf_counter()
            return
        dt = time.perf_counter() - self._t0
        if info["generation"] < 2:
            self.young_s += dt
            self.young_n += 1
            return
        ann, self._ann = self._ann, None
        if ann is not None:
            # the thread by its Python name: the host plane's lines all
            # carry the process's
            ann.set_metadata(collected=info["collected"],
                             uncollectable=info["uncollectable"],
                             thread=threading.current_thread().name)
            ann.__exit__(None, None, None)
        self.pause_s += dt
        self.pause_n += 1
        self.pause_max_s = max(self.pause_max_s, dt)
        if dt >= STALL_S:
            self.stall = (dt, threading.current_thread().name)

    def stats(self) -> dict:
        """`gc_pause_*`: full collections (seconds, count, the longest
        since start); `gc_young_*`: generations 0 and 1."""
        return {"gc_pause_s_total": round(self.pause_s, 6),
                "gc_pause_n": self.pause_n,
                "gc_pause_max_ms": round(self.pause_max_s * 1e3, 3),
                "gc_young_s_total": round(self.young_s, 6),
                "gc_young_n": self.young_n}


_gc = _GcWatch()
_gc_install = threading.Lock()


def watch_gc() -> _GcWatch:
    """Install the process's collector watch (once, however many engines
    a process builds) and return it."""
    with _gc_install:
        if _gc not in gc.callbacks:
            gc.callbacks.append(_gc)
    return _gc


class _Span:
    """One timed phase of the engine loop (`EngineProfiler.span`). Spans
    of one profiler nest on one thread (the loop's): each knows its
    parent, so its own time is what no child covers."""

    __slots__ = ("_prof", "_name", "_ann", "_args", "_t0", "_parent",
                 "_child_s", "_gc0")

    def __init__(self, prof: "EngineProfiler", name: str, ann, args: dict):
        self._prof = prof
        self._name = name
        self._ann = ann
        self._args = args

    def set(self, **args) -> None:
        """Arguments known only once the phase has run (how many were
        admitted, how many tokens were emitted). A no-op unless a capture
        is recording this span."""
        if self._ann is not None:
            self._ann.set_metadata(**args)

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        prof = self._prof
        self._parent, prof._open = prof._open, self
        self._child_s = 0.0
        self._gc0 = _gc.pause_s
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        dt = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        self._prof._open = parent = self._parent
        if parent is not None:
            parent._child_s += dt
        self._prof._close(self, dt)
        return False


class _NoSpan(_Noop):
    """What `span` returns when nothing listens: profiling disabled and
    no capture active."""

    def __enter__(self):
        return self

    def set(self, **args) -> None:
        return None


_NO_SPAN = _NoSpan()


# jax's monitoring events a first dispatch is made of. Each duration
# event fires on the thread that did the work, as the work ends
_PART_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieve_s"}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
PARTS = ("trace_s", "lower_s", "compile_s", "load_s", "retrieve_s")


class _Parts:
    """What the compiles of one scope (or, under no scope, of one thread)
    were made of. One writer: the thread it belongs to.

    The parts are kept DISJOINT. An event is the interval that ends now
    and lasted its duration; events arrive innermost first, so those an
    event contains are the tail of `_stack`, and its own time is its
    duration less theirs. A nested jit's trace (jax reports each) thus
    adds up to the outermost trace alone, a function traced while
    another is lowered counts once, and the parts never exceed the wall
    time around them. `retrieve_s`, the cache's own read, lies inside
    `load_s` and is kept beside the sum."""

    __slots__ = ("trace_s", "lower_s", "compile_s", "load_s", "retrieve_s",
                 "hits", "misses", "names", "_stack", "_hit")

    def __init__(self, named: bool = False):
        self.trace_s = self.lower_s = self.compile_s = self.load_s = 0.0
        self.retrieve_s = 0.0
        self.hits = self.misses = 0
        # {function: backend compiles or loads} of the programs nobody
        # listed (the `unscoped` record alone keeps names)
        self.names: Optional[dict] = {} if named else None
        self._stack: list = []         # [(start, duration)]
        self._hit = False

    def add(self, part: str, duration: float, fun_name) -> None:
        if part == "retrieve_s":
            self.retrieve_s += duration
            return
        start = time.monotonic() - duration
        stack, inner = self._stack, 0.0
        while stack and stack[-1][0] >= start:
            inner += stack.pop()[1]
        stack.append((start, duration))
        own = max(0.0, duration - inner)
        if part == "backend":
            # the persistent cache says "hit" inside the interval this
            # event closes; a compile without a cache says nothing
            hit, self._hit = self._hit, False
            part = "load_s" if hit else "compile_s"
            if hit:
                self.hits += 1
            else:
                self.misses += 1
            names = self.names
            if names is not None and (fun_name in names or len(names) < 64):
                names[fun_name] = names.get(fun_name, 0) + 1
        setattr(self, part, getattr(self, part) + own)

    def as_dict(self) -> dict:
        return {p: round(getattr(self, p), 6) for p in PARTS}


_tls = threading.local()        # .scope: the _Parts of the open scope


def _on_duration(event: str, duration: float, **kw) -> None:
    """jax calls this from inside a trace, a lowering or a compile, on
    the thread that does it: whatever goes wrong here stays here."""
    part = _PART_OF.get(event)
    if part is not None:
        try:
            (getattr(_tls, "scope", None) or _startup.loose()).add(
                part, float(duration), kw.get("fun_name"))
        except Exception:  # noqa: BLE001 - never fail a compile
            pass


def _on_event(event: str, **kw) -> None:
    if event == _CACHE_HIT:
        try:
            (getattr(_tls, "scope", None) or _startup.loose())._hit = True
        except Exception:  # noqa: BLE001 - never fail a compile
            pass


def _process_created() -> float:
    """This process's creation on `time.monotonic()`, as the kernel has
    it (/proc/self/stat `starttime`, ticks of the boot clock; to 10 ms).
    Now, where /proc does not say."""
    now = time.monotonic()
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(") ", 1)[1].split()[19])
        age = time.clock_gettime(time.CLOCK_BOOTTIME) \
            - ticks / os.sysconf("SC_CLK_TCK")
        return now - age if age >= 0.0 else now
    except (OSError, ValueError, IndexError, AttributeError):
        return now


class _Stage:
    """One stage of the start, timed where it is entered and left."""

    __slots__ = ("_ledger", "_name", "_t0")

    def __init__(self, ledger: "_StartupLedger", name: str):
        self._ledger = ledger
        self._name = name

    def __enter__(self):
        self._t0 = time.monotonic()
        return None

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None:
            self._ledger.stamp(self._name, self._t0, time.monotonic())
        return False


class _StartupLedger:
    """A process's start-up (`startup` returns the one instance, made with
    the module like `_GcWatch`): stages, first dispatches, the compiles
    nobody listed, the constructing thread. Everything is on
    `time.monotonic()`, the machine's clock (`clock_s` is on it too), so a
    reader in another process lays the ledger on its own line.

    `view` is what `/v1/stats` carries as `startup`: built once, its lists
    appended to in place, so a poll copies a reference. The ledger closes
    to STAGES at the first `ready` (a second engine of the process is not
    a start); first dispatches are recorded for as long as the process
    lives, at most `MAX_PROGRAMS` of them."""

    MAX_PROGRAMS = 1024

    def __init__(self):
        self.created = _process_created()
        self.ready: Optional[float] = None
        self._lock = threading.Lock()
        self._listening = False
        self._loose: list[_Parts] = []      # one a thread, under no scope
        self.stages: list = []              # [name, start, seconds]
        self.programs: list = []
        # totals over the scoped first dispatches
        self._scoped = _Parts()
        self.view = {"created": round(self.created, 6), "ready": None,
                     "built_on": None, "stages": self.stages,
                     "programs": self.programs, "unscoped": {}}

    # ---- jax's events ---------------------------------------------------
    def listen(self) -> None:
        """Register the process's pair of `jax.monitoring` listeners, once
        (the engine calls this after its `import jax`; a worker that
        builds no engine never imports jax for it). jax fires the events
        only while something traces, lowers or compiles: a call of a
        compiled program fires none."""
        with self._lock:
            if self._listening:
                return
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(_on_duration)
            monitoring.register_event_listener(_on_event)
            self._listening = True

    def loose(self) -> _Parts:
        """The calling thread's record of what it compiles under no
        scope."""
        parts = getattr(_tls, "loose", None)
        if parts is None:
            parts = _tls.loose = _Parts(named=True)
            with self._lock:
                self._loose.append(parts)
        return parts

    # ---- stages ---------------------------------------------------------
    def stage(self, name: str) -> _Stage:
        return _Stage(self, name)

    def stamp(self, name: str, start: float, end: float) -> None:
        with self._lock:
            if self.ready is None:
                self.stages.append(
                    [name, round(start, 6), round(end - start, 6)])

    def stamp_since_last(self, name: str, after: str) -> None:
        """The stage from the end of stage `after` to now, where `after`
        is the last stage stamped (`actor_wait`: only a worker waits)."""
        with self._lock:
            last = self.stages[-1] if self.stages else None
        if last is not None and last[0] == after:
            self.stamp(name, last[1] + last[2], time.monotonic())

    def built_on(self) -> None:
        """The calling thread builds the engine."""
        th = threading.current_thread()
        self.view["built_on"] = [th.name, th is threading.main_thread()]

    def mark_ready(self) -> None:
        """The replica is ready: the mark that ends the stages, and the
        operator's line."""
        now = time.monotonic()
        with self._lock:
            if self.ready is not None:
                return
            self.stages.append(["ready", round(now, 6), 0.0])
            self.ready = now
            self.view["ready"] = round(now, 6)
        top = sorted(self.programs, key=lambda p: -p["wall_s"])[:3]
        tot = self.totals()
        logger.info(
            "replica ready %.1fs after its process began (built on %s): %s; "
            "%d first dispatches, executables %d loaded from the cache and "
            "%d compiled; largest %s",
            now - self.created, self.view["built_on"],
            ", ".join(f"{n} {s:.1f}s" for n, _t, s in self.stages[:-1]),
            tot["startup_programs"], tot["startup_cache_hits"],
            tot["startup_cache_misses"],
            ", ".join(f"{p['sig']} {p['wall_s']:.1f}s" for p in top))

    # ---- first dispatches -----------------------------------------------
    def record(self, rec: dict, parts: _Parts) -> None:
        with self._lock:
            if len(self.programs) < self.MAX_PROGRAMS:
                self.programs.append(rec)
            tot = self._scoped
            tot.hits += parts.hits
            tot.misses += parts.misses
            for p in PARTS:
                setattr(tot, p, getattr(tot, p) + getattr(parts, p))

    def unscoped(self) -> dict:
        """What compiled under no scope, over every thread: the parts,
        executables loaded and compiled, and which functions they were."""
        with self._lock:
            loose = list(self._loose)
        out = {p: round(sum(getattr(x, p) for x in loose), 6) for p in PARTS}
        out["hits"] = sum(x.hits for x in loose)
        out["misses"] = sum(x.misses for x in loose)
        names: dict = {}
        for x in loose:
            for k, v in list(x.names.items()):
                names[k] = names.get(k, 0) + v
        out["n"] = out["hits"] + out["misses"]
        out["names"] = names         # (at most 64 functions a thread)
        return out

    def totals(self, unscoped: Optional[dict] = None) -> dict:
        """The flat `startup_*` keys: scoped and unscoped together, since
        the process began (first dispatches after `ready` included)."""
        un = unscoped or self.unscoped()
        tot = self._scoped
        return {
            "startup_s": (round(self.ready - self.created, 3)
                          if self.ready is not None else None),
            "startup_programs": len(self.programs),
            "startup_cache_hits": tot.hits + un["hits"],
            "startup_cache_misses": tot.misses + un["misses"],
            "startup_trace_s": round(tot.trace_s + un["trace_s"], 3),
            "startup_lower_s": round(tot.lower_s + un["lower_s"], 3),
            "startup_load_s": round(tot.load_s + un["load_s"], 3),
            "startup_backend_compile_s": round(
                tot.compile_s + un["compile_s"], 3)}

    def stats(self) -> dict:
        """`startup` and the flat totals, for `engine_stats()`."""
        un = self.view["unscoped"] = self.unscoped()
        return {"startup": self.view, **self.totals(un)}


_startup = _StartupLedger()


def startup() -> _StartupLedger:
    """The process's start-up ledger."""
    return _startup


class _CompileScope:
    """A signature's first dispatch: its wall time, and what jax says it
    was made of (the events that fire on this thread while the scope is
    open). Under a capture also the span `rt/compile` on this thread's
    line, so an idle gap a first use caused reads `compile` in a trace."""

    __slots__ = ("_prof", "_kind", "_sig", "_mid", "_parts", "_outer",
                 "_ann", "_t", "_t0")

    def __init__(self, prof: "EngineProfiler", kind: str, sig,
                 mid_traffic: bool):
        self._prof = prof
        self._kind = kind
        self._sig = sig
        self._mid = mid_traffic

    def __enter__(self):
        self._ann = None
        if _capture.active:
            self._ann = sys.modules["jax"].profiler.TraceAnnotation(
                SPAN_PREFIX + "compile", kind=self._kind, sig=str(self._sig))
            self._ann.__enter__()
        self._parts = _Parts()
        self._outer = getattr(_tls, "scope", None)
        _tls.scope = self._parts
        self._t = time.monotonic()
        self._t0 = time.perf_counter()
        return None

    def __exit__(self, exc_type, exc, tb):
        dt = time.perf_counter() - self._t0
        _tls.scope = self._outer
        rec = None
        if exc_type is None:
            rec = self._prof._record_compile(
                self._kind, self._sig, dt, self._mid, self._t, self._parts)
        if self._ann is not None:
            if rec is not None:
                self._ann.set_metadata(**{
                    k: rec[k] for k in ("hit", "trace_s", "lower_s")},
                    **{k: rec[k] for k in ("compile_s", "load_s") if rec[k]})
            self._ann.__exit__(exc_type, exc, tb)
        return False


class EngineProfiler:
    """Per-engine introspection state: phase rings and totals, compile
    tracker, ITL ring, memory layout. All mutating entry points are cheap
    enough to sit on the engine loop's hot path; `enabled=False` reduces
    phase/ITL recording to an attribute check (two for a span: this one
    and the capture flag). Compile tracking stays on either way — it only
    does work on the FIRST dispatch of a new signature, and a silent
    mid-traffic compile is exactly what this exists to catch."""

    def __init__(self, enabled: bool = True, ring_size: int = 256,
                 itl_ring_size: int = 2048):
        self.enabled = bool(enabled)
        self._lock = threading.Lock()
        self._rings: dict[str, collections.deque] = {
            p: collections.deque(maxlen=ring_size) for p in PHASES}
        # running totals per phase: [seconds, count]. Written by the loop
        # thread alone (`queue_wait` included: the admission pass records
        # it), read by engine_stats(); deltas over any interval give host
        # time by phase. The collector's totals are NOT here: a
        # collection runs in any thread (`_GcWatch`).
        self._totals: dict[str, list] = {p: [0.0, 0] for p in PHASES}
        # the innermost open span (the loop thread's), host stalls
        # (spans of host work whose own time reached STALL_S) and when
        # one was last reported
        self._open: Optional[_Span] = None
        self.host_stall_s = 0.0
        self.host_stall_n = 0
        self._stall_told = 0.0
        self._gc = watch_gc()
        self._itl: collections.deque = collections.deque(maxlen=itl_ring_size)
        self._seen: set = set()
        self.compile_events = 0
        self.mid_traffic_compiles = 0
        self.compile_s = 0.0
        # memory layout (set once by the engine after weights/pool init)
        self.weights_bytes = 0
        self.kv_pool_bytes = 0

    # ---- phase timers --------------------------------------------------
    def record(self, phase: str, dt: float) -> None:
        """One sample of a phase. One writer: the loop thread."""
        if not self.enabled:
            return
        self._rings[phase].append(dt)
        tot = self._totals[phase]
        tot[0] += dt
        tot[1] += 1
        PHASE_SECONDS.observe(dt, {"phase": phase})

    def span(self, name: str, **args):
        """Context manager around one phase of the engine loop: a ring
        sample, a histogram observation and the phase's running total
        when `enabled`; while a capture is active in this process, also a
        `TraceAnnotation("rt/<name>", **args)` in the profiler's trace.
        Enter and leave it OUTSIDE the engine lock (the histogram
        observation must not run under it). `.set(**args)` on the
        returned span adds arguments known only at the end."""
        if _capture.active:
            import jax

            return _Span(self, name, jax.profiler.TraceAnnotation(
                SPAN_PREFIX + name, **args), args)
        if not self.enabled:
            return _NO_SPAN
        return _Span(self, name, None, args)

    def _close(self, span: _Span, dt: float) -> None:
        """A span has ended: its sample, and the stall it may have been.
        Own time = what no child span covers (a slow `prefill` is not
        counted again as its parent `admit`)."""
        if not self.enabled:
            return
        self.record(span._name, dt)
        own = dt - span._child_s
        if own >= STALL_S and span._name not in _NOT_HOST_WORK:
            self.host_stall_s += own
            self.host_stall_n += 1
            self._tell_stall(span._name, own, span._args.get("seq"),
                             self._gc.pause_s - span._gc0)
        if self._gc.stall is not None:
            # a full collection on ANY thread holds the GIL: the loop
            # stood still wherever it was. Reported here, not from the
            # collector's callback (see _GcWatch)
            (seconds, thread), self._gc.stall = self._gc.stall, None
            self._tell_stall("gc", seconds, None, seconds, thread=thread)

    def _tell_stall(self, phase: str, seconds: float, seq, gc_s: float,
                    **more) -> None:
        """What an operator has when a replica's throughput dips: the
        phase, the second, and whether it was the collector. At most one
        warning and one `loop_stall` journal event a second."""
        now = time.monotonic()
        if now - self._stall_told < 1.0:
            return
        self._stall_told = now
        logger.warning(
            "engine loop stalled %.3fs in %s (seq=%s%s), %.3fs of it in full "
            "garbage collections", seconds, phase, seq,
            "".join(f", {k}={v}" for k, v in more.items()), gc_s)
        from ray_tpu.observability import events as _fr
        _fr.emit("loop_stall", "WARNING", reason=phase,
                 attrs={"phase": phase, "seq": seq,
                        "seconds": round(float(seconds), 4),
                        "gc_s": round(float(gc_s), 4), **more})

    def stall_stats(self) -> dict:
        """`host_stall_*` of this engine's loop and the process's
        `gc_*` totals."""
        return {"host_stall_s_total": round(self.host_stall_s, 6),
                "host_stall_n": self.host_stall_n, **self._gc.stats()}

    def record_itl(self, gap_s: float) -> None:
        if not self.enabled:
            return
        self._itl.append(gap_s)
        ITL_SECONDS.observe(gap_s)

    def phase_stats(self) -> dict:
        """Per phase `phase_<name>_p50_ms` / `_p95_ms` over the ring (None
        where no samples exist yet, or profiling is disabled) and the
        running totals `phase_<name>_s_total` / `phase_<name>_n`; plus
        `itl_s` (p50)."""
        out: dict[str, Optional[float]] = {}
        for p in PHASES:
            vals = sorted(self._rings[p])
            out[f"phase_{p}_p50_ms"] = (
                round(_pct(vals, 0.5) * 1e3, 4) if vals else None)
            out[f"phase_{p}_p95_ms"] = (
                round(_pct(vals, 0.95) * 1e3, 4) if vals else None)
            seconds, n = self._totals[p]
            out[f"phase_{p}_s_total"] = round(seconds, 6)
            out[f"phase_{p}_n"] = n
        itl = sorted(self._itl)
        out["itl_s"] = round(_pct(itl, 0.5), 6) if itl else None
        return out

    # ---- compile tracking ----------------------------------------------
    def compile_scope(self, kind: str, sig, mid_traffic: bool = False):
        """Context manager around a jit entry point's dispatch. First use
        of ``sig`` is timed and counted as a compile event; later uses
        return a shared no-op. ``mid_traffic`` should be True when any
        request has been submitted — such a compile stalled live work."""
        if sig in self._seen:
            return _NOOP
        return _CompileScope(self, kind, sig, mid_traffic)

    def compile_count(self, kinds) -> int:
        """Compiled-program count for the given scope kinds (each sig's
        first element is its kind — e.g. ("decode", w)). Feeds the
        per-kernel compile counters in engine_stats(): with warmup on,
        this number is reached before traffic and must then stay flat
        (the compile-once contract of a width's program)."""
        kinds = tuple(kinds)
        with self._lock:
            return sum(1 for s in self._seen
                       if isinstance(s, tuple) and s and s[0] in kinds)

    def _record_compile(self, kind: str, sig, dt: float, mid_traffic: bool,
                        t: Optional[float] = None,
                        parts: Optional[_Parts] = None) -> Optional[dict]:
        """A first dispatch has ended: the counters, the two metric
        families, and its record in the process's start-up ledger (None
        where another thread recorded the signature first)."""
        with self._lock:
            if sig in self._seen:
                return None
            self._seen.add(sig)
            self.compile_events += 1
            self.compile_s += dt
            if mid_traffic:
                self.mid_traffic_compiles += 1
        COMPILE_EVENTS.inc(1, {"kind": kind,
                               "mid_traffic": str(bool(mid_traffic)).lower()})
        COMPILE_SECONDS.observe(dt, {"kind": kind})
        parts = parts or _Parts()
        split = parts.as_dict()
        rec = {"kind": kind,
               "sig": list(sig) if isinstance(sig, (tuple, list))
               else [str(sig)],
               "t": round(time.monotonic() - dt if t is None else t, 6),
               "wall_s": round(dt, 6), **split,
               # what is left of the wall: the dispatch (the execution is
               # asynchronous and is not waited for)
               "rest_s": round(max(0.0, dt - sum(
                   split[p] for p in PARTS if p != "retrieve_s")), 6),
               # every executable of the scope came out of the cache
               "hit": int(parts.hits > 0 and parts.misses == 0),
               "thread": threading.current_thread().name,
               "mid_traffic": int(bool(mid_traffic))}
        _startup.record(rec, parts)
        if mid_traffic:
            logger.warning(
                "mid-traffic compile: kind=%s sig=%s took %.2fs — every "
                "active generation stalled for it (warm this program at "
                "startup, see engine warmup_compile)", kind, sig, dt)
            # off-box visibility (ISSUE 19): a WARNING journal event
            # carrying the compile signature and its parts. Warmup
            # compiles (mid_traffic=False) emit nothing — the regression
            # test holds that line.
            from ray_tpu.observability import events as _fr
            _fr.emit("mid_traffic_compile", "WARNING",
                     reason=kind,
                     attrs={"kind": kind, "sig": rec["sig"],
                            "seconds": round(float(dt), 4),
                            "hit": rec["hit"], **split})
        return rec

    # ---- memory accounting ---------------------------------------------
    def set_memory_layout(self, weights_bytes: int,
                          kv_pool_bytes: int) -> None:
        self.weights_bytes = int(weights_bytes)
        self.kv_pool_bytes = int(kv_pool_bytes)
        DEVICE_MEMORY.set(self.weights_bytes, {"component": "weights"})
        DEVICE_MEMORY.set(self.kv_pool_bytes, {"component": "kv_pool"})

    def memory_stats(self, used_pages: Optional[int] = None,
                     total_pages: Optional[int] = None,
                     devices=None) -> dict:
        occ = None
        if used_pages is not None and total_pages:
            occ = round(used_pages / total_pages, 4)
            KV_OCCUPANCY.set(occ)
        in_use, peak = device_memory_stats(devices)
        if in_use is not None:
            DEVICE_MEMORY.set(in_use, {"component": "in_use"})
        if peak is not None:
            DEVICE_MEMORY.set(peak, {"component": "peak"})
        return {"weights_bytes": self.weights_bytes,
                "kv_pool_bytes": self.kv_pool_bytes,
                "kv_page_occupancy": occ,
                "device_bytes_in_use": in_use,
                "device_peak_bytes": peak}


def tree_bytes(tree) -> int:
    """Total bytes of every array leaf in a pytree (weights / KV pool
    sizing; shape*dtype math, no device round trip)."""
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        nbytes = getattr(leaf, "nbytes", None)
        if nbytes is None:
            size = getattr(leaf, "size", None)
            itemsize = getattr(getattr(leaf, "dtype", None), "itemsize", None)
            nbytes = size * itemsize if size and itemsize else 0
        total += int(nbytes)
    return total


def device_memory_stats(devices=None) -> tuple[Optional[int], Optional[int]]:
    """(bytes_in_use, peak_bytes_in_use) of the FULLEST of ``devices``
    (default: the process default device) — one chip's view, the one
    closest to its HBM limit; a tensor-parallel replica passes every chip
    of its mesh so device 0 is not read as the whole replica. (None, None)
    where the allocator reports nothing (the cpu backend)."""
    import jax

    if devices is None:
        devices = jax.devices()[:1]
    stats = [s for s in (d.memory_stats() for d in devices) if s]
    if not stats:
        return None, None
    return (max(s.get("bytes_in_use", 0) for s in stats),
            max(s.get("peak_bytes_in_use", 0) for s in stats))


# ---------------------------------------------------------------------------
# on-demand XPlane capture (remote-drivable: worker RPC handlers call these)
# ---------------------------------------------------------------------------

def _profile_options():
    """What every capture of this module is taken with. No Python frames:
    the default capture hooks every Python call of every thread of the
    process (the engine loop's and each request handler's) and so slows
    the host it measures: a replica streaming 2,700 tokens/s left its
    chip idle 12.7 % of a traced span for it, 0.03 % without (PERF.md,
    PR 35). What reads a capture (benchmark/span_reduce.py) reads the
    rt/ spans and the device planes."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    return options


class CaptureController:
    """Process-wide start/stop around `jax.profiler` tracing. jax allows
    ONE active trace per process, so this serializes: a second start while
    active raises instead of corrupting the run. `active` is the plain
    attribute `EngineProfiler.span` reads to decide whether to annotate."""

    def __init__(self):
        self._lock = threading.Lock()
        self._logdir: Optional[str] = None
        self._started_at: Optional[float] = None
        self.active = False

    def start(self, logdir: Optional[str] = None) -> dict:
        import jax

        with self._lock:
            if self._logdir is not None:
                raise RuntimeError(
                    f"capture already active (logdir={self._logdir})")
            if not logdir:
                logdir = os.path.join(
                    "/tmp", "ray_tpu_xprof", str(int(time.time())))
            # a subdirectory of its own per process: the profiler names
            # its file by host and second, so two workers given one
            # directory would overwrite each other's trace
            logdir = os.path.join(logdir, str(os.getpid()))
            os.makedirs(logdir, exist_ok=True)
            jax.profiler.start_trace(logdir, create_perfetto_link=False,
                                     profiler_options=_profile_options())
            self._logdir = logdir
            self._started_at = time.time()
            self.active = True
            return {"logdir": logdir, "pid": os.getpid()}

    def stop(self) -> dict:
        import jax

        with self._lock:
            if self._logdir is None:
                raise RuntimeError("no capture active")
            self.active = False
            jax.profiler.stop_trace()
            logdir, self._logdir = self._logdir, None
            dur = time.time() - (self._started_at or time.time())
            self._started_at = None
        return {"logdir": logdir, "duration_s": round(dur, 3),
                "pid": os.getpid()}


_capture = CaptureController()


def start_capture(logdir: Optional[str] = None) -> dict:
    return _capture.start(logdir)


def stop_capture() -> dict:
    return _capture.stop()


def save_device_memory_profile(path: Optional[str] = None) -> str:
    """pprof device-memory dump — the 'why is my model OOMing' tool.
    RPC-friendly default path when none is given."""
    import jax

    if not path:
        path = os.path.join(
            "/tmp", "ray_tpu_xprof",
            f"memory-{int(time.time())}-{os.getpid()}.prof")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    jax.profiler.save_device_memory_profile(path)
    return path


# ---------------------------------------------------------------------------
# local context-manager helpers (driver/train-fn ergonomics; exported as
# ray_tpu.util.profile_trace / annotate)
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def profile_trace(logdir: str):
    """Capture an XPlane trace of everything inside the block: device
    planes and `annotate` regions, no Python frames (`_profile_options`).

    Usage (inside a train fn)::

        with profile_trace("/tmp/prof"):
            for _ in range(10):
                state, metrics = step(state, batch)
        # then: tensorboard --logdir /tmp/prof  (Profile tab)
    """
    import jax

    os.makedirs(logdir, exist_ok=True)
    jax.profiler.start_trace(logdir, create_perfetto_link=False,
                             profiler_options=_profile_options())
    try:
        yield logdir
    finally:
        jax.profiler.stop_trace()


def annotate(name: str):
    """Named region inside a profile_trace (shows as a span in XProf).
    Usage: `with annotate("data-load"): ...`"""
    import jax

    return jax.profiler.TraceAnnotation(name)


def dump_thread_stacks() -> str:
    """Every thread's Python stack as text (named), for on-demand hang
    diagnosis (ref: dashboard/modules/reporter/profile_manager.py:191 —
    the reference shells out to py-spy; a pure-Python snapshot needs no
    debugger attach and works from an RPC handler)."""
    import sys
    import threading as _threading
    import traceback

    names = {t.ident: t.name for t in _threading.enumerate()}
    out = []
    for tid, frame in sys._current_frames().items():
        out.append(f"--- thread {names.get(tid, '?')} ({tid})\n"
                   + "".join(traceback.format_stack(frame)))
    return "\n".join(out)
