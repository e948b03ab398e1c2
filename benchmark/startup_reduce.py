"""A replica's start-up ledger laid on the benchmark's own line.

The program keeps the ledger (ray_tpu/observability/profiling.py
``startup()``; /v1/stats ``startup``): the stages from the worker process's
creation to ready, a record for every program's first dispatch (wall, and
jax's own split of it into tracing, lowering and the backend's compile or
the persistent cache's load) and what compiled under no scope. All of it is
on ``time.monotonic()``, which on Linux is the machine's clock, so the
window's ``t0`` (the parent's ``time.monotonic()``) and the ledger lie on
one line: ``t0 - setup_s`` is the benchmark's process start, everything
before the ledger's ``created`` is the harness and the runtime above the
worker (``ray_tpu.init``, ``serve.run``'s controller and scheduling), and
the cell's ``ramp_s`` before ``t0`` is the closed loop running in.

The ledger read is ``stats_before``'s, taken as the window opens, so it
holds the warm-up requests' prefill and chunk programs too. A program
without the ledger (the commit before it) gives nothing to read: ``lay``
returns None, every reader returns None, nothing raises.
"""

from __future__ import annotations

from benchmark import common

# serve_cell opens the window this long after the ramp (``t0 = now +
# ramp_s + 0.05``)
RAMP_LEAD_S = 0.05
PARTS = ("trace_s", "lower_s", "compile_s", "load_s")


def ramp_s(run: dict) -> float:
    """The cell's ``ramp_s`` (0 for the open loop, which has none)."""
    try:
        _entry, cell, _config = common.load_cell(run["cell"])
    except (KeyError, OSError, StopIteration, common.BenchError):
        return 0.0
    if (run.get("report") or {}).get("rehearsal"):
        cell = cell.get("rehearsal", cell)
    return float(cell.get("traffic", {}).get("ramp_s", 0.0))


def _union_s(intervals: list[tuple[float, float]]) -> float:
    total, hi = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e > hi:
            total += e - max(s, hi)
            hi = e
    return total


def lay(run: dict) -> dict | None:
    """The ledger between the benchmark's process start and ``t0``:
    ``programs`` (first dispatched before ``t0``), ``stages`` ``(name,
    start, end)``, ``unscoped``, and the seconds of ``[t0 - setup_s, t0]``
    under no stage, no program and not the ramp (``untraced_s``), split
    into before the worker process, between its creation and ready, and
    after ready."""
    startup = (run.get("stats_before") or {}).get("startup")
    if not isinstance(startup, dict) or "programs" not in startup \
            or startup.get("created") is None:
        return None
    t0, setup_s = run["window"]["t0"], run["setup_s"]
    begin = t0 - setup_s
    programs = [p for p in startup["programs"] if p["t"] < t0]
    stages = [(n, s, s + d) for n, s, d in startup.get("stages") or []]
    ramp_from = t0 - ramp_s(run) - RAMP_LEAD_S
    seen = [(s, e) for _n, s, e in stages]
    seen += [(p["t"], p["t"] + p["wall_s"]) for p in programs]
    seen.append((ramp_from, t0))
    created = max(begin, min(startup["created"], t0))
    ready = startup.get("ready")
    ready = t0 if ready is None else max(created, min(ready, t0))

    def untraced(lo: float, hi: float) -> float:
        cut = [(max(s, lo), min(e, hi)) for s, e in seen
               if min(e, hi) > max(s, lo)]
        return max(0.0, (hi - lo) - _union_s(cut))

    return {"begin": begin, "t0": t0, "setup_s": setup_s,
            "programs": programs, "stages": stages,
            "unscoped": startup.get("unscoped") or {},
            "built_on": startup.get("built_on"),
            "untraced_s": untraced(begin, t0),
            "untraced_before_worker_s": untraced(begin, created),
            "untraced_between_stages_s": untraced(created, ready),
            "untraced_after_ready_s": untraced(ready, t0)}


def _mean_ms(programs: list[dict], keys: tuple) -> float | None:
    if not programs:
        return None
    return 1e3 * sum(p[k] for p in programs for k in keys) / len(programs)


def _load_ms(laid: dict) -> float | None:
    return _mean_ms([p for p in laid["programs"] if p["hit"]], ("load_s",))


def _lower_ms(laid: dict) -> float | None:
    return _mean_ms(laid["programs"], ("trace_s", "lower_s"))


def programs_share(run: dict) -> float | None:
    """100 x the wall seconds of the programs first dispatched before
    ``t0`` over ``setup_s``."""
    laid = lay(run)
    if laid is None or laid["setup_s"] <= 0:
        return None
    return 100.0 * sum(p["wall_s"] for p in laid["programs"]) \
        / laid["setup_s"]


def load_ms_per_program(run: dict) -> float | None:
    """Mean ``load_s`` (ms) over the programs before ``t0`` that came out
    of the persistent cache; None where none did (a cold start)."""
    laid = lay(run)
    return None if laid is None else _load_ms(laid)


def lower_ms_per_program(run: dict) -> float | None:
    """Mean ``trace_s + lower_s`` (ms) over the programs before ``t0``."""
    laid = lay(run)
    return None if laid is None else _lower_ms(laid)


def cache_misses(run: dict) -> float | None:
    """Programs before ``t0`` of which the backend compiled any part, and
    the executables compiled under no scope. 0 in a warm run."""
    laid = lay(run)
    if laid is None:
        return None
    return float(sum(1 for p in laid["programs"] if p["compile_s"] > 0)
                 + laid["unscoped"].get("misses", 0))


def untraced_share(run: dict) -> float | None:
    """100 x the seconds of ``[t0 - setup_s, t0]`` under no stage, no
    program and not the ramp, over ``setup_s``."""
    laid = lay(run)
    if laid is None or laid["setup_s"] <= 0:
        return None
    return 100.0 * laid["untraced_s"] / laid["setup_s"]


def detail(run: dict) -> str | None:
    """One line for standard error: where the seconds the ledger does not
    see lie, the stages, and what compiled under no scope."""
    laid = lay(run)
    if laid is None:
        return None
    by_stage: dict[str, float] = {}
    for n, s, e in laid["stages"]:
        by_stage[n] = by_stage.get(n, 0.0) + e - s
    un = laid["unscoped"]
    return ("startup: untraced {:.2f} s of {:.2f} (before the worker {:.2f}, "
            "between stages {:.2f}, after ready {:.2f}); stages {}; built on "
            "{}; {} programs, a program load_ms {} lower_ms {}; unscoped n={} "
            "hits={} misses={} {}").format(
        laid["untraced_s"], laid["setup_s"],
        laid["untraced_before_worker_s"], laid["untraced_between_stages_s"],
        laid["untraced_after_ready_s"],
        " ".join(f"{n}={s:.2f}" for n, s in by_stage.items()),
        laid["built_on"], len(laid["programs"]), _load_ms(laid),
        _lower_ms(laid), un.get("n"), un.get("hits"),
        un.get("misses"), " ".join(f"{p}={un.get(p)}" for p in PARTS))
