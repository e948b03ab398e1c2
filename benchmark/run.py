"""The benchmark's one command: one run of one cell.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell, its configuration, the configuration's model family, its
traffic generator and its metrics by name (BENCHMARK.json,
benchmark/workloads, configs, models, traffic, metrics); runs the cell
through the entry points users call; prints a
``{"report": ...}`` line and then, last, the result line:
``{"correct", "attempted", "failed", "metrics", "device"[, "breakdown"]}``.
With --trace 0 the metrics are the cell's end-to-end metrics, with
--trace 1 its per-layer metrics.

Without the chips the cell asks for it exits non-zero and prints no
result. ``--rehearsal`` walks the same control flow at the configuration's
tiny preset on the CPU: its line says ``"platform": "cpu"`` and
``"rehearsal": true`` and none of its numbers is a device number.

Adding a model family takes four new files and three manifest entries,
and no edit of a file that is here:
  benchmark/models/<family>.py     the adapter: published keys -> sizes
      (with ``attn_layers`` where not every layer calls the paged kernel),
      the program's model config and init (``model_config(sz,
      n_layers=depth)`` at a depth that holds every kind of layer), the
      reference's name and keywords, ``attention_backend`` and
      ``paged_programs`` (the four programs check 1 drives; the cache is
      the family's own pytree), the train functions, counts, scopes; for
      routed experts ``routing_taken`` (the docstring of the family that
      is there lists the names)
  benchmark/reference/<name>.py    the plain float32 reference; for
      routed experts it takes ``routing=`` and provides ``routing_slack``
      (contract: docstring of benchmark/reference/__init__.py)
  benchmark/configs/<config>.json  ``model_family``, ``published`` (the
      source's shape keys verbatim), the keys again as run, ``reduced``,
      ``engine`` (with ``tp_degree`` = the cell's chips) or ``trainer``,
      ``checks`` (for routed experts ``rms_tolerance``, ``routing_slack``
      and ``routing_flip_share_max`` beside ``tolerance``; where the block
      cannot take the Pallas kernel ``checks.logits.backend``: "gather"
      with its ``backend_why``), ``rehearsal``
  benchmark/workloads/<cell>.json  the traffic, for a generator that exists
and in BENCHMARK.json one entry each under ``configs`` and ``workloads``,
and the cell's name under the ``workloads`` of the metrics it reports.
tests/benchmark_suite/test_manifest.py does exactly this in a copy, and
test_routed_family.py puts a family with routed experts through the checks.
What the ENGINE can serve is another matter: it builds the dense block
only (PERF.md section 7).
"""

from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

DEADLINE_S = 1150.0        # a first run may take 1200 s, compiling


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--list", action="store_true",
                    help="print the cells BENCHMARK.json names and exit")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny preset on the CPU; proves nothing about a chip")
    args = ap.parse_args()

    try:
        if not os.path.isdir(os.path.join(ROOT, "ray_tpu")):
            raise ImportError("no ray_tpu/ beside benchmark/")
        import ray_tpu  # noqa: F401
    except ImportError as e:
        print(f"benchmark/run.py: the system under test is not here ({e}); "
              f"run from the root of a checkout", file=sys.stderr)
        return 2
    from benchmark import common

    if args.list or not args.workload:
        for w in common.manifest()["workloads"]:
            print(w["name"], w["config"], w["traffic"], w["chips"])
        return 0 if args.list else 2
    try:
        entry, cell, config = common.load_cell(args.workload)
    except (common.BenchError, OSError, StopIteration) as e:
        print(f"benchmark/run.py: {e}", file=sys.stderr)
        return 2
    man = common.manifest()
    if args.seconds is None:
        args.seconds = float(man["run_seconds"])

    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={entry['chips']}")
    else:
        from ray_tpu.parallel.topology import local_chip_count
        found = local_chip_count()
        if found < entry["chips"]:
            print(f"benchmark/run.py: the cell needs {entry['chips']} TPU "
                  f"chip(s), this machine has {found}; there is no CPU mode "
                  f"(--rehearsal is a rehearsal)", file=sys.stderr)
            return 3
    # every program, however quick to compile, goes into the persistent
    # cache, so that only a checkout's first run compiles
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")

    def out_of_time():
        print("benchmark/run.py: out of time", file=sys.stderr)
        common.kill_descendants()
        os._exit(4)

    watchdog = threading.Timer(DEADLINE_S, out_of_time)
    watchdog.daemon = True
    watchdog.start()
    try:
        waited = 0.0 if args.rehearsal else common.wait_chips_free(60.0)
        runner = {"serve": "serve_cell", "train": "train_cell"}[config["kind"]]
        mod = __import__(f"benchmark.{runner}", fromlist=["run"])
        run = mod.run(entry, cell, config, args, T_PROCESS)
        run["cell"], run["manifest"] = entry["name"], man
        run["family"] = common.family(config)
        if args.trace and run.get("trace_dir"):
            from benchmark import trace_reduce
            run["trace"] = trace_reduce.summarise(
                trace_reduce.read_dir(run["trace_dir"]))
            if run["trace"] is None and not args.rehearsal:
                raise common.BenchError(
                    "the traced run holds no device operation")
        group = "per_layer" if args.trace else "end_to_end"
        metrics, unread = {}, []
        for m in common.cell_metrics(man, entry["name"], group):
            try:
                value = common.load_module("metrics", m["name"]).reduce(run)
            except common.UnknownDevice:
                if not args.rehearsal:   # on the CPU a share of a peak
                    raise                # is left out, never made up
                value = None
            if value is None:
                unread.append(m["name"])
            else:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        end_wait = common.wait_children_gone(30.0)
        if not args.rehearsal:
            end_wait += common.wait_chips_free(30.0)
        if not common.parent_off_chip():
            raise common.BenchError("the parent initialised a jax backend")
    except common.BenchError as e:
        print(f"benchmark/run.py FAILED: {e}", file=sys.stderr)
        common.kill_descendants()
        return 1
    except SystemExit:
        common.kill_descendants()
        raise
    except BaseException:
        import traceback
        traceback.print_exc()
        common.kill_descendants()
        return 1
    finally:
        watchdog.cancel()

    device = dict(run["device"])
    attempted, failed = run_counts(run)
    result = {"correct": all(c["ok"] for c in run["checks"].values()),
              "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if args.rehearsal:
        result["rehearsal"] = True
    if args.trace and run.get("trace"):
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        result["breakdown"] = run["trace"]["breakdown"]
    report = dict(run.get("report") or {})
    report.update({
        "workload": entry["name"], "seed": args.seed, "seconds": args.seconds,
        "trace": args.trace, "checks": run["checks"],
        "child": run.get("child"), "metrics_not_read": unread,
        "waited_for_chips_s": {"at_start": waited, "at_end": end_wait},
        "extra": run.get("extra"),
        "wall_s": time.time() - T_PROCESS})
    for line in compared(run["checks"]):
        print(f"benchmark: {line}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps({"report": report}))
    print(json.dumps(result), flush=True)
    return 0


def compared(checks: dict) -> list[str]:
    """One line a check, the last lines of a run's standard error: every
    scalar of the check's dictionary under the check's own names, so each
    number compared lies beside its limit; a dictionary inside it one
    level down as ``<name>.<key>``, a list cut to 120 characters. No
    table of names: a check that brings a new number brings its line."""
    def flat(d: dict, prefix: str = ""):
        for k, v in d.items():
            if isinstance(v, dict):
                if not prefix:
                    yield from flat(v, f"{k}.")
            else:
                yield f"{prefix}{k} " + (repr(v)[:120] if isinstance(v, list)
                                         else repr(v))
    return [f"check {name}: " + "; ".join(flat(c))
            for name, c in checks.items()]


def run_counts(run: dict) -> tuple[int, int]:
    """(attempted, failed): requests sent in the window and those that
    errored (a stream the closed loop cut at the end of its cool-down is
    neither), or train steps."""
    if run["kind"] == "train":
        return len(run["train"]["window_step_s"]), 0
    recs = [r for r in run["records"] if not r.get("abandoned")]
    return len(recs), sum(1 for r in recs if r.get("error"))


if __name__ == "__main__":
    sys.exit(main())
