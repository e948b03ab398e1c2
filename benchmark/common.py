"""What every part of the harness shares: finding files by the names in
BENCHMARK.json, reading a configuration, process hygiene, the result line.

Nothing here imports jax: the parent process of a run never initialises a
backend (one process holds a chip at a time).
"""

from __future__ import annotations

import functools
import glob
import importlib.util
import json
import os
import signal
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BYTE_BOS, BYTE_EOS = 256, 257     # ray_tpu's ByteTokenizer specials


class BenchError(Exception):
    """The run cannot produce a result line (exit non-zero, print none)."""


class UnknownDevice(KeyError):
    """peaks.json has no row for this device_kind."""


def manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def bench_dir(root: str = ROOT) -> str:
    return os.path.join(root, "benchmark")


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> tuple[dict, dict, dict]:
    """(manifest entry, cell file, configuration file) of one cell."""
    man = manifest(root)
    entry = next((w for w in man["workloads"] if w["name"] == name), None)
    if entry is None:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    cell = load_json(bench_dir(root), "workloads", f"{name}.json")
    cfg_entry = next(c for c in man["configs"] if c["name"] == entry["config"])
    config = load_json(root, cfg_entry["file"])
    return entry, cell, config


@functools.lru_cache(maxsize=None)
def load_module(kind: str, name: str, root: str = ROOT):
    """benchmark/<kind>/<name>.py, loaded once a process (a reference's
    jitted functions keep their compiled programs); for a metric ``a.b``
    without a file of its own, the family's ``a.py`` (one reader, read in
    several cells)."""
    for cand in (name, name.split(".")[0]):
        path = os.path.join(bench_dir(root), kind, f"{cand}.py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                f"benchmark.{kind}.{cand.replace('.', '_').replace('-', '_')}",
                path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    raise BenchError(f"no benchmark/{kind}/{name}.py")


def family(config: dict, root: str = ROOT):
    """The adapter of the model family a configuration's file names
    (``model_family``: benchmark/models/<family>.py). Everything the
    harness knows about a block it asks of this module."""
    if not config.get("model_family"):
        raise BenchError("the configuration's file states no model_family")
    return load_module("models", config["model_family"], root)


def reference(fam):
    """The plain reference a family's adapter names (benchmark/reference/
    __init__.py has the contract), from the checkout that holds the
    adapter (benchmark/models/<family>.py)."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(fam.__file__))))
    return load_module("reference", fam.REFERENCE, root)


def cell_metrics(man: dict, cell_name: str, group: str) -> list[dict]:
    """The metrics of ``group`` (end_to_end | per_layer) read in a cell."""
    return [m for m in man[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def section(config: dict, key: str, rehearsal: bool) -> dict:
    return dict((config["rehearsal"] if rehearsal else config)[key])


def peaks(device_kind: str) -> dict:
    table = load_json(HERE, "peaks.json")
    if device_kind not in table or device_kind.startswith("_"):
        raise UnknownDevice(
            f"no peaks known for device_kind={device_kind!r}; add it to "
            f"benchmark/peaks.json with its source")
    return table[device_kind]


# ---- the engine's shape arithmetic (engine.py _bucket / _route_admitted) --

def prefill_bucket(n: int, max_prompt_len: int) -> int:
    b = 16
    while b < n:
        b *= 2
    return min(b, max_prompt_len)


def programs_for_prompt(n: int, engine: dict) -> list[tuple]:
    """The prefill programs a prompt of n tokens (BOS included) compiles on
    first use when nothing of it is cached: one whole-prompt bucket, or
    full chunks plus a final-chunk bucket."""
    chunk, cap = engine["prefill_chunk"], engine["max_prompt_len"]
    n = min(n, cap)
    if chunk <= 0 or n <= chunk:
        return [("prefill", prefill_bucket(n, cap))]
    out, start = [], 0
    while n - start > chunk:
        out.append(("chunk", chunk))
        start += chunk
    out.append(("chunk", prefill_bucket(n - start, cap)))
    return out


def warm_prompt_lengths(lo: int, hi: int, engine: dict) -> list[int]:
    """The fewest prompt lengths that between them use every prefill
    program any length in [lo, hi] can use: the closed set the cell's file
    allows, not what one seed drew."""
    seen: set = set()
    out = []
    for n in range(lo, hi + 1):
        progs = set(programs_for_prompt(n, engine))
        if not progs <= seen:
            seen |= progs
            out.append(n)
    return out


def warm_prompts(lo: int, hi: int, engine: dict) -> list[tuple[int, str]]:
    """(tokens, text) of the warm-up requests. Each text repeats a letter
    of its own, so no warm-up prompt shares a cached page with another: a
    prefix hit would send it down the chunk path and leave its whole-prompt
    program cold."""
    return [(n, chr(97 + i % 26) * (n - 1))
            for i, n in enumerate(warm_prompt_lengths(lo, hi, engine))]


# ---- processes and chips ---------------------------------------------------

def descendants(pid: int | None = None) -> list[int]:
    """Live (non-zombie) processes below ``pid`` (default: this one)."""
    parent: dict[int, int] = {}
    for stat in glob.glob("/proc/[0-9]*/stat"):
        try:
            with open(stat) as f:
                head, tail = f.read().rsplit(") ", 1)
        except OSError:
            continue
        fields = tail.split()
        if fields[0] != "Z":
            parent[int(head.split(" ", 1)[0])] = int(fields[1])
    out, frontier = [], {pid or os.getpid()}
    while frontier:
        frontier = {p for p, pp in parent.items() if pp in frontier}
        out.extend(frontier)
    return out


def kill_descendants() -> list[int]:
    pids = descendants()
    for pid in pids:
        try:
            os.kill(pid, signal.SIGKILL)
        except OSError:
            pass
    return pids


def wait_children_gone(limit_s: float = 30.0) -> float:
    """Until no process below this one is alive. Returns the wait; kills
    what is left at the limit (and says so by raising)."""
    t0 = time.monotonic()
    while descendants():
        if time.monotonic() - t0 > limit_s:
            left = kill_descendants()
            raise BenchError(f"processes outlived their phase: {left}")
        time.sleep(0.1)
    return time.monotonic() - t0


def chip_holders() -> list[int]:
    """Processes (any user we can see) with a TPU device node open."""
    out = []
    for fd in glob.glob("/proc/[0-9]*/fd/*"):
        try:
            target = os.readlink(fd)
        except OSError:
            continue
        if target.startswith("/dev/vfio/") and target != "/dev/vfio/vfio" \
                or target.startswith("/dev/accel"):
            out.append(int(fd.split("/")[2]))
    return sorted(set(out))


def wait_chips_free(limit_s: float) -> float:
    """Bounded wait for a chip another process still holds (the run before
    may have printed its line while its workers were exiting)."""
    t0 = time.monotonic()
    while True:
        holders = [p for p in chip_holders() if p != os.getpid()]
        if not holders:
            return time.monotonic() - t0
        if time.monotonic() - t0 > limit_s:
            raise BenchError(f"chips still held by {holders} after "
                             f"{limit_s:.0f} s")
        time.sleep(0.25)


def parent_off_chip() -> bool:
    jax = sys.modules.get("jax")
    if jax is None:
        return True
    from jax._src import xla_bridge
    return not xla_bridge.backends_are_initialized()


def fold_seed(seed: int):
    """A jax PRNG key from any whole number up to 2**63 (PRNGKey alone
    takes 32 bits)."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def byte_encode(text: str) -> list[int]:
    """The byte tokenizer's encoding (BOS + one id per byte)."""
    return [BYTE_BOS] + list(text.encode("utf-8", errors="replace"))


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile (q in 0..100) of a non-empty list."""
    s = sorted(values)
    k = max(0, min(len(s) - 1, int(-(-q * len(s) // 100)) - 1))
    return s[k]
