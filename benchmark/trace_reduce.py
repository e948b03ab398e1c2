"""From a profiler trace to numbers: busy union, idle share, per-program
and per-kernel device time, exposed collective time, the breakdown.

A trace is read once into plain events ``[plane, line, name, start_ns,
dur_ns]`` (``read_xplane``), and everything else works on that list, so
the reduction is tested on a small recorded list kept as JSON
(benchmark/data/).

What the planes look like on a TPU v5e (jax 0.9, libtpu 0.0.34): one plane
``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event per
program execution, named ``jit_<fn>(<hash>)``), ``XLA Ops`` (one event per
executed HLO op, leaf ops only, named by the HLO instruction text) and
``Async XLA Ops`` (start..done spans of asynchronous collectives and
copies). There is no jax.named_scope in ray_tpu yet, so programs are told
apart by the jitted function's name and kernels by ``custom-call``.
"""

from __future__ import annotations

import glob
import json
import os
import re

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all")
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


def read_xplane(path: str, devices_only: bool = True) -> list[list]:
    """Events of one xplane file; by default of its device planes only
    (the host threads' planes hold nine events in ten and none is read)."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if devices_only and not _DEVICE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append([plane.name, line.name, ev.name,
                            int(ev.start_ns), int(ev.duration_ns)])
    return out


def read_dir(trace_dir: str) -> list[list]:
    """Device events of every xplane file under a profiler log directory
    (worker processes without a chip write host-only files: nothing)."""
    events: list[list] = []
    for path in sorted(glob.glob(os.path.join(
            trace_dir, "**", "*.xplane.pb"), recursive=True)):
        events.extend(read_xplane(path))
    return events


def load_events(path: str) -> list[list]:
    if path.endswith(".json"):
        with open(path) as f:
            return json.load(f)
    return read_xplane(path)


def _merge(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Sorted, disjoint intervals covering the same points."""
    out: list[tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def union_s(intervals: list[tuple[int, int]]) -> float:
    """Seconds covered by the union of [start, end) nanosecond intervals."""
    return sum(e - s for s, e in _merge(intervals)) / 1e9


def program_name(name: str) -> str:
    """``jit_step(485682885152639333)`` -> ``jit_step``."""
    return re.sub(r"\(\d+\)$", "", name)


def subtract_s(a: list[tuple[int, int]], b: list[tuple[int, int]]) -> float:
    """Seconds of union(a) not covered by union(b)."""
    return union_s(a + b) - union_s(b)


CONTAINERS = ("while", "conditional", "call")
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'


def parse_op(name: str) -> tuple[str, str]:
    """(opcode, result shape) of an XLA Ops event, whose name is the HLO
    instruction's text: ``%fusion.732 = bf16[2,2048,4096]{2,1,0:T(8,128)}
    fusion(...)`` or ``%while.38 = (s32[], bf16[...]) while(...)``."""
    _lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return program_name(name)[:60], ""
    if rhs.startswith("("):                       # a tuple of results
        depth = 0
        for i, ch in enumerate(rhs):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        shape, rest = "(tuple)", rhs[i + 1:]
    else:
        shape, _, rest = rhs.partition(" ")
        shape = re.sub(r"\{.*$", "", shape)
    return rest.strip().split("(", 1)[0].strip(), shape


def short_name(name: str) -> str:
    """Opcode and result shape, no instance number: ``fusion
    bf16[2,2048,4096]``; a Pallas kernel is ``custom-call(kernel) ...``."""
    op, shape = parse_op(name)
    if op == "custom-call" and KERNEL_TARGET in name:
        op = "custom-call(kernel)"
    return f"{op} {shape}".strip()


def is_container(name: str) -> bool:
    """Control flow whose event spans its body's ops (their time would be
    counted twice)."""
    return parse_op(name)[0] in CONTAINERS


def is_collective(name: str) -> bool:
    return any(parse_op(name)[0].startswith(c) for c in COLLECTIVES)


def is_kernel(name: str) -> bool:
    """A compiled Pallas (Mosaic) kernel."""
    return KERNEL_TARGET in name and parse_op(name)[0] == "custom-call"


def summarise(events: list[list]) -> dict | None:
    """Per device and averaged: window, busy, programs, ops, kernels,
    collectives. None when no device plane holds an op. A device's window
    is the span of its own events: the host planes start seconds earlier
    and stop later (the profiler's own start-up), when no device event
    could have been recorded."""
    devices: dict[str, dict] = {}
    for plane, line, name, start, dur in events:
        if not _DEVICE.match(plane):
            continue
        d = devices.setdefault(plane, {"modules": [], "ops": [], "async": []})
        if line == "XLA Modules":
            d["modules"].append((name, start, dur))
        elif line == "XLA Ops":
            d["ops"].append((name, start, dur))
        elif line == "Async XLA Ops":
            d["async"].append((name, start, dur))
    devices = {k: v for k, v in devices.items() if v["ops"]}
    if not devices:
        return None
    per_dev = {}
    for plane, d in sorted(devices.items()):
        every = d["ops"] + d["async"] + d["modules"]
        t_lo = min(s for _, s, _u in every)
        t_hi = max(s + u for _, s, u in every)
        busy = [(s, s + u) for _, s, u in d["ops"]]
        ops = [o for o in d["ops"] if not is_container(o[0])]
        compute = [(s, s + u) for n, s, u in ops if not is_collective(n)]
        coll = [(s, s + u) for n, s, u in ops + d["async"] if is_collective(n)]
        programs: dict[str, list] = {}
        for n, _s, u in d["modules"]:
            p = programs.setdefault(program_name(n), [0, 0.0])
            p[0] += 1
            p[1] += u / 1e9
        by_op: dict[str, list] = {}
        for n, _s, u in ops:
            p = by_op.setdefault(short_name(n), [0, 0.0])
            p[0] += 1
            p[1] += u / 1e9
        merged = _merge(busy)
        mods = sorted(d["modules"], key=lambda m: m[1])
        top = sorted(((s1 - e0, s1) for (_s0, e0), (s1, _e1)
                      in zip(merged, merged[1:])), reverse=True)[:10]
        gaps = [("in or before " + next(
            (program_name(n) for n, ms, mu in mods if ms + mu > s1),
            "end of trace"), g / 1e9) for g, s1 in top]
        per_dev[plane] = {
            "window_s": (t_hi - t_lo) / 1e9, "busy_s": union_s(busy), "compute_busy_s": union_s(compute),
            "collective_s": union_s(coll),
            "collective_exposed_s": subtract_s(coll, compute),
            "programs": programs, "ops": by_op,
            "kernel_calls": sum(1 for n, _, _ in ops if is_kernel(n)),
            "kernel_s": sum(u for n, _, u in ops if is_kernel(n)) / 1e9,
            "gaps": gaps}
    n = len(per_dev)
    first = next(iter(per_dev.values()))
    return {
        "window_s": sum(d["window_s"] for d in per_dev.values()) / n,
        "device_count": n,
        "busy_s": sum(d["busy_s"] for d in per_dev.values()) / n,
        "collective_exposed_s":
            sum(d["collective_exposed_s"] for d in per_dev.values()) / n,
        "per_device": per_dev,
        "breakdown": {
            "device_ops": [[k, v[1]] for k, v in sorted(
                first["ops"].items(), key=lambda kv: -kv[1][1])[:10]],
            "idle_gaps": [[k, v] for k, v in first["gaps"]]},
    }


# how the serve engine's programs are named today (engine.py jits lambdas)
def is_decode_program(name: str, seconds: float) -> bool:
    """The engine's fused decode block: ``jax.jit(lambda ...)``, so
    ``jit__lambda``; the slot-patch lambdas share the name and take
    microseconds."""
    return name.startswith("jit__lambda") and seconds >= 5e-4


def is_prefill_program(name: str, seconds: float) -> bool:
    """Whole-prompt prefill and prefill chunks: ``jit_impl``."""
    return name.startswith("jit_impl")
