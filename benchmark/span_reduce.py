"""From a profiler trace to per-layer numbers whose numerator and
denominator come from ONE clock: the engine's own spans (``rt/<phase>``,
ray_tpu/observability/profiling.py) in the trace's host plane, the device
ops with the ``jax.named_scope`` path they were traced under, and the
Pallas kernels by their ``name``.

What the profiler writes for a TPU v5e (jax 0.9, libtpu 0.0.34), beyond
what trace_reduce.py says: an ``XLA Ops`` event carries only its time; the
scope path is a stat of the event's METADATA, ``tf_op``
(``jit(<lambda>)/decode_block/while/body/closed_call/decode_step/while/
body/closed_call/attn/paged_decode_attention/pallas_call:``; under
autodiff a component reads ``transpose(jvp(attn))``), which
``jax.profiler.ProfileData`` does not expose. So the file is read here
with a minimal reader of the protobuf wire format (the xplane schema's
field numbers are in ``_read_plane``), which needs nothing beyond the
standard library. The compiler's own ops carry a scope too: the whole-pool
``copy`` of a decode program has ``jit(<lambda>)/decode_block/while:``.

A trace is reduced once to plain tuples (``read_dir`` / ``from_rows``):

    ops      [(short name, start_ns, end_ns, tf_op, is_container)]
    modules  [(program, start_ns, end_ns)]       one per execution
    spans    [(phase, start_ns, end_ns, args)]   the engine-loop thread

and everything else works on those, so it is tested on hand-built lists
and on a cut of a recorded chip trace (benchmark/data/).

A program without the spans or the scopes (the commit before they were
added) gives a trace in which the readers below find nothing: they return
None and never raise.
"""

from __future__ import annotations

import functools
import glob
import json
import os
import re
import struct
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmark import trace_reduce  # noqa: E402

SPAN_PREFIX = "rt/"
# The scopes of a model's own arithmetic are its family's (``MODEL_SCOPES``
# of benchmark/models/<family>.py; the functions below take them as
# ``model_scopes``). The rest of a program is cache and state movement,
# under the engine's scopes: kv_write, gather_state / scatter_state, what
# sits directly under a program's scope (scan carries, the compiler's
# copies), and ops with no scope at all.
OTHER_SCOPES = ("kv_write", "gather_state", "scatter_state", "decode_step",
                "decode_block", "prefill", "prefill_chunk", "verify")
WAITING = ("loop_wait", "harvest")       # the host has nothing to run
DISPATCH_KIND = {"decode_dispatch": "decode", "verify_dispatch": "verify",
                 "prefill": "prefill", "chunk_prefill": "prefill"}
KERNEL_KIND = {"paged_decode_attention": "decode",
               "paged_verify_attention": "verify"}


# ---- the protobuf wire format, as far as an xplane file needs it -----------

def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint or a
    fixed-width field, a memoryview for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val = buf[i:i + size]
            i += size
        elif wire == 1:
            val = int.from_bytes(buf[i:i + 8], "little")
            i += 8
        elif wire == 5:
            val = int.from_bytes(buf[i:i + 4], "little")
            i += 4
        else:
            raise ValueError(f"wire type {wire}")
        yield key >> 3, val


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf, stat_names: dict) -> tuple[str, object]:
    """XStat: metadata_id=1, double=2, uint64=3, int64=4, str=5, bytes=6,
    ref=7 (a string kept as a stat name)."""
    name, val = "", None
    for no, v in _fields(buf):
        if no == 1:
            name = stat_names.get(v, "")
        elif no == 3:
            val = v
        elif no == 4:
            val = _signed(v)
        elif no == 5:
            val = bytes(v).decode("utf-8", "replace")
        elif no == 7:
            val = stat_names.get(v, "")
        elif no == 2:
            val = struct.unpack("<d", v.to_bytes(8, "little"))[0]
    return name, val


def _read_plane(buf, want_event, event_stats=True) -> list[tuple[str, list]]:
    """One XPlane (name=2, lines=3, event_metadata=4, stat_metadata=5; the
    maps' entries are key=1, value=2). Returns, for each of its lines (a
    device's line of ops, a host thread), the line's name and [(event
    name, start_ns, dur_ns, stats, metadata stats)] of the events whose
    metadata ``want_event(name)`` accepts (``event_stats=False`` leaves the
    events' own stats unread: a device op's are three numbers nobody
    needs, a hundred thousand times). XLine: name=2,
    timestamp_ns=3, events=4. XEvent: metadata_id=1, offset_ps=2,
    duration_ps=3, stats=4. XEventMetadata: id=1, name=2, stats=5."""
    lines, ev_meta, stat_meta = [], [], []
    for no, v in _fields(buf):
        if no == 3:
            lines.append(v)
        elif no == 4:
            ev_meta.append(v)
        elif no == 5:
            stat_meta.append(v)
    stat_names = {}
    for entry in stat_meta:
        for no, v in _fields(entry):
            if no == 2:
                sid, sname = 0, ""
                for n2, v2 in _fields(v):
                    if n2 == 1:
                        sid = v2
                    elif n2 == 2:
                        sname = bytes(v2).decode("utf-8", "replace")
                stat_names[sid] = sname
    wanted: dict[int, tuple[str, dict]] = {}
    for entry in ev_meta:
        for no, v in _fields(entry):
            if no != 2:
                continue
            mid, mname, mstats = 0, "", []
            for n2, v2 in _fields(v):
                if n2 == 1:
                    mid = v2
                elif n2 == 2:
                    mname = bytes(v2).decode("utf-8", "replace")
                elif n2 == 5:
                    mstats.append(v2)
            if want_event(mname):
                wanted[mid] = (mname, dict(_stat(s, stat_names)
                                           for s in mstats))
    out = []
    for line in lines:
        lname, t0, events = "", 0, []
        for no, v in _fields(line):
            if no == 2:
                lname = bytes(v).decode("utf-8", "replace")
            elif no == 3:
                t0 = v
            elif no == 4:
                events.append(v)
        rows = []
        out.append((lname, rows))
        for ev in events:
            mid = offset = dur = 0
            stats = []
            for no, v in _fields(ev):
                if no == 1:
                    if v not in wanted:
                        break
                    mid = v
                elif no == 2:
                    offset = v
                elif no == 3:
                    dur = v
                elif no == 4 and event_stats:
                    stats.append(v)
            else:
                mname, mstats = wanted[mid]
                rows.append((mname, t0 + offset // 1000, dur // 1000,
                             dict(_stat(s, stat_names) for s in stats),
                             mstats))
    return out


def _planes(path: str):
    """(name, raw bytes) of the planes of an XSpace (planes=1)."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    for no, v in _fields(buf):
        if no == 1:
            pname = ""
            for n2, v2 in _fields(v):
                if n2 == 2:
                    pname = bytes(v2).decode()
                    break
            yield pname, v


def read_xplane(path: str) -> dict | None:
    """The reduced trace of one xplane file, or None when it holds no
    device plane with ops (a worker without a chip)."""
    device = host = None
    for pname, raw in _planes(path):
        if device is None and trace_reduce._DEVICE.match(pname):
            lines = dict(_read_plane(raw, lambda name: True,
                                     event_stats=False))
            if lines.get("XLA Ops"):
                device = lines
        elif pname == "/host:CPU":
            host = raw
    if device is None:
        return None
    rows = [["op", trace_reduce.short_name(n), s, u, m.get("tf_op") or "",
             int(trace_reduce.is_container(n))]
            for n, s, u, _st, m in device["XLA Ops"]]
    rows += [["module", trace_reduce.program_name(n), s, u, "", 0]
             for n, s, u, _st, _m in device.get("XLA Modules", [])]
    if host is not None:
        threads = _read_plane(
            host, lambda name: name.startswith(SPAN_PREFIX))
        # the engine loop is one thread: the line with the most passes
        loop = max((evs for _name, evs in threads), default=[],
                   key=lambda evs: sum(
                       1 for e in evs if e[0] == SPAN_PREFIX + "loop_pass"))
        rows += [["span", n[len(SPAN_PREFIX):], s, u, st, 0]
                 for n, s, u, st, _m in loop]
    return from_rows(rows)


def read_dir(trace_dir: str) -> dict | None:
    """The reduced trace of the file under a profiler log directory that
    holds a device plane (every process of a run writes a file of its own;
    the replica's worker holds the chip)."""
    for path in sorted(glob.glob(os.path.join(
            trace_dir, "**", "*.xplane.pb"), recursive=True)):
        trace = read_xplane(path)
        if trace is not None:
            return trace
    return None


def from_rows(rows: list[list]) -> dict:
    """Rows ``[kind, name, start_ns, dur_ns, info, container]`` with kind
    op | module | span (info: an op's tf_op, a span's arguments): the form
    a trace is kept in as JSON."""
    return {
        "ops": sorted(((n, s, s + u, info, bool(c))
                       for k, n, s, u, info, c in rows if k == "op"),
                      key=lambda o: o[1:3]),
        "modules": sorted(((n, s, s + u) for k, n, s, u, _i, _c in rows
                           if k == "module"), key=lambda m: m[1]),
        "spans": sorted(((n, s, s + u, info) for k, n, s, u, info, _c in rows
                         if k == "span"), key=lambda sp: (sp[1], -sp[2])),
    }


def to_rows(trace: dict, t_lo: int, t_hi: int) -> list[list]:
    """The rows of what lies inside [t_lo, t_hi): how a cut of a recorded
    trace is written to benchmark/data/."""
    rows = [["op", n, s, e - s, tf, int(c)] for n, s, e, tf, c in trace["ops"]
            if t_lo <= s and e <= t_hi]
    rows += [["module", n, s, e - s, "", 0] for n, s, e in trace["modules"]
             if t_lo <= s and e <= t_hi]
    rows += [["span", n, s, e - s, a, 0] for n, s, e, a in trace["spans"]
             if t_lo <= s and e <= t_hi]
    return rows


def dump(rows: list[list], path: str) -> None:
    """Rows as JSON with every string kept once (a trace repeats a few
    hundred names and scope paths tens of thousands of times)."""
    strings: dict[str, int] = {}
    packed = [[strings.setdefault(k, len(strings)),
               strings.setdefault(n, len(strings)), s, u,
               strings.setdefault(i, len(strings)) if isinstance(i, str)
               else i, c] for k, n, s, u, i, c in rows]
    with open(path, "w") as f:
        json.dump({"strings": list(strings), "rows": packed}, f,
                  separators=(",", ":"))


def load(path: str) -> dict:
    with open(path) as f:
        data = json.load(f)
    st = data["strings"]
    return from_rows([[st[k], st[n], s, u, st[i] if isinstance(i, int) else i,
                       c] for k, n, s, u, i, c in data["rows"]])


def of_run(run: dict) -> dict | None:
    """The reduced trace of a run, read once (the metrics of one run share
    it)."""
    if "span_trace" not in run:
        run["span_trace"] = (read_dir(run["trace_dir"])
                             if run.get("trace_dir") else None)
    return run["span_trace"]


# ---- scopes and kernels -----------------------------------------------------

def scope_path(tf_op: str) -> list[str]:
    """The named scopes of an op, outermost first: the path's components
    with autodiff's wrappers taken off (``transpose(jvp(attn))``)."""
    return [re.sub(r"^(?:\w+\()+|\)+$", "", part)
            for part in tf_op.rstrip(":").split("/")]


@functools.lru_cache(maxsize=None)
def layer_of(tf_op: str, model_scopes: tuple) -> str:
    """The innermost scope this repo names (the family's or the engine's),
    or "" (no scope: the compiler's own, or an eager op)."""
    for part in reversed(scope_path(tf_op)):
        if part in model_scopes or part in OTHER_SCOPES:
            return part
    return ""


@functools.lru_cache(maxsize=None)
def kernel_of(tf_op: str) -> str:
    """The ``name`` of the Pallas kernel an op is, or ""."""
    path = scope_path(tf_op)
    if "pallas_call" in path[1:]:
        return path[path.index("pallas_call", 1) - 1]
    return ""


# ---- intervals --------------------------------------------------------------

def _within(items: list, spans: list[tuple[int, int]],
            start=lambda it: it[1]):
    """(item, index of the span that holds its start) for the items (sorted
    by start) that begin inside one of the disjoint sorted spans."""
    i = 0
    for it in items:
        s = start(it)
        while i < len(spans) and spans[i][1] <= s:
            i += 1
        if i < len(spans) and spans[i][0] <= s:
            yield it, i


def _overlap_s(a: list[tuple[int, int]], b: list[tuple[int, int]]) -> float:
    """Seconds covered by both unions."""
    return (trace_reduce.union_s(a) + trace_reduce.union_s(b)
            - trace_reduce.union_s(a + b))


def _gaps(trace: dict) -> tuple[list[tuple[int, int]], int, int]:
    """The device's idle intervals and its traced span, as
    trace_reduce.summarise takes them: the span of the device's events,
    minus the union of its ops (containers included)."""
    busy = trace_reduce._merge([(s, e) for _n, s, e, _t, _c in trace["ops"]])
    every = busy + [(s, e) for _n, s, e in trace["modules"]]
    t_lo, t_hi = min(s for s, _ in every), max(e for _, e in every)
    return ([(e0, s1) for (_s0, e0), (s1, _e1) in zip(busy, busy[1:])],
            t_lo, t_hi)


def leaf_spans(trace: dict) -> list[tuple[str, int, int]]:
    """The loop thread's time cut into pieces, each under its innermost
    span: (phase, start, end). Time of a parent that no child covers stays
    the parent's."""
    out: list[tuple[str, int, int]] = []
    stack: list[list] = []            # [name, end, cursor]

    def close(upto: int):
        while stack and stack[-1][1] <= upto:
            name, end, cur = stack.pop()
            if end > cur:
                out.append((name, cur, end))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for name, s, e, _a in trace["spans"]:
        close(s)
        if stack and s > stack[-1][2]:
            out.append((stack[-1][0], stack[-1][2], s))
        if stack:
            stack[-1][2] = max(stack[-1][2], s)
        stack.append([name, e, s])
    close(1 << 62)
    return sorted(out, key=lambda p: p[1])


# ---- one ordered stream: executions and the spans that dispatched them ------

def attn_layers(sizes: dict) -> int:
    """How many of a model's layers call the paged attention kernel: what
    a family's ``sizes()`` states as ``attn_layers`` (a hybrid block: 3 of
    14), else every layer."""
    return sizes.get("attn_layers", sizes["n_layers"])


def executions(trace: dict, n_layers: int) -> list[dict]:
    """The engine's big programs in the order the device ran them: decode
    blocks and verify rounds (``jit__lambda``; the kind and the steps from
    the calls of the kernel inside, over the layers that call it:
    ``n_layers`` here and below is ``attn_layers(sizes)``) and prefills or
    prefill chunks (``jit_impl``). A ``jit__lambda`` without a kernel of
    ours inside (a program compiled before the kernels had names) stays a
    decode block of 0 steps."""
    out = []
    for prog, s, e in trace["modules"]:
        sec = (e - s) / 1e9
        if trace_reduce.is_decode_program(prog, sec):
            out.append({"kind": "decode", "start": s, "end": e, "steps": 0,
                        "kernel_calls": 0, "kernel_ns": 0})
        elif trace_reduce.is_prefill_program(prog, sec):
            out.append({"kind": "prefill", "start": s, "end": e})
    kernels = [(s, e, kernel_of(tf)) for _n, s, e, tf, c in trace["ops"]
               if not c]
    for (s, e, kern), i in _within(
            [k for k in kernels if k[2] in KERNEL_KIND],
            [(x["start"], x["end"]) for x in out], start=lambda k: k[0]):
        x = out[i]
        if x["kind"] != "prefill":
            x["kind"] = KERNEL_KIND[kern]
            x["kernel_calls"] += 1
            x["kernel_ns"] += e - s
    for x in out:
        if x["kind"] != "prefill":
            x["steps"] = x["kernel_calls"] / n_layers
    # an execution under way when the capture began has lost its first ops
    return [x for x in out if x["kind"] == "prefill"
            or x["kernel_calls"] % n_layers == 0]


def dispatches(trace: dict) -> list[dict]:
    """The loop's dispatch spans in host order."""
    return [{"kind": DISPATCH_KIND[n], "start": s, "end": e, "args": a}
            for n, s, e, a in trace["spans"] if n in DISPATCH_KIND]


def _fits(x: dict, d: dict) -> bool:
    if x["kind"] != d["kind"]:
        return False
    if d["kind"] == "decode":
        return x["steps"] == d["args"]["k"]
    if d["kind"] == "verify":
        return x["steps"] == 1      # one kernel call a layer for k+1 rows
    return True


def match_stream(trace: dict, n_layers: int) -> dict:
    """Pairs (execution, dispatch span). The device runs programs in the
    order the loop dispatched them, so the two lists are one stream seen
    twice, ragged at the ends: the trace's first executions were
    dispatched before the capture began and its last dispatches ran after
    it ended. They are aligned by the number of leading executions without
    a span that leaves the fewest pairs unfit (kind, and a decode block's
    steps against its span's ``k``); among equals, the smallest at which
    no execution begins before its span does."""
    ex, di = executions(trace, n_layers), dispatches(trace)
    best = None
    for lead in range(0, max(1, min(len(ex), 64))):
        pairs = list(zip(ex[lead:], di))
        if not pairs:
            break
        unfit = sum(1 for x, d in pairs if not _fits(x, d))
        causal = all(x["start"] >= d["start"] for x, d in pairs)
        key = (unfit, not causal, lead)
        if best is None or key < best[0]:
            best = (key, pairs)
    pairs = best[1] if best else []
    return {"pairs": pairs, "unfit": best[0][0] if best else 0,
            "lead": best[0][2] if best else 0,
            "executions": ex, "dispatches": di}


# ---- the metrics ------------------------------------------------------------

def decode_step_traced_ms(trace: dict, n_layers: int) -> float | None:
    """Device time of the decode programs in the trace over the decode
    steps those executions ran (calls of ``paged_decode_attention`` inside
    them over the layers); None unless the ``k`` of the dispatch spans
    matched to them says the same to within one block."""
    m = match_stream(trace, n_layers)
    ex = [x for x in m["executions"] if x["kind"] == "decode"]
    steps = sum(x["steps"] for x in ex)
    pairs = [(x, d) for x, d in m["pairs"]
             if x["kind"] == "decode" == d["kind"]]
    if not steps or not pairs:
        return None
    ks = [d["args"]["k"] for _x, d in pairs]
    if abs(sum(x["steps"] for x, _d in pairs) - sum(ks)) > max(ks):
        return None
    return sum(x["end"] - x["start"] for x in ex) / 1e6 / steps


def prefill_traced_ms_per_ktok(trace: dict, n_layers: int) -> float | None:
    """Device time of the prefill and prefill-chunk executions over the
    prompt tokens (``tokens``) of the spans that dispatched them."""
    pairs = [(x, d) for x, d in match_stream(trace, n_layers)["pairs"]
             if x["kind"] == "prefill" == d["kind"]]
    tokens = sum(d["args"]["tokens"] for _x, d in pairs)
    if not tokens:
        return None
    return sum(x["end"] - x["start"] for x, _d in pairs) / 1e6 \
        / (tokens / 1e3)


def device_by_scope(trace: dict, is_main,
                    model_scopes: tuple) -> dict[str, float]:
    """Seconds of the leaf ops inside the executions ``is_main(program,
    seconds)`` picks, by ``layer_of`` ("" = no scope of ours)."""
    model_scopes = tuple(model_scopes)      # layer_of caches on it
    spans = [(s, e) for n, s, e in trace["modules"]
             if is_main(n, (e - s) / 1e9)]
    out: dict[str, float] = {}
    for (_n, s, e, tf, _c), _i in _within(
            [o for o in trace["ops"] if not o[4]], spans):
        layer = layer_of(tf, model_scopes)
        out[layer] = out.get(layer, 0.0) + (e - s) / 1e9
    return out


def model_op_share(trace: dict, is_main, model_scopes: tuple) -> float | None:
    """Share (%) of the main programs' op time under a model scope."""
    by = device_by_scope(trace, is_main, model_scopes)
    named = sum(v for k, v in by.items() if k)
    if not named:
        return None                    # a program without scopes
    return 100.0 * sum(by.get(k, 0.0) for k in model_scopes) \
        / sum(by.values())


def program_split(trace: dict, n_layers: int) -> dict[str, float]:
    """The device's traced span as decode programs + prefill programs +
    idle, in seconds (what is left of the span is the small programs
    between them: slot patches, concatenations)."""
    gaps, t_lo, t_hi = _gaps(trace)
    ex = executions(trace, n_layers)
    return {"span_s": (t_hi - t_lo) / 1e9,
            "decode_s": sum(x["end"] - x["start"] for x in ex
                            if x["kind"] != "prefill") / 1e9,
            "prefill_s": sum(x["end"] - x["start"] for x in ex
                             if x["kind"] == "prefill") / 1e9,
            "idle_s": trace_reduce.union_s(gaps)}


def prefill_program_share(trace: dict, n_layers: int) -> float | None:
    """Share (%) of the device's traced span inside prefill and
    prefill-chunk executions; None where the trace holds none."""
    split = program_split(trace, n_layers)
    if not split["prefill_s"]:
        return None
    return 100.0 * split["prefill_s"] / split["span_s"]


def idle_by_span(trace: dict) -> dict[str, float]:
    """Seconds of device idle time by the loop's innermost span over it
    ("" = under no span)."""
    gaps, _lo, _hi = _gaps(trace)
    out = {"": trace_reduce.union_s(gaps)}
    pieces: dict[str, list] = {}
    for name, s, e in leaf_spans(trace):
        pieces.setdefault(name, []).append((s, e))
    for name, iv in pieces.items():
        out[name] = _overlap_s(gaps, iv)
        out[""] -= out[name]
    return out


def idle_host_busy_share(trace: dict) -> float | None:
    """Share (%) of the device's traced span in which it is idle AND the
    loop thread is inside a span other than loop_wait and harvest."""
    if not trace["spans"]:
        return None
    _idle, t_lo, t_hi = _gaps(trace)
    by = idle_by_span(trace)
    busy = sum(v for k, v in by.items() if k and k not in WAITING)
    return 100.0 * busy / ((t_hi - t_lo) / 1e9)


def paged_decode_roofline_traced(trace: dict, sizes: dict,
                                 peak: dict) -> float | None:
    """The paged decode kernel's share of its roofline over the decode
    executions matched to a dispatch span: the bytes every call must move
    (costs.paged_decode_bytes) with the live context the span states
    (``ctx_tokens`` cached tokens over ``active`` slots as the block
    starts, one more token a slot each step) over the bandwidth, over the
    time the calls took."""
    from benchmark import costs
    need_s = took_s = 0.0
    hd = sizes["dim"] // sizes["n_heads"]
    layers = attn_layers(sizes)
    for x, d in match_stream(trace, layers)["pairs"]:
        a = d["args"]
        if not (x["kind"] == "decode" == d["kind"]) \
                or x["steps"] != a["k"] or not x["kernel_ns"]:
            continue
        for step in range(a["k"]):
            mean = (a["ctx_tokens"] + a["active"] * (step + 1)) / a["active"]
            need_s += layers * costs.paged_decode_bytes(
                [mean] * a["active"], sizes["n_kv_heads"], hd,
                sizes["n_heads"]) / peak["hbm_bytes_per_s"]
        took_s += x["kernel_ns"] / 1e9
    return 100.0 * need_s / took_s if took_s else None


def name_idle_gaps(trace: dict, top: int = 10) -> list[list]:
    """The device's longest idle gaps as trace_reduce's breakdown lists
    them, each named by the loop span that covers most of it:
    ``["emit before jit__lambda", seconds]``."""
    gaps, _lo, _hi = _gaps(trace)
    pieces = leaf_spans(trace)
    mods = trace["modules"]
    out = []
    for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        cover: dict[str, int] = {}
        for name, s, e in pieces:
            if s < g1 and e > g0:
                cover[name] = cover.get(name, 0) + min(e, g1) - max(s, g0)
        span = max(cover, key=cover.get) if cover else "no span"
        nxt = next((n for n, _s, e in mods if e > g1), "end of trace")
        out.append([f"{span} before {nxt}", (g1 - g0) / 1e9])
    return out


def engine_loop_busy_share(before: dict, after: dict) -> float | None:
    """1 - (time the loop spent parked or blocked on a result) / the time
    between the two reads of /v1/stats by the replica's own ``clock_s``: a
    traced run's closing read comes seconds after the window's end, so the
    seconds the harness asked for are not the interval. None where a read
    lacks a total or the clock."""
    keys = [f"phase_{p}_s_total" for p in WAITING]
    if any(k not in d for d in (before, after) for k in (*keys, "clock_s")):
        return None
    took = after["clock_s"] - before["clock_s"]
    if took <= 0:
        return None
    waited = sum(after[k] - before[k] for k in keys)
    return 100.0 * (1.0 - waited / took)


def report(trace: dict, n_layers: int, is_main, model_scopes: tuple) -> dict:
    """What PERF.md's section 5 is written from."""
    model_scopes = tuple(model_scopes)
    m = match_stream(trace, n_layers)
    idle = idle_by_span(trace)
    by = device_by_scope(trace, is_main, model_scopes)
    ops: dict[tuple, float] = {}
    spans = [(s, e) for n, s, e in trace["modules"]
             if is_main(n, (e - s) / 1e9)]
    for (n, s, e, tf, _c), _i in _within(
            [o for o in trace["ops"] if not o[4]], spans):
        key = (n, layer_of(tf, model_scopes), kernel_of(tf), tf[-90:])
        ops[key] = ops.get(key, 0.0) + (e - s) / 1e9
    ex = m["executions"]
    return {
        "split_s": program_split(trace, n_layers),
        "idle_by_span_s": dict(sorted(idle.items(), key=lambda kv: -kv[1])),
        "device_by_scope_s": dict(sorted(by.items(), key=lambda kv: -kv[1])),
        "top_ops": [[*k, v] for k, v in sorted(
            ops.items(), key=lambda kv: -kv[1])[:14]],
        "gaps": name_idle_gaps(trace),
        "stream": {"executions": len(ex), "dispatches": len(m["dispatches"]),
                   "pairs": len(m["pairs"]), "unfit": m["unfit"],
                   "lead": m["lead"],
                   "decode_steps": sum(x.get("steps", 0) for x in ex)},
        "programs_s": _programs(trace),
    }


def _programs(trace: dict) -> dict[str, float]:
    out: dict[str, float] = {}
    for n, s, e in trace["modules"]:
        out[n] = out.get(n, 0.0) + (e - s) / 1e9
    return dict(sorted(out.items(), key=lambda kv: -kv[1])[:8])


if __name__ == "__main__":
    # python3 benchmark/span_reduce.py <trace dir> <cell>
    #     [--cut <from_s> <to_s> <out.json>]
    # the cell's configuration gives the layers, the model family (its
    # scopes) and which program is the main one
    from benchmark import common
    _entry, _cell, config = common.load_cell(sys.argv[2])
    fam = common.family(config)
    tr = read_dir(sys.argv[1])
    main = (lambda n, _s: n.startswith("jit_step")) \
        if config["kind"] == "train" else trace_reduce.is_decode_program
    if "--cut" in sys.argv:
        i = sys.argv.index("--cut")
        lo = min(s for _n, s, _e, _t, _c in tr["ops"])
        rows = to_rows(tr, lo + int(float(sys.argv[i + 1]) * 1e9),
                       lo + int(float(sys.argv[i + 2]) * 1e9))
        base = min(r[2] for r in rows)
        for r in rows:
            r[2] -= base
        dump(rows, sys.argv[i + 3])
    print(json.dumps(report(tr, attn_layers(fam.sizes(config, False)), main,
                            fam.MODEL_SCOPES), indent=1))
