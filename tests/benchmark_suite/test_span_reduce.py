"""benchmark/span_reduce.py: the metrics that read the engine's spans, the
device ops' scopes and the kernels' names from one trace. On a trace built
by hand (every number below is a hand count), on a cut of a trace recorded
on the v5e (benchmark/data/trace_spans_chat_v5e.json: 0.63 s of the chat
cell, PR 25), on an xplane file encoded by hand (the wire-format reader),
and on a trace of a program that has neither spans nor scopes (the parent
commit: every reader returns None and none raises)."""

import os
import struct

import pytest

from benchmark import common, span_reduce as sr, trace_reduce

RECORDED = os.path.join(common.HERE, "data", "trace_spans_chat_v5e.json")
US = 1000                                     # the hand trace counts in us
PATH = "jit(<lambda>)/decode_block/while/body/closed_call/decode_step/" \
    "while/body/closed_call/"
KERNEL = PATH + "attn/paged_decode_attention/pallas_call:"
MLP = PATH + "mlp/dot_general:"
SIZES = {"dim": 256, "n_heads": 4, "n_kv_heads": 2, "n_layers": 2}
FAM = common.family(common.load_cell("mistral7b-serve-chat")[2])
SCOPES = tuple(FAM.MODEL_SCOPES)


def _program(name, start, ops):
    """A module execution and its ops laid end to end from ``start``."""
    rows, t = [], start
    for short, dur, tf_op in ops:
        rows.append(["op", short, t * US, dur * US, tf_op, 0])
        t += dur
    return rows, t


def _span(name, start, end, **args):
    return ["span", name, start * US, (end - start) * US, args, 0]


def hand_rows(k_first=2, k_second=1):
    """Two layers. The device runs, in order: a decode block of 2 steps
    dispatched before the capture began, a prefill, a decode block of 2
    steps, one of 1 step; the host's spans dispatch the last three and
    then a prefill that runs after the capture has ended."""
    kern = ("custom-call(kernel) bf16[4,2,8,64]", 100, KERNEL)
    rows = []
    for name, start, end, ops in [
        ("jit__lambda", 0, 1000, [kern] * 4 + [
            ("copy bf16[2,2,64,16,64]", 200,
             "jit(<lambda>)/decode_block/while:"),
            ("fusion bf16[4,512]", 300, MLP), ("copy-done bf16[8]", 100, "")]),
        ("jit__lambda", 1050, 1060, [("fusion s32[5]", 10, "")]),  # a patch
        ("jit_impl", 1100, 1500, [
            ("fusion bf16[1,128,4,64]", 300,
             "jit(impl)/prefill/while/body/closed_call/attn/dot_general:"),
            ("copy bf16[2,64,16,64]", 100,
             "jit(impl)/prefill/while/body/closed_call/kv_write/scatter:")]),
        ("jit__lambda", 1700, 2500, [kern] * 4 + [
            ("fusion bf16[4,512]", 200, MLP),
            ("fusion s32[4]", 100, "jit(<lambda>)/decode_block/while/body/"
             "closed_call/decode_step/sample/argmax:"),
            ("copy bf16[2,64,16,64]", 100, PATH + "kv_write/scatter:")]),
        ("jit__lambda", 2500, 3100, [kern] * 2 + [
            ("fusion bf16[4,512]", 100, MLP),
            ("copy bf16[2,2,64,16,64]", 300, "")]),
    ]:
        got, t = _program(name, start, ops)
        assert t == end
        rows += got + [["module", name, start * US, (end - start) * US, "", 0]]
    rows += [
        _span("loop_pass", 500, 1200),
        _span("admit", 500, 700, admitted=1, waiting=0),
        _span("prefill", 550, 650, rid="a1", bucket=128, tokens=100),
        _span("decode_dispatch", 700, 1150, seq=5, k=k_first, w=4, active=3,
              ctx_tokens=90),
        _span("patch_flush", 720, 1120, dirty=1, overrides=1),
        _span("loop_pass", 1200, 2000),
        _span("admit", 1200, 1210, admitted=0, waiting=0),
        _span("decode_dispatch", 1210, 1300, seq=6, k=k_second, w=4,
              active=2, ctx_tokens=64),
        _span("patch_flush", 1220, 1250, dirty=0, overrides=0),
        _span("harvest", 1300, 1650, seq=4, k=2),
        _span("emit", 1650, 1690, seq=4, tokens=6, finished=0),
        _span("loop_pass", 2000, 2900),
        _span("admit", 2000, 2050, admitted=1, waiting=0),
        _span("prefill", 2010, 2040, rid="b2", bucket=64, tokens=50),
        _span("loop_wait", 2100, 2800),
    ]
    return rows


HAND = sr.from_rows(hand_rows())


def test_scope_and_kernel_are_read_from_the_path():
    assert sr.layer_of(KERNEL, SCOPES) == "attn"
    assert sr.layer_of(MLP, SCOPES) == "mlp"
    # the model's scopes are the family's: another family's list tells
    # another story of the same path
    assert sr.layer_of(MLP, ("experts",)) == "decode_step"
    assert sr.kernel_of(KERNEL) == "paged_decode_attention"
    assert sr.kernel_of(MLP) == ""
    assert sr.layer_of("jit(<lambda>)/decode_block/while:", SCOPES) \
        == "decode_block"
    assert sr.layer_of(PATH + "kv_write/scatter:", SCOPES) == "kv_write"
    assert sr.layer_of("", SCOPES) == ""
    assert sr.layer_of("jit(concatenate)/x:", SCOPES) == ""
    # under autodiff a scope is wrapped
    bwd = "jit(step)/jit(main)/transpose(jvp(attn))/shard_map/" \
        "flash_bwd_dq/pallas_call:"
    assert sr.scope_path(bwd)[2] == "attn"
    assert sr.layer_of(bwd, SCOPES) == "attn"
    assert sr.kernel_of(bwd) == "flash_bwd_dq"
    assert sr.layer_of("jit(step)/jvp(loss)/while/body/lm_head/dot:",
                       SCOPES) == "lm_head"
    assert sr.layer_of("jit(step)/optimizer/mul:", SCOPES) == "optimizer"


def test_executions_and_dispatches_are_matched_as_one_stream():
    m = sr.match_stream(HAND, 2)
    assert [(x["kind"], x.get("steps")) for x in m["executions"]] == [
        ("decode", 2.0), ("prefill", None), ("decode", 2.0), ("decode", 1.0)]
    assert [d["kind"] for d in m["dispatches"]] == [
        "prefill", "decode", "decode", "prefill"]
    # one leading execution has no span; the last prefill span no execution
    assert (m["lead"], m["unfit"], len(m["pairs"])) == (1, 0, 3)
    assert [d["args"].get("seq") for _x, d in m["pairs"]] == [None, 5, 6]


def test_hand_counts_of_every_metric():
    # 2.4 ms of decode programs, (4 + 4 + 2) kernel calls / 2 layers
    assert sr.decode_step_traced_ms(HAND, 2) == pytest.approx(2.4 / 5)
    # 0.4 ms for the 100 tokens of the one matched prefill
    assert sr.prefill_traced_ms_per_ktok(HAND, 2) == pytest.approx(4.0)
    # kernels 1000 + mlp 600 + sample 100 of 2400 us of ops
    assert sr.model_op_share(HAND, trace_reduce.is_decode_program, SCOPES) \
        == pytest.approx(100 * 1700 / 2400)
    by = sr.device_by_scope(HAND, trace_reduce.is_decode_program, SCOPES)
    assert by == pytest.approx({"attn": 1e-3, "mlp": 6e-4, "sample": 1e-4,
                                "decode_block": 2e-4, "kv_write": 1e-4,
                                "": 4e-4})
    # idle: [1000,1050) and [1060,1100) under patch_flush, [1500,1700)
    # under harvest 150, emit 40, loop_pass itself 10
    assert sr.idle_by_span(HAND) == pytest.approx(
        {"": 0.0, "patch_flush": 90e-6, "harvest": 150e-6, "emit": 40e-6,
         "loop_pass": 10e-6, "admit": 0.0, "prefill": 0.0,
         "decode_dispatch": 0.0, "loop_wait": 0.0}, abs=1e-12)
    assert sr.idle_host_busy_share(HAND) == pytest.approx(100 * 140 / 3100)
    # bytes by hand: block of 2 steps, 3 slots, 90 cached tokens: (93 + 96)
    # x 512 + 2 x 3072 a layer; block of 1 step, 2 slots, 64: 66 x 512 +
    # 2048 a layer; two layers; 600 us of kernel time
    need = 2 * ((93 + 96) * 512 + 2 * 3072) + 2 * (66 * 512 + 2048)
    assert need == 277_504
    assert sr.paged_decode_roofline_traced(
        HAND, SIZES, {"hbm_bytes_per_s": 1e9}) == pytest.approx(
            100 * need / 1e9 / 600e-6)
    assert sr.engine_loop_busy_share(
        {"phase_loop_wait_s_total": 1.0, "phase_harvest_s_total": 2.0,
         "clock_s": 500.0},
        {"phase_loop_wait_s_total": 11.0, "phase_harvest_s_total": 22.0,
         "clock_s": 550.0}) == pytest.approx(40.0)


def test_engine_loop_busy_share_divides_by_the_replicas_clock():
    """A traced run's closing read comes when the capture has been written:
    64 s after the opening one, not the 51 the harness asked for. The loop
    waited 48 of those 64 s: busy 25 % (over 51 it would read 5.9, and a
    loop that waited 56 s would read negative)."""
    before = {"phase_loop_wait_s_total": 2.0, "phase_harvest_s_total": 3.0,
              "clock_s": 500.0}
    after = {"phase_loop_wait_s_total": 10.0, "phase_harvest_s_total": 43.0,
             "clock_s": 564.0}
    assert sr.engine_loop_busy_share(before, after) == pytest.approx(25.0)
    run = _run(None)
    run.update(stats_before=before, stats_after=after)
    assert run["window"]["seconds"] == 50.0      # not what it divides by
    assert common.load_module("metrics", "engine_loop_busy_share").reduce(
        run) == pytest.approx(25.0)
    # a read without the clock (a program before PR 39), or a clock that
    # did not move: nothing, never a share of the seconds asked for
    bare = {k: v for k, v in after.items() if k != "clock_s"}
    assert sr.engine_loop_busy_share(before, bare) is None
    assert sr.engine_loop_busy_share(bare, after) is None
    assert sr.engine_loop_busy_share(before, dict(after, clock_s=500.0)) \
        is None


def test_numerator_and_denominator_that_disagree_give_none():
    """The spans say 8 + 8 steps where the kernel calls count 2 + 1: more
    than one block apart, so no number."""
    bad = sr.from_rows(hand_rows(k_first=8, k_second=8))
    assert sr.decode_step_traced_ms(bad, 2) is None
    # within one block (8 + 1 against 2 + 1) the device's count stands
    near = sr.from_rows(hand_rows(k_first=8))
    assert sr.decode_step_traced_ms(near, 2) == pytest.approx(2.4 / 5)
    # and the roofline leaves the block whose steps differ out
    assert sr.paged_decode_roofline_traced(
        near, SIZES, {"hbm_bytes_per_s": 1e9}) == pytest.approx(
            100 * 2 * (66 * 512 + 2048) / 1e9 / 200e-6)


def test_idle_gaps_are_named_by_the_span_over_them():
    assert sr.name_idle_gaps(HAND) == [
        ["harvest before jit__lambda", pytest.approx(200e-6)],
        ["patch_flush before jit__lambda", pytest.approx(50e-6)],
        ["patch_flush before jit_impl", pytest.approx(40e-6)]]
    # the same gaps, in the same order, as the accepted breakdown lists
    events = [["/device:TPU:0", "XLA Ops", n, s, e - s]
              for n, s, e, _t, _c in HAND["ops"]]
    events += [["/device:TPU:0", "XLA Modules", n + "(1)", s, e - s]
               for n, s, e in HAND["modules"]]
    old = trace_reduce.summarise(events)["breakdown"]["idle_gaps"]
    assert [g for _n, g in old] == [g for _n, g in sr.name_idle_gaps(HAND)]


def test_loop_time_is_cut_into_innermost_spans():
    pieces = sr.leaf_spans(HAND)
    first = [(n, s // US, e // US) for n, s, e in pieces if e <= 1200 * US]
    assert first == [("admit", 500, 550), ("prefill", 550, 650),
                     ("admit", 650, 700), ("decode_dispatch", 700, 720),
                     ("patch_flush", 720, 1120),
                     ("decode_dispatch", 1120, 1150),
                     ("loop_pass", 1150, 1200)]
    # every instant of the three passes is under exactly one piece
    assert sum(e - s for _n, s, e in pieces) == (700 + 800 + 900) * US


def test_recorded_chip_trace():
    """0.63 s of mistral7b-serve-chat on the v5e: nine executions (k=1
    decode blocks between prefills and chunks), six dispatch spans; the
    first three executions were dispatched before the cut begins."""
    t = sr.load(RECORDED)
    m = sr.match_stream(t, 16)
    assert [(x["kind"], x.get("steps")) for x in m["executions"]] == [
        ("prefill", None), ("prefill", None), ("decode", 1.0),
        ("prefill", None), ("prefill", None), ("prefill", None),
        ("decode", 1.0), ("prefill", None), ("prefill", None)]
    assert (m["lead"], m["unfit"], len(m["pairs"])) == (3, 0, 6)
    assert [d["args"]["tokens"] for x, d in m["pairs"]
            if d["kind"] == "prefill"] == [303, 450, 262, 244, 343]
    assert sr.decode_step_traced_ms(t, 16) == pytest.approx(69.117, abs=1e-3)
    assert sr.prefill_traced_ms_per_ktok(t, 16) == pytest.approx(
        193.045, abs=1e-3)
    assert sr.model_op_share(t, trace_reduce.is_decode_program, SCOPES) \
        == pytest.approx(23.051, abs=1e-3)
    assert sr.idle_host_busy_share(t) == pytest.approx(1.4947, abs=1e-3)
    assert sr.paged_decode_roofline_traced(
        t, {"dim": 4096, "n_heads": 32, "n_kv_heads": 8, "n_layers": 16},
        common.peaks("TPU v5 lite")) == pytest.approx(9.618, abs=1e-2)
    by = sr.device_by_scope(t, trace_reduce.is_decode_program, SCOPES)
    # in these blocks of one step the pool's copies sit under the layer
    # scan (decode_step: the stacked pool sliced and written back) and
    # under kv_write, and outweigh the model's own ops
    assert by["decode_step"] > by["attn"] + by["mlp"] > 0.03
    assert by["kv_write"] > 0.01 and by["decode_block"] < 1e-6
    assert sr.name_idle_gaps(t)[0][0] == "patch_flush before jit_concatenate"


def test_span_is_split_into_decode_prefill_and_idle():
    """By hand: span 3100 us; decode blocks 1000 + 800 + 600 (the 10 us
    slot patch is neither), the prefill 400, idle 50 + 40 + 200."""
    assert sr.program_split(HAND, 2) == pytest.approx(
        {"span_s": 3100e-6, "decode_s": 2400e-6, "prefill_s": 400e-6,
         "idle_s": 290e-6})
    assert sr.prefill_program_share(HAND, 2) == pytest.approx(100 * 400 / 3100)
    # the reader prints what span_reduce's own summary prints
    rep = sr.report(HAND, 2, trace_reduce.is_decode_program, SCOPES)
    assert rep["split_s"] == sr.program_split(HAND, 2)


def test_prefill_program_share_on_the_recorded_trace_and_on_none():
    t = sr.load(RECORDED)
    split = sr.report(t, 16, trace_reduce.is_decode_program, SCOPES)["split_s"]
    reader = common.load_module("metrics", "prefill_program_share.chat")
    run = {"span_trace": t, "sizes": {"n_layers": 16}}
    assert reader.reduce(run) == pytest.approx(
        100 * split["prefill_s"] / split["span_s"])
    # seven prefill executions and two one-step decode blocks (PR 25's
    # engine: a cut made between prefills), idle 2.7 %
    assert split == pytest.approx({
        "span_s": 0.634599, "decode_s": 0.138234, "prefill_s": 0.436739,
        "idle_s": 0.016895}, abs=1e-6)
    assert reader.reduce(run) == pytest.approx(68.8213, abs=1e-3)
    assert split["decode_s"] + split["prefill_s"] + split["idle_s"] \
        <= split["span_s"]
    # a trace without a prefill program: nothing to read
    rows = [r for r in hand_rows() if r[1] != "jit_impl"
            and "jit(impl)" not in str(r[4])]
    run = {"span_trace": sr.from_rows(rows), "sizes": {"n_layers": 2}}
    assert sr.program_split(run["span_trace"], 2)["prefill_s"] == 0.0
    assert reader.reduce(run) is None
    assert reader.reduce({"span_trace": None, "sizes": {"n_layers": 2},
                          "trace_dir": None}) is None


# ---- a block of which only some layers call the paged kernel ---------------

HYBRID = {"dim": 2048, "n_heads": 32, "n_kv_heads": 8, "n_layers": 14,
          "attn_layers": 3}


def hybrid_rows():
    """3 attention layers of 14: a decode block of 8 steps makes 24 kernel
    calls, one of 2 steps makes 6; then a prefill."""
    kern = ("custom-call(kernel) bf16[4,8,8,64]", 10, KERNEL)
    conv = ("fusion bf16[4,2048]", 20, PATH + "conv/mul:")
    rows = []
    for name, start, end, ops in [
        ("jit__lambda", 0, 1120, ([kern] * 3 + [conv] * 4) * 8
         + [("fusion bf16[4,512]", 240, MLP)]),
        ("jit__lambda", 1200, 1800, ([kern] * 3 + [conv] * 4) * 2
         + [("fusion bf16[4,512]", 380, MLP)]),
        ("jit_impl", 1900, 2200, [("fusion bf16[1,128,32,64]", 300,
                                   "jit(impl)/prefill/while/body/attn/dot:")]),
    ]:
        got, t = _program(name, start, ops)
        assert t == end, (name, t)
        rows += got + [["module", name, start * US, (end - start) * US, "", 0]]
    rows += [
        _span("loop_pass", 0, 2300),
        _span("decode_dispatch", 10, 50, seq=1, k=8, w=4, active=4,
              ctx_tokens=400),
        _span("decode_dispatch", 60, 90, seq=2, k=2, w=4, active=4,
              ctx_tokens=432),
        _span("prefill", 100, 140, rid="c3", bucket=128, tokens=120),
    ]
    return rows


def test_steps_are_kernel_calls_over_the_layers_that_call_the_kernel():
    """ISSUE 29: 24 calls at ``attn_layers`` 3 are 8 steps; over the 14
    layers of the block they are no whole number and the execution is
    dropped, so every reader below would find nothing or the wrong thing."""
    t = sr.from_rows(hybrid_rows())
    assert sr.attn_layers(HYBRID) == 3 and sr.attn_layers(SIZES) == 2
    ex = sr.executions(t, sr.attn_layers(HYBRID))
    assert [(x["kind"], x.get("kernel_calls"), x.get("steps")) for x in ex] \
        == [("decode", 24, 8.0), ("decode", 6, 2.0), ("prefill", None, None)]
    assert [x["kind"] for x in sr.executions(t, 14)] == ["prefill"]
    m = sr.match_stream(t, 3)
    assert (m["lead"], m["unfit"], len(m["pairs"])) == (0, 0, 3)
    run = _run(t)
    run["sizes"] = HYBRID
    read = lambda name: common.load_module("metrics", name).reduce(run)  # noqa: E731
    assert read("decode_step_traced_ms.peak") == pytest.approx(1.72 / 10)
    assert read("prefill_traced_ms_per_ktok") == pytest.approx(0.3 / 0.12)
    assert read("prefill_program_share.peak") == pytest.approx(
        100 * 300 / 2200)
    # bytes: 3 layers a step, not 14; 4 slots, 400 (432) cached tokens as
    # the block starts, one more a slot each step; 300 us of kernel time
    hd = 64
    need = sum(3 * (((ctx + 4 * (i + 1)) * 8 * hd * 2 * 2)
                    + 4 * 32 * hd * 2 * 2)
               for ctx, k in ((400, 8), (432, 2)) for i in range(k))
    assert sr.paged_decode_roofline_traced(
        t, HYBRID, {"hbm_bytes_per_s": 1e9}) == pytest.approx(
            100 * need / 1e9 / 300e-6)
    # a family that states nothing: every layer calls the kernel
    run["sizes"] = {k: v for k, v in HYBRID.items() if k != "attn_layers"}
    assert read("decode_step_traced_ms.peak") is None
    assert sr.report(t, 3, trace_reduce.is_decode_program, SCOPES)[
        "stream"]["decode_steps"] == 10


# ---- the wire-format reader, on a file encoded here ------------------------

def _vi(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _f(no, val):
    """One field: int -> varint, bytes/str -> length-delimited."""
    if isinstance(val, int):
        return _vi(no << 3) + _vi(val)
    if isinstance(val, str):
        val = val.encode()
    return _vi(no << 3 | 2) + _vi(len(val)) + val


def _plane(name, stat_names, event_meta, lines):
    out = _f(2, name)
    for sid, sname in stat_names.items():
        out += _f(5, _f(1, sid) + _f(2, _f(1, sid) + _f(2, sname)))
    for mid, (mname, stats) in event_meta.items():
        out += _f(4, _f(1, mid) + _f(2, _f(1, mid) + _f(2, mname) + b"".join(
            _f(5, s) for s in stats)))
    for lname, t0, events in lines:
        out += _f(3, _f(2, lname) + _f(3, t0) + b"".join(
            _f(4, _f(1, mid) + _f(2, off) + _f(3, dur) + b"".join(
                _f(4, s) for s in stats)) for mid, off, dur, stats in events))
    return out


def test_xplane_file_is_read_without_any_proto_library(tmp_path):
    fusion = "%fusion.1 = bf16[2,8]{1,0} fusion(bf16[2,8]{1,0} %a), kind=kLoop"
    loop = "%while.2 = (s32[], bf16[2,8]{1,0}) while((s32[], bf16[2,8]) %t)"
    device = _plane(
        "/device:TPU:0", {1: "tf_op", 2: "flops"},
        {1: (fusion, [_f(1, 1) + _f(5, MLP), _f(1, 2) + _f(3, 12)]),
         2: ("jit__lambda(123)", []), 3: (loop, [])},
        [("XLA Ops", 1000, [(1, 5_000_000, 2_000_000, []),
                            (3, 4_000_000, 9_000_000, [])]),
         ("XLA Modules", 1000, [(2, 4_000_000, 9_000_000, [])])])
    double = _vi(2 << 3 | 1) + struct.pack("<d", 0.5)
    host = _plane(
        "/host:CPU", {1: "seq", 2: "rid", 3: "abc", 4: "load", 5: "k"},
        {1: ("rt/loop_pass", []), 2: ("rt/decode_dispatch", []),
         3: ("PjitFunction(<lambda>)", []), 4: ("rt/admit", [])},
        [("python3", 500, [(3, 0, 10, []), (4, 0, 1_000_000, [])]),
         ("python3", 2000, [
             (1, 1_000_000, 8_000_000, []), (3, 1_500_000, 10_000, []),
             (2, 2_000_000, 3_000_000,
              [_f(1, 1) + _f(4, 7), _f(1, 2) + _f(7, 3), _f(1, 4) + double,
               _f(1, 5) + _f(4, (1 << 64) - 1)])])])
    path = tmp_path / "w" / "plugins" / "profile" / "t" / "h.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(_f(1, _plane("/host:metadata", {}, {}, []))
                     + _f(1, device) + _f(1, host))
    # a second process's file: host plane only
    other = tmp_path / "a" / "plugins" / "profile" / "t" / "h.xplane.pb"
    other.parent.mkdir(parents=True)
    other.write_bytes(_f(1, host))
    assert sr.read_xplane(str(other)) is None
    t = sr.read_dir(str(tmp_path))
    assert t["ops"] == [("while (tuple)", 5000, 14000, "", True),
                        ("fusion bf16[2,8]", 6000, 8000, MLP, False)]
    assert t["modules"] == [("jit__lambda", 5000, 14000)]
    # the loop thread is the line that holds the passes; times in ns on
    # the line's own timestamp; int, interned string, double, negative int
    assert t["spans"] == [
        ("loop_pass", 3000, 11000, {}),
        ("decode_dispatch", 4000, 7000,
         {"seq": 7, "rid": "abc", "load": 0.5, "k": -1})]
    assert sr.read_dir(str(tmp_path / "nothing-here")) is None


# ---- the metric files, as run.py calls them --------------------------------

# entries of BENCHMARK.json: since PR 59 a reader's bare name is its entry
# that moves serve_tokens_per_s (peak among its cells)
NEW = ["decode_step_traced_ms.chat", "decode_step_traced_ms",
       "prefill_traced_ms_per_ktok", "model_op_share.chat",
       "model_op_share", "model_op_share.train",
       "idle_host_busy_share.chat", "idle_host_busy_share",
       "engine_loop_busy_share.chat", "engine_loop_busy_share",
       "paged_decode_roofline_traced", "prefill_program_share.chat",
       "prefill_program_share"]


def _run(trace, kind="serve"):
    return {"kind": kind, "span_trace": trace, "sizes": SIZES, "family": FAM,
            "device": {"kind": "TPU v5 lite"}, "window": {"seconds": 50.0},
            "stats_before": {"phase_loop_wait_s_total": 1.0, "clock_s": 7.0,
                             "phase_harvest_s_total": 2.0, "steps": 1},
            "stats_after": {"phase_loop_wait_s_total": 11.0, "clock_s": 57.0,
                            "phase_harvest_s_total": 22.0, "steps": 9},
            "trace": {"breakdown": {"device_ops": [], "idle_gaps": [
                ["in or before jit__lambda", 200e-6]]}}}


@pytest.mark.parametrize("metric", NEW)
def test_metric_file_reads_the_hand_trace(metric):
    entry = next(m for m in common.manifest()["per_layer"]
                 if m["name"] == metric)
    assert entry["workloads"] and entry["source"] in (
        "device_trace", "program_counter")
    run = _run(HAND, "train" if metric.endswith(".train") else "serve")
    value = common.load_module("metrics", metric).reduce(run)
    if metric.endswith(".train"):
        assert value is None          # no jit_step in a serve trace
        step = sr.from_rows([
            ["module", "jit_step", 0, 1000, "", 0],
            ["op", "fusion bf16[2,8]", 0, 600,
             "jit(step)/transpose(jvp(mlp))/dot_general:", 0],
            ["op", "copy bf16[2,8]", 600, 400, "", 0]])
        value = common.load_module("metrics", metric).reduce(
            _run(step, "train"))
        assert value == pytest.approx(60.0)
    assert isinstance(value, float) and value > 0
    if metric.startswith("idle_host_busy_share"):
        # the breakdown run.py prints afterwards names its gaps by span
        assert run["trace"]["breakdown"]["idle_gaps"][0] == [
            "harvest before jit__lambda", pytest.approx(200e-6)]


@pytest.mark.parametrize("metric", [m for m in NEW if not m.startswith(
    "prefill_program_share")])      # that one needs the programs' names only
def test_metric_file_finds_nothing_in_a_program_without_spans_or_scopes(
        metric):
    """The parent commit: programs found by jit name only, no rt/ span, no
    scope, no kernel name, no phase totals. None, and no exception."""
    rows = [[k, n, s, u, "" if k == "op" else i, c]
            for k, n, s, u, i, c in hand_rows() if k != "span"]
    run = _run(sr.from_rows(rows))
    run["stats_before"] = run["stats_after"] = {"steps": 3}
    assert common.load_module("metrics", metric).reduce(run) is None
    assert run["trace"]["breakdown"]["idle_gaps"][0][0].startswith("in or")
    # and with no trace at all (a run that was not traced, a rehearsal)
    run = _run(None)
    run["stats_before"] = run["stats_after"] = {}
    assert common.load_module("metrics", metric).reduce(run) is None


def test_trace_is_read_once_for_all_metrics_of_a_run(monkeypatch, tmp_path):
    calls = []
    monkeypatch.setattr(sr, "read_dir", lambda d: calls.append(d) or HAND)
    run = {"trace_dir": str(tmp_path)}
    assert sr.of_run(run) is HAND and sr.of_run(run) is HAND
    assert calls == [str(tmp_path)]
    assert sr.of_run({"trace_dir": None}) is None


def test_recorded_trace_round_trips_through_the_interned_form(tmp_path):
    rows = sr.to_rows(HAND, 0, 1 << 62)
    assert len(rows) == len(hand_rows())
    sr.dump(rows, str(tmp_path / "t.json"))
    assert sr.load(str(tmp_path / "t.json")) == HAND
    # a cut keeps only what lies wholly inside it
    cut = sr.from_rows(sr.to_rows(HAND, 1100 * US, 2500 * US))
    assert [m[0] for m in cut["modules"]] == ["jit_impl", "jit__lambda"]
    assert [s[0] for s in cut["spans"]] == [
        "loop_pass", "admit", "decode_dispatch", "patch_flush", "harvest",
        "emit", "admit", "prefill"]
