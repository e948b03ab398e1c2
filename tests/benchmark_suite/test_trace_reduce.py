"""The reduction from a trace to numbers, on a small trace recorded on the
v5e (benchmark/data/trace_serve_v5e.events.json: the first 12 ms of one
prefill program and one decode block of the chat cell, names cut to 260
characters) and on events built by hand; the kernels' bytes and FLOPs functions
against hand counts; the table of peaks."""

import os

import pytest

from benchmark import common, costs, trace_reduce as tr

RECORDED = os.path.join(common.HERE, "data", "trace_serve_v5e.events.json")
DEV = "/device:TPU:0"


def _ev(line, name, start, dur, plane=DEV):
    return [plane, line, name, start, dur]


HAND = [
    _ev("XLA Modules", "jit_step(123)", 0, 1000),
    _ev("XLA Ops", "%fusion.1 = bf16[2,8]{1,0} fusion(bf16[2,8] %a)", 0, 400),
    _ev("XLA Ops", "%fusion.2 = bf16[2,8]{1,0} fusion(bf16[2,8] %b)", 300, 300),
    _ev("XLA Ops", "%all-gather.3 = bf16[8,8]{1,0} all-gather(bf16[2,8] %c)",
        500, 300),
    _ev("Async XLA Ops", "%collective-permute-start.4 = bf16[2,8]{1,0} "
        "collective-permute-start(bf16[2,8] %d)", 550, 400),
    _ev("XLA Ops", '%k.5 = bf16[2,8]{1,0} custom-call(bf16[2,8] %e), '
        'custom_call_target="tpu_custom_call"', 900, 100),
    _ev("XLA Ops", "%while.6 = (s32[]{:T(128)}, bf16[2,8]{1,0:T(8,128)(2,1)}) "
        "while((s32[], bf16[2,8]) %t), body=%b", 0, 1000),
    _ev("", "host thread", -5000, 20000, plane="/host:CPU"),
]


def test_union_and_subtraction_of_intervals():
    assert tr.union_s([(0, 10), (5, 20), (30, 40)]) == pytest.approx(30e-9)
    assert tr.union_s([]) == 0.0
    assert tr.subtract_s([(0, 10), (20, 30)], [(5, 25)]) == pytest.approx(10e-9)


def test_op_names_are_parsed_from_hlo_text():
    assert tr.parse_op(HAND[1][2]) == ("fusion", "bf16[2,8]")
    assert tr.parse_op(HAND[6][2]) == ("while", "(tuple)")
    assert tr.short_name(HAND[5][2]) == "custom-call(kernel) bf16[2,8]"
    assert tr.is_kernel(HAND[5][2]) and not tr.is_kernel(HAND[1][2])
    assert tr.is_collective(HAND[3][2]) and tr.is_collective(HAND[4][2])
    assert tr.is_container(HAND[6][2]) and not tr.is_container(HAND[3][2])
    assert not tr.is_kernel('%c = bf16[8] custom-call(bf16[8] %x), '
                            'custom_call_target="ConcatBitcast"')


def test_hand_built_trace_reduces_to_hand_counts():
    s = tr.summarise(HAND)
    d = s["per_device"][DEV]
    # the device's own span, not the host thread's
    assert s["window_s"] == pytest.approx(1000e-9)
    # without the while container: [0,800) u [900,1000)
    leaf = tr.summarise([e for e in HAND if "while" not in e[2]])
    assert leaf["busy_s"] == pytest.approx(900e-9)
    assert s["busy_s"] == pytest.approx(1000e-9)     # a container covers all
    # leaf compute only, so that a container cannot hide a collective
    assert d["compute_busy_s"] == pytest.approx(700e-9)
    assert d["collective_exposed_s"] == pytest.approx(300e-9)
    # collectives [500,950); compute without the container [0,600) u [900,1000)
    lp = leaf["per_device"][DEV]
    assert lp["collective_s"] == pytest.approx(450e-9)
    assert lp["collective_exposed_s"] == pytest.approx(300e-9)
    assert d["kernel_calls"] == 1 and d["kernel_s"] == pytest.approx(100e-9)
    assert d["programs"] == {"jit_step": [1, pytest.approx(1e-6)]}
    # the container's time is not counted a second time among the ops
    assert "while (tuple)" not in d["ops"]
    assert d["ops"]["fusion bf16[2,8]"] == [2, pytest.approx(700e-9)]
    assert s["breakdown"]["device_ops"][0][0] == "fusion bf16[2,8]"
    assert leaf["breakdown"]["idle_gaps"][0] == [
        "in or before jit_step", pytest.approx(100e-9)]


def test_no_device_plane_reduces_to_nothing():
    assert tr.summarise([e for e in HAND if e[0] != DEV]) is None


def test_recorded_trace_of_the_chat_cell():
    s = tr.summarise(tr.load_events(RECORDED))
    d = s["per_device"][DEV]
    assert s["device_count"] == 1
    assert set(d["programs"]) >= {"jit__lambda", "jit_impl"}
    assert d["programs"]["jit__lambda"] == [1, pytest.approx(0.220353457)]
    assert d["programs"]["jit_impl"] == [1, pytest.approx(0.048320167)]
    assert tr.is_decode_program("jit__lambda", 0.220353457)
    assert tr.is_prefill_program("jit_impl", 0.048320167)
    # the slot-patch lambdas share the decode program's name, not its size
    assert not tr.is_decode_program("jit__lambda", 3e-6)
    assert d["kernel_calls"] == 4 and 1e-3 < d["kernel_s"] < 2e-3
    assert 0 < s["busy_s"] <= s["window_s"]
    names = [n for n, _ in s["breakdown"]["device_ops"]]
    assert "custom-call(kernel) bf16[16,8,16,128]" in names
    assert len(names) <= 10 and len(s["breakdown"]["idle_gaps"]) <= 10


def test_kernel_bytes_and_flops_against_hand_counts():
    fl = costs.flash_attention_flops(2, 2048, 32, 128)
    assert fl["fwd"] == 2 * 2 * 32 * 2048 * 2048 * 128
    assert fl["bwd"] == 2.5 * fl["fwd"]
    by = costs.flash_attention_bytes(2, 2048, 32, 128)
    assert by["fwd"] == 4 * 2 * 2048 * 32 * 128 * 2
    # two slots, 100 and 300 live tokens: K and V once each, q in, o out
    assert costs.paged_decode_bytes([100, 300], 8, 128, 32) == (
        400 * 8 * 128 * 2 * 2 + 2 * 32 * 128 * 2 * 2)
    peak = common.peaks("TPU v5 lite")
    t, side = costs.roofline_s(fl["fwd"], by["fwd"], peak)
    assert side == "compute" and t == pytest.approx(fl["fwd"] / 197e12)
    t, side = costs.roofline_s(1e6, 819e9, peak)
    assert side == "bandwidth" and t == pytest.approx(1.0)


def test_peaks_table_raises_on_an_unknown_device():
    assert common.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    for kind in ("cpu", "TPU v9", "_source"):
        with pytest.raises(KeyError):
            common.peaks(kind)
