"""Model FLOP/s utilisation: the FLOPs the forward and backward passes
need per token (the family's own count, benchmark/models/<family>.py: for
the dense block 6 per matmul parameter plus causal attention;
recomputation not counted) times tokens per second, over chips times the
bf16 peak. Over the steps' own times, not the window's wall: it is read in
the traced run, where starting and stopping the profiler sits between
steps. host_clock + peaks.json."""

from benchmark import common


def reduce(run):
    t = run["train"]
    tps = len(t["window_step_s"]) * t["tokens_per_step"] / sum(t["window_step_s"])
    peak = common.peaks(run["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * run["family"].train_flops_per_token(
        run["sizes"], t["seq_len"]) * tps / (t["chips"] * peak)
