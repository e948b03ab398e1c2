"""Tokens the engine recorded per decode step it dispatched, over the
window: batch occupancy as the engine counts it (32 slots at most).
program_counter."""


def reduce(run):
    a, b = run["stats_before"], run["stats_after"]
    steps = b["steps"] - a["steps"]
    return (b["tokens_out"] - a["tokens_out"]) / steps if steps else None
