"""Passes a slot ran for each token it gave, over the window: the engine's
``slot_passes_total`` (live slots summed over the passes of the block
dispatches harvested) over ``tokens_out``. A block of B tokens costs S
denoise passes and a commit pass: (S + 1) / B when nothing is cut (0.75 at
B 4, S 2); what a stop token, ``max_tokens`` or a dispatch past a finished
stream discards comes on top. A program without the counter reports
nothing. program_counter."""


def reduce(run):
    a, b = run["stats_before"], run["stats_after"]
    if "slot_passes_total" not in a or "slot_passes_total" not in b:
        return None
    tokens = b["tokens_out"] - a["tokens_out"]
    passes = b["slot_passes_total"] - a["slot_passes_total"]
    return passes / tokens if tokens and passes else None
