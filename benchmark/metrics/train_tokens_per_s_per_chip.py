"""Tokens of the steps completed in the window, per second and chip, over
the window's wall time: from its first step's start to its last step's
device sync, whatever happened between steps. host_clock."""


def reduce(run):
    t = run["train"]
    return (len(t["window_step_s"]) * t["tokens_per_step"]
            / t["window_wall_s"] / t["chips"])
