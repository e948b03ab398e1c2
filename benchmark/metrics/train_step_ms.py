"""Median host-clock time of one train step of the window, each ending in
a device sync on the loss. host_clock."""

import statistics


def reduce(run):
    return 1e3 * statistics.median(run["train"]["window_step_s"])
