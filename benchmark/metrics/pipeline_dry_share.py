"""Share of the replica's wall time between the last moment the engine
loop knew the device busy and a dispatch that found it with nothing queued
while slots were live: delta ``dry_s_total`` / delta ``clock_s`` of
/v1/stats from ``stats_before`` to the last once-a-second read the capture
has not touched (``stall_reduce.last_untraced``: about 20 s). The host
cannot know when the device finished, so this is an UPPER bound of the
device idle it stands for. A program without the counter reports nothing.
program_counter."""

from benchmark import stall_reduce


def reduce(run):
    return stall_reduce.untraced_share(run, "dry_s_total")
