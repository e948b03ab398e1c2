"""Share of the replica's wall time inside stalls of the engine loop's
host: spans of host work (any but ``loop_pass``, ``loop_wait``,
``harvest``) whose own time reached 50 ms: delta ``host_stall_s_total`` /
delta ``clock_s`` of /v1/stats, over the same interval as
``pipeline_dry_share``. A program without the counter reports nothing.
program_counter."""

from benchmark import stall_reduce


def reduce(run):
    return stall_reduce.untraced_share(run, "host_stall_s_total")
