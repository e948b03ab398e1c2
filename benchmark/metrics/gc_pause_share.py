"""Share of the replica's wall time inside full (generation 2) garbage
collections, on any thread of its process: delta ``gc_pause_s_total`` /
delta ``clock_s`` of /v1/stats between ``stats_before`` and
``stats_after``, the longest interval a run has (an event every 25 s needs
it; in a traced run it ends when the capture has been written, and the
clock knows). A collection holds the GIL: the engine loop stands still for
all of it. A program without the counter reports nothing.
program_counter."""

from benchmark import stall_reduce


def reduce(run):
    return stall_reduce.counter_share(
        run.get("stats_before"), run.get("stats_after"), "gc_pause_s_total")
