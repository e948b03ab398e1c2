"""Between the two reads of /v1/stats at the window's edges: 1 - (time the
engine loop spent parked in ``loop_wait`` or blocked in ``harvest``) / the
delta of the replica's ``clock_s``, from the running totals
``phase_<p>_s_total``. Near 100 % means the host loop sets the pace.
program_counter."""

from benchmark import span_reduce


def reduce(run):
    return span_reduce.engine_loop_busy_share(
        run["stats_before"], run["stats_after"])
