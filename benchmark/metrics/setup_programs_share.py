"""Share of ``setup_s`` spent in the first dispatches of the engine's
programs: the sum of ``wall_s`` (tracing, lowering, compile or load,
dispatch) of the programs the start-up ledger recorded before the window
opened, the warm-up requests' prefill and chunk programs included, over
``setup_s``. A program without the ledger reports nothing.
program_span."""

from benchmark import startup_reduce


def reduce(run):
    return startup_reduce.programs_share(run)
