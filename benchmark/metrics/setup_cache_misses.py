"""Programs first dispatched before the window of which the backend
compiled any part, plus the executables compiled under no compile scope
(``startup.unscoped.misses``). 0 in a warm run: a run that reads more was
not warm, and its ``setup_s`` is another quantity. A program without the
ledger reports nothing. program_counter."""

from benchmark import startup_reduce


def reduce(run):
    return startup_reduce.cache_misses(run)
