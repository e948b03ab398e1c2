"""Mean time the backend took to hand over an executable the persistent
compile cache held (``load_s`` of the start-up ledger), over the programs
first dispatched before the window that hit the cache, in ms. Nothing
where none hit (a cold start) or the program keeps no ledger.
program_span."""

from benchmark import startup_reduce


def reduce(run):
    return startup_reduce.load_ms_per_program(run)
