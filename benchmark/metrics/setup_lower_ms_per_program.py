"""Mean time a program's first dispatch spent before the backend saw it:
the outermost trace plus jaxpr -> MLIR (``trace_s + lower_s`` of the
start-up ledger), over the programs first dispatched before the window,
in ms. A hit of the compile cache saves none of it. A program without
the ledger reports nothing. program_span."""

from benchmark import startup_reduce


def reduce(run):
    return startup_reduce.lower_ms_per_program(run)
