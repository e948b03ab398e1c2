"""Share of ``setup_s`` the start-up ledger does not see: the part of
``[t0 - setup_s, t0]`` under no stage, no program's first dispatch and not
the cell's ``ramp_s``, over ``setup_s``: the harness and the runtime above
the worker (``ray_tpu.init``, ``serve.run``), gaps between stages, the
warm-up requests' own service time. Where it lies goes to standard error
as one ``benchmark:`` line. A program without the ledger reports nothing.
program_span."""

import sys

from benchmark import startup_reduce


def reduce(run):
    line = startup_reduce.detail(run)
    if line is not None:
        print(f"benchmark: {line}", file=sys.stderr)
    return startup_reduce.untraced_share(run)
