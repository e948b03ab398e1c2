"""Share of the device's op time in the traced window under the ``ssm``
scope: a state-space mixer's two projections, its convolution, the scan
over a prefill's columns or the update of a decode step's, the writes of
its state, its gate and norm: what the mixer that keeps a state and no
pages costs of a step, where a slot's cost does not grow with its context.
A trace without the scope reads nothing. device_trace."""

from benchmark import span_reduce

SCOPE = "ssm"


def share(run, scope: str):
    """Share (%) of the leaf ops' time under ``scope``, or None."""
    trace = span_reduce.of_run(run)
    if trace is None:
        return None
    total = under = 0.0
    for _n, s, e, tf, container in trace["ops"]:
        if container:
            continue
        total += e - s
        if scope in span_reduce.scope_path(tf):
            under += e - s
    return 100.0 * under / total if under else None


def reduce(run):
    return share(run, SCOPE)
