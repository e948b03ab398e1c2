"""Share of the decode programs' op time in ops under the ``attn_full``
scope: the reads of the full layers of a block that also has window
layers (the paged kernel over every live page). A trace without that
scope reports nothing. device_trace."""

from benchmark.metrics import window_attn_share

SCOPE = "attn_full"


def reduce(run):
    return window_attn_share.scope_share(run, SCOPE)
