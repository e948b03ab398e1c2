"""Share of the device's op time in the traced window under the
``lm_head`` scope. Read where a configuration's cut in depth keeps the
whole head beside a few layers (a pipeline's first stage that also holds
the last stage's head): the head's share of a step then reads several
times what the deployment's would, and this number says by how much the
cut distorts the step. Only a configuration whose sizes state ``ssm_heads``
reads it; a trace without the scope reads nothing. device_trace."""

from benchmark import common

SCOPE = "lm_head"


def reduce(run):
    if not run["sizes"].get("ssm_heads"):
        return None
    return common.load_module("metrics", "ssm_mixer_share").share(run, SCOPE)
