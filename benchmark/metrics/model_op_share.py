"""Share of the main programs' op time (the decode blocks of a serve cell,
the train step of a train cell) in ops under one of the model family's own
scopes (``MODEL_SCOPES`` of benchmark/models/<family>.py; the dense block:
embed, norm, attn, mlp, lm_head, sample, loss, optimizer). The rest is
kv_write, state gather and scatter, scan carries, and the compiler's
copies. device_trace."""

from benchmark import span_reduce, trace_reduce


def is_train_step(name: str, _seconds: float) -> bool:
    return name.startswith("jit_step")


def reduce(run):
    trace = span_reduce.of_run(run)
    if trace is None:
        return None
    return span_reduce.model_op_share(
        trace, is_train_step if run["kind"] == "train"
        else trace_reduce.is_decode_program, run["family"].MODEL_SCOPES)
