"""Device time of prefill and prefill-chunk programs in the traced window
per thousand prompt tokens prefilled in it (prompt tokens of the requests
whose first token came inside the traced window). device_trace."""

from benchmark import trace_reduce


def reduce(run):
    t, marks = run.get("trace"), run.get("trace_marks") or {}
    if not t or marks.get("t_start") is None:
        return None
    toks = sum(r.get("prompt_tokens") or r["prompt_tokens_asked"]
               for r in run["records"] if r.get("first") is not None
               and marks["t_start"] <= r["first"] <= marks["t_stop"])
    _n, seconds = trace_reduce.program_time(t, trace_reduce.is_prefill_program)
    return 1e3 * seconds / (toks / 1e3) if toks else None
