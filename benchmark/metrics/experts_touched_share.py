"""Experts touched a routed layer and decode step, over the experts the
replica holds: the engine's ``experts_touched_total`` over
``routed_layer_steps_total`` (decode blocks harvested inside the window)
over ``n_experts``. At 100 % every step reads every expert's weights, and
the routed layer's least time is all of them over the bandwidth. A program
without these counters reports nothing. program_counter."""


def reduce(run):
    a, b = run["stats_before"], run["stats_after"]
    keys = ("experts_touched_total", "routed_layer_steps_total")
    held = run["sizes"].get("n_experts")
    if not held or any(k not in a or k not in b for k in keys):
        return None
    steps = b[keys[1]] - a[keys[1]]
    return 100.0 * (b[keys[0]] - a[keys[0]]) / (steps * held) if steps \
        else None
