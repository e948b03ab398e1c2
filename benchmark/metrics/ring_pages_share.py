"""Pages the window layers' rings hold over what the same requests would
hold for such a layer without the ring, mean of the once-a-second samples
of the window: the engine's ``window_pages_in_use`` over
``full_pages_in_use`` (both a layer: a request's growing table is what a
window layer would keep beside a full one). An engine without these gauges,
or without window layers, reports nothing. program_counter."""


def reduce(run):
    vals = [s["window_pages_in_use"] / s["full_pages_in_use"]
            for _t, s in run["stats_samples"]
            if s.get("window_pages_in_use") and s.get("full_pages_in_use")]
    return 100.0 * sum(vals) / len(vals) if vals else None
