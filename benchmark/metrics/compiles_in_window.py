"""Programs the engine compiled while requests were in flight, inside the
window: ``mid_traffic_compiles`` after minus before. 0 by the closed warm
set. program_counter."""


def reduce(run):
    return float(run["stats_after"]["mid_traffic_compiles"]
                 - run["stats_before"]["mid_traffic_compiles"])
