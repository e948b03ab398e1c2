"""Share of the device's traced span in which the device is idle AND a
full garbage collection runs on some thread of the replica (the ``rt/gc``
events of EVERY line of the host plane: ``benchmark/stall_reduce.py``).
Also renames the breakdown's longest idle gaps (as ``idle_host_busy_share``
names them) so that one mostly under a collection reads ``gc_in_<loop
span> before <program>``. A program without the collector's watch reports
nothing and leaves the breakdown as it is. device_trace + program_span."""

from benchmark import span_reduce, stall_reduce


def reduce(run):
    trace = span_reduce.of_run(run)
    gcs = stall_reduce.gc_of_run(run)
    if trace is None or gcs is None:
        return None
    breakdown = (run.get("trace") or {}).get("breakdown")
    if breakdown is not None and trace["spans"]:
        breakdown["idle_gaps"] = stall_reduce.name_idle_gaps(trace, gcs)
    return stall_reduce.idle_gc_share(trace, gcs)
