"""Time a chip spends in collectives while none of its compute ops runs,
as a share of the traced window, averaged over the chips. device_trace."""


def reduce(run):
    t = run.get("trace")
    if not t:
        return None
    return 100.0 * t["collective_exposed_s"] / t["window_s"]
