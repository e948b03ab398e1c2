"""1 - (union of the device's op intervals) / traced window, averaged over
the chips. device_trace."""


def reduce(run):
    t = run.get("trace")
    if not t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
