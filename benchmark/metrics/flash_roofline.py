"""The flash-attention kernels' share of their roofline in the train step:
the least time one chip needs for the forward and backward attention of
its share of the batch in every layer (max of FLOPs / peak and bytes /
bandwidth, benchmark/costs.py; the forward that remat runs again is not
counted as useful) over the time the kernel calls took, per traced step.
device_trace."""

from benchmark import common, costs


def reduce(run):
    t = run.get("trace")
    if not t:
        return None
    dev = next(iter(t["per_device"].values()))
    steps = sum(n for name, (n, _s) in dev["programs"].items()
                if name.startswith("jit_step"))
    if not steps or not dev["kernel_s"]:
        return None
    tr, sz = run["train"], run["sizes"]
    b = tr["global_batch"] // tr["chips"]
    hd = sz["dim"] // sz["n_heads"]
    fl = costs.flash_attention_flops(b, tr["seq_len"], sz["n_heads"], hd)
    by = costs.flash_attention_bytes(b, tr["seq_len"], sz["n_heads"], hd)
    peak = common.peaks(run["device"]["kind"])
    need = sum(costs.roofline_s(fl[k], by[k], peak)[0] for k in ("fwd", "bwd"))
    return 100.0 * need * sz["n_layers"] / (dev["kernel_s"] / steps)
