"""Share of the cluster-attention kernels' roofline in the traced period:
the least time the chip could take for the required kernel work (the
larger of FLOPs over peak FLOP/s and bytes over peak HBM bandwidth,
bench/counts.py, per kernel and call) summed over the traced sparse
steps' calls, over the kernels' summed device time, in percent."""


def read(r):
    t = r.get("trace") or {}
    k = sum((t.get("kernel_s") or {}).values())
    least = r.get("kernel_least_s")
    if not k or not least:
        return None
    return 100.0 * least / k
