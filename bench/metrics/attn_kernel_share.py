"""Device time of the three cluster-attention kernels (forward, dQ, dK/dV)
over the device's busy time in the traced period, in percent."""


def read(r):
    t = r.get("trace") or {}
    k = sum((t.get("kernel_s") or {}).values())
    if not k or not t.get("busy_s"):
        return None
    return 100.0 * k / t["busy_s"]
