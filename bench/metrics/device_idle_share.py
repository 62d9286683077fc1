"""1 - (union of the device's op intervals) / (traced window), in percent,
averaged over the cell's chips."""


def read(r):
    t = r.get("trace") or {}
    if not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
