"""Mean host-clock time of the window's dense interleave steps."""


def read(r):
    s = r.get("step_s", {}).get("dense") or []
    return 1e3 * sum(s) / len(s) if s else None
