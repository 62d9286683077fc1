"""Live block_idx slots over all nq x mb_cap slots of the active layout, in
percent: the share of the kernels' grid that does work (a count)."""


def read(r):
    v = r.get("grid_live_share")
    return None if v is None else 100.0 * v
