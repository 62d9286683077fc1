"""Model FLOP utilisation of the whole training step: the window's required
model FLOPs (bench/counts.py, backward as twice the forward, recomputation
not counted) over the window's host-clock seconds, over chips x the bf16
peak (bench/peaks.py)."""


def read(r):
    if not (r.get("window_s") and r.get("model_flops") and r.get("peaks")):
        return None
    return 100.0 * r["model_flops"] / r["window_s"] / (
        r["chips"] * r["peaks"]["bf16_flops"])
