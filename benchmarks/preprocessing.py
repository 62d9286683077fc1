"""Paper §IV-E: preprocessing (reorder + condition check + reformation)
cost as a share of end-to-end training time."""

from __future__ import annotations

from benchmarks.common import GraphTrainBench, row


def main(full=False):
    epochs = 60
    bench = GraphTrainBench(arch="graphormer_slim", n=1024)
    prep_s = bench.prep_seconds
    hist, t_epoch, acc = bench.train("torchgt", epochs=epochs)
    total = t_epoch * epochs
    row("sec4e_preprocessing", prep_s * 1e6,
        f"train_total={total:.2f}s share={prep_s/(prep_s+total)*100:.1f}% "
        f"cut_ratio={bench.prep.cut:.3f}")


if __name__ == "__main__":
    main()
