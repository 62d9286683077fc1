"""The benchmark's FLOP and byte counts: equal to a hand count on a tiny
layout, and blind to block size, dead slots and padding."""

import numpy as np
import pytest

from _bench_path import BENCH  # noqa: F401

import counts

S = 16


def _pairs():
    """A hand-made admitted set: the 4 x 4 diagonal tiles, a global row
    and column, and three scattered pairs."""
    ok = np.zeros((S, S), bool)
    for i in range(0, S, 4):
        ok[i:i + 4, i:i + 4] = True
    ok[0, :] = ok[:, 0] = True
    ok[5, 13] = ok[13, 5] = ok[9, 2] = True
    return ok


def _layout(ok, bq, bk, mb_pad=0, buckets_full=False):
    """Express the admitted set as (block_idx, buckets) at block size
    (bq, bk); ``mb_pad`` extra dead slots per row."""
    nq, nk = S // bq, S // bk
    rows = []
    for i in range(nq):
        js = [j for j in range(nk)
              if ok[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk].any()]
        rows.append(js)
    mb = max(len(r) for r in rows) + mb_pad
    bi = np.full((nq, mb), -1, np.int32)
    bu = np.full((nq, mb, bq, bk), -1, np.int8)
    for i, js in enumerate(rows):
        for m, j in enumerate(js):
            bi[i, m] = j
            tile = ok[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk]
            bu[i, m] = np.where(tile | buckets_full, 1, -1)
    return bi, bu


def test_pairs_hand_count():
    ok = _pairs()
    want = 4 * 16 + (S - 4) * 2 + 3   # tiles, row and column, 3 pairs
    assert int(ok.sum()) == want
    bi, bu = _layout(ok, 4, 4)
    assert counts.admitted_pairs(bi, bu, 4, 4) == want


@pytest.mark.parametrize("bq,bk,pad", [(4, 4, 0), (8, 8, 0), (2, 4, 3),
                                       (8, 2, 1), (16, 16, 2)])
def test_counts_blind_to_blocking_and_padding(bq, bk, pad):
    ok = _pairs()
    bi, bu = _layout(ok, bq, bk, mb_pad=pad)
    pairs = counts.admitted_pairs(bi, bu, bq, bk)
    assert pairs == int(ok.sum())
    kc = counts.attn_kernel_counts(seq=S, heads=2, kv_heads=2, d_head=8,
                                   pairs=pairs)
    ref_bi, ref_bu = _layout(ok, 4, 4)
    assert kc == counts.attn_kernel_counts(
        seq=S, heads=2, kv_heads=2, d_head=8,
        pairs=counts.admitted_pairs(ref_bi, ref_bu, 4, 4))


def test_kernel_counts_hand():
    P = 40
    kc = counts.attn_kernel_counts(seq=S, heads=2, kv_heads=2, d_head=8,
                                   pairs=P)
    # q = k = v = o = 16 x 2 x 8 x 2 bytes = 512; lse/delta 16 x 2 x 4
    assert kc["fwd"] == (64.0 * P, 512 * 4 + 128 + P)
    assert kc["dq"] == (64.0 * P, 512 * 5 + 256 + P)
    assert kc["dkv"] == (64.0 * P, 512 * 6 + 256 + P)


def test_model_flops_hand():
    m = {"d_model": 4, "n_heads": 2, "n_kv_heads": 2, "d_head": 2,
         "d_ff": 8, "feat_dim": 3, "n_classes": 5, "n_layers": 2,
         "lap_pe_dim": 0}
    # per token: feat 2*3*4 + head 2*4*5 = 64; per layer and token:
    # qkv 2*4*6*2 + o 2*2*2*4 + mlp 3*2*4*8 = 320; attention 4*2*2 a pair
    assert counts.forward_flops(m, S, 40) == 16 * 64 + 2 * (16 * 320
                                                           + 16 * 40)
    assert counts.step_flops(m, S, 40) == 3 * 12544


def test_live_share_and_least_time():
    bi = np.array([[0, 1, -1, -1], [1, -1, -1, -1]])
    assert counts.live_share(bi) == 3 / 8
    pk = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.least_seconds(200.0, 10.0, pk) == (2.0, "compute")
    assert counts.least_seconds(100.0, 50.0, pk) == (5.0, "memory")
