"""Paper Figs. 7 & 9: scalability.

(a) Fig 9a — max trainable sequence length vs device count: analytic
    activation-memory model calibrated by the dry-run memory analysis;
    GP-RAW (O(S^2) scores) vs TorchGT (O(S) with graph parallelism).
(b) §III-C comm-complexity claim — a2a volume O(S/P) vs all-gather O(S):
    measured from compiled HLO at P in {2,4,8} (fake devices, subprocess).
(c) sparse path — per-device all-to-all volume of the sharded
    cluster-sparse attention (parallel/cluster_parallel.py) from compiled
    HLO: the comm cost of the full Cluster-aware Graph Parallelism
    composition, not just the dense a2a primitive.

All mesh/shard_map construction goes through repro.compat.
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

from benchmarks.common import row

HBM = 16e9  # v5e
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def max_seq_len(n_dev: int, *, d=64, n_layers=4, n_heads=8, mode: str):
    """Largest S (per replica) fitting activation memory on n_dev chips."""
    # bf16 activations; per layer: h (S,d) x ~8 buffers + attention
    per_tok = 8 * d * 2 * n_layers
    budget = n_dev * HBM * 0.6
    if mode == "raw":
        # dense scores (S, S) per head materialized (no flash): dominates
        import math
        a = n_heads * n_layers * 4.0
        return int(math.sqrt(budget / a))
    # torchgt: O(S) activations, sequence sharded over devices
    return int(budget / per_tok)


def _subprocess(code: str, p: int):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={p}"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=300, env=env)
    out = {}
    for line in r.stdout.splitlines():
        parts = line.split()
        if len(parts) == 2 and parts[1].lstrip("-").isdigit():
            out[parts[0]] = int(parts[1])
    if not out and r.returncode != 0:
        print(f"-- comm_volume subprocess failed (P={p}):\n{r.stderr}",
              file=sys.stderr)
    return out


def comm_volume(p: int):
    """Per-device a2a vs all-gather bytes for one attention layer at fixed
    global S, measured from HLO on p fake devices."""
    code = f"""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro import compat
        from repro.analysis.ir.hlo import comm_summary
        mesh = compat.make_mesh(({p},), ("model",))
        B, S, H, Dh = 1, 4096, {p}, 64
        x = jax.ShapeDtypeStruct((B, S // {p}, H, Dh), jnp.bfloat16)

        def a2a(q):
            return compat.shard_map(
                lambda ql: jax.lax.all_to_all(ql, "model", 2, 1, tiled=True),
                mesh=mesh, in_specs=P(None, "model", None, None),
                out_specs=P(None, None, "model", None))(q)

        def ag(q):
            return compat.shard_map(
                lambda ql: jax.lax.all_gather(ql, "model", axis=1,
                                              tiled=True),
                mesh=mesh, in_specs=P(None, "model", None, None),
                out_specs=P(None, None, None, None))(q)

        for name, fn in (("a2a", a2a), ("ag", ag)):
            txt = jax.jit(fn).lower(x).compile().as_text()
            print(name, int(comm_summary(txt)["total_bytes"]))
    """
    return _subprocess(code, p)


def sparse_comm_volume(p: int, *, seq: int = 4096, heads: int = 8,
                       d_head: int = 64, bq: int = 128):
    """Per-device all-to-all bytes of the sharded cluster-sparse attention
    layer (LM local+global layout) from compiled HLO, plus its dot FLOPs —
    the O(S/P) comm / O(active_blocks) compute point of §III-C."""
    code = f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro import compat
        from repro.core.reformation import lm_local_global_layout
        from repro.analysis.ir.hlo import comm_summary
        from repro.parallel.cluster_parallel import sharded_cluster_attention
        p, S, H, Dh, bq = {p}, {seq}, {heads}, {d_head}, {bq}
        mesh = compat.make_mesh((p,), ("model",))
        lay = lm_local_global_layout(S, bq=bq, bk=bq, window=1024,
                                     n_global=bq)
        bidx = jnp.asarray(lay.block_idx)[None]
        q = jax.ShapeDtypeStruct((1, S, H, Dh), jnp.bfloat16)
        fn = jax.jit(lambda a, b, c: sharded_cluster_attention(
            a, b, c, bidx, mesh=mesh, axis="model", dp_axes=(),
            bq=bq, bk=bq, causal=True))
        with compat.use_mesh(mesh):
            txt = fn.lower(q, q, q).compile().as_text()
        cs = comm_summary(txt)
        print("a2a", int(cs["bytes"]["all-to-all"]))
        print("total", int(cs["total_bytes"]))
        print("flops", int(cs["flops"]))
    """
    return _subprocess(code, p)


def main(full=False):
    for n_dev in (1, 8, 64, 256):
        s_raw = max_seq_len(n_dev, mode="raw")
        s_gt = max_seq_len(n_dev, mode="torchgt")
        row(f"fig9a_maxseq_{n_dev}dev", 0.0,
            f"gp_raw={s_raw} torchgt={s_gt} ratio={s_gt/max(s_raw,1):.0f}x")
    for p in (2, 4, 8):
        v = comm_volume(p)
        if "a2a" in v and "ag" in v:
            row(f"fig7_comm_P{p}", 0.0,
                f"a2a_bytes={v['a2a']} allgather_bytes={v['ag']} "
                f"ratio={v['ag']/max(v['a2a'],1):.2f}x")
    for p in (2, 4, 8):
        v = sparse_comm_volume(p)
        if "a2a" in v:
            row(f"sparse_comm_P{p}", 0.0,
                f"a2a_bytes_per_dev={v['a2a']} coll_bytes={v['total']} "
                f"sparse_flops_per_dev={v['flops']}")


if __name__ == "__main__":
    main()
