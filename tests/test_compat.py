"""Compat layer coverage: (a) every repro.* module imports, (b) 1-D and
2-D meshes build under 8 fake CPU devices, (c) the sharded cluster-sparse
attention path matches the single-device jnp oracle on a 4-way model axis
(the Cluster-aware Graph Parallelism composition), (d) axis types and the
ambient-mesh context take the installed JAX's spellings.

Multi-device parts run in subprocesses (XLA_FLAGS must be set before jax
initializes); single-device compat semantics run in-process."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _subproc import run_code as _run

from repro import compat


# --------------------------------------------------------------- in-process

def test_make_mesh_single_device():
    mesh = compat.make_mesh((1,), ("data",))
    assert mesh.shape == {"data": 1}
    with compat.use_mesh(mesh):
        pass  # context enters and exits cleanly


def test_make_mesh_rejects_shape_name_mismatch():
    with pytest.raises(ValueError):
        compat.make_mesh((1, 1), ("data",))


def test_sharded_cluster_attention_single_device_fallback():
    """p == 1 short-circuits to the oracle — no shard_map, same numbers."""
    from repro.core.dual_attention import cluster_sparse_attention
    from repro.parallel.cluster_parallel import sharded_cluster_attention

    mesh = compat.make_mesh((1,), ("model",))
    B, S, H, Dh, bq = 1, 128, 2, 8, 32
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, S, H, Dh))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, H, Dh))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, H, Dh))
    nq = S // bq
    # diagonal blocks only, one -1 pad slot per row
    bidx = jnp.asarray(np.stack([np.arange(nq), np.full(nq, -1)], 1),
                       jnp.int32)[None]
    ref = cluster_sparse_attention(q, k, v, bidx, bq=bq, bk=bq)
    out = sharded_cluster_attention(q, k, v, bidx, mesh=mesh, bq=bq, bk=bq)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_make_mesh_forwards_explicit_axis_types():
    """Auto on every axis by default; an explicit tuple is forwarded."""
    from jax.sharding import AxisType

    mesh = compat.make_mesh((1,), ("x",))
    assert tuple(mesh.axis_types) == (AxisType.Auto,)
    mesh = compat.make_mesh((1,), ("x",), axis_types=(AxisType.Explicit,))
    assert tuple(mesh.axis_types) == (AxisType.Explicit,)


def test_use_mesh_installs_ambient_mesh():
    """``use_mesh`` is ``jax.set_mesh``: inside it the mesh is ambient."""
    mesh = compat.make_mesh((1,), ("data",))
    with compat.use_mesh(mesh):
        assert dict(jax.sharding.get_abstract_mesh().shape) == {"data": 1}
    assert not jax.sharding.get_abstract_mesh().shape


# -------------------------------------------------------------- subprocess

def test_all_modules_import_and_meshes_build():
    out = _run("""
        import importlib, pkgutil
        import jax
        import repro
        from repro import compat

        failed = []
        for m in sorted(set(mi.name for mi in pkgutil.walk_packages(
                repro.__path__, "repro."))):
            try:
                importlib.import_module(m)
            except Exception as e:  # noqa: BLE001
                failed.append((m, repr(e)))
        assert not failed, failed

        assert len(jax.devices()) == 8
        m1 = compat.make_mesh((8,), ("data",))
        assert m1.shape == {"data": 8}
        m2 = compat.make_mesh((2, 4), ("data", "model"))
        assert m2.shape == {"data": 2, "model": 4}
        with compat.use_mesh(m2):
            pass
        from repro.launch.mesh import make_host_mesh
        mh = make_host_mesh(model=4)
        assert mh.shape == {"data": 2, "model": 4}
        print("OK")
    """)
    assert "OK" in out


def test_sharded_cluster_attention_matches_oracle():
    """4-way model-axis sharded cluster-sparse attention == jnp oracle, on
    a real reformed SBM layout with bucket masks + head-sharded bias."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import compat
        from repro.core.dual_attention import cluster_sparse_attention
        from repro.core.graph import sbm_graph
        from repro.core.reformation import build_layout
        from repro.parallel.cluster_parallel import (can_shard_cluster,
                                                     sharded_cluster_attention)

        mesh = compat.make_mesh((2, 4), ("data", "model"))
        B, H, KV, Dh, bq = 2, 8, 8, 16, 64
        g = sbm_graph(500, 4, p_in=0.08, p_out=0.002, seed=0)
        lay = build_layout(g, bq=bq, bk=bq, k_clusters=4, d_b=8, n_global=1)
        S = lay.seq_len
        assert S == 512 and can_shard_cluster(H, KV, S, 4, bq, bq)

        key = jax.random.PRNGKey(0)
        q = jax.random.normal(key, (B, S, H, Dh))
        k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KV, Dh))
        v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KV, Dh))
        bidx = jnp.broadcast_to(jnp.asarray(lay.block_idx),
                                (B,) + lay.block_idx.shape)
        bkts = jnp.broadcast_to(jnp.asarray(lay.buckets),
                                (B,) + lay.buckets.shape)
        bias = jax.random.normal(jax.random.fold_in(key, 3),
                                 (H, lay.n_buckets)) * 0.2

        ref = cluster_sparse_attention(q, k, v, bidx, bkts, bias,
                                       bq=bq, bk=bq)
        fn = jax.jit(lambda *a: sharded_cluster_attention(
            *a, mesh=mesh, axis="model", bq=bq, bk=bq))
        with compat.use_mesh(mesh):
            outp = fn(q, k, v, bidx, bkts, bias)
        err = float(jnp.abs(outp - ref).max())
        assert err <= 1e-5, err

        # GQA: 8 q-heads over 4 kv-heads, head-sharded bias still aligned
        kg = k[:, :, :4]
        vg = v[:, :, :4]
        refg = cluster_sparse_attention(q, kg, vg, bidx, bkts, bias,
                                        bq=bq, bk=bq)
        with compat.use_mesh(mesh):
            outg = fn(q, kg, vg, bidx, bkts, bias)
        errg = float(jnp.abs(outg - refg).max())
        assert errg <= 1e-5, errg

        # the sharded path must actually move data with all-to-all
        txt = fn.lower(q, k, v, bidx, bkts, bias).compile().as_text()
        assert "all-to-all" in txt, "no a2a in HLO"
        print("OK", err, errg)
    """)
    assert "OK" in out
