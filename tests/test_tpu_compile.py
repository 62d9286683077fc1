"""The main path compiles for a TPU v5e chip that is described, not
attached: the cluster-sparse kernels (forward with residuals and
``jax.grad`` through the recomputation backward; biased with ``fuse_bias``
off and on, and unbiased; batch-shared 2-D and per-graph 3-D layouts) and
the whole jitted train steps of ``graphormer_slim`` (sparse and dense) and
``gt`` (sparse), at published widths and ``chip_smoke.py``'s graph sizes;
and ``graphormer_slim``'s sharded sparse step and eval over the described
host's four chips.

The TPU compiler refuses here what interpret mode accepts: illegal block
shapes, gathers Mosaic cannot lower, scalar-prefetch streams that overflow
SMEM, programs larger than the chip's HBM. Nothing runs, so nothing here
is a time or a result.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""

import argparse
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro import compat
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.kernels import ops as kops
# compiling the kernel bodies themselves, below the dispatch layer
from repro.kernels.cluster_attention import cluster_attention  # repro-lint: disable=REP002
from repro.kernels.cluster_attention_bwd import cluster_attention_vjp  # repro-lint: disable=REP002
from repro.models import build
from repro.parallel.sharding import recipe_for
from repro.runtime.trainer import Trainer, TrainerConfig

ROOT = Path(__file__).resolve().parents[1]
V5E_HBM_BYTES = 16 * 1024 ** 3


def _smoke():
    """chip_smoke.py's constants (its graph sizes are what these compiles
    guard)."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache off
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def dispatch_to_kernel(monkeypatch):
    """Code that asks ``jax.default_backend()`` sees the chip, so ``auto``
    dispatch resolves to the compiled kernel; winner tables stay out."""
    for var in [kops._ENV_GLOBAL, *kops._ENV_PER_OP.values()]:
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("REPRO_TUNE", "0")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    kops.set_mode("auto")
    yield
    kops.set_mode("auto")


def _task(arch: str, nodes: int):
    """The node task exactly as ``launch/train.py`` builds it."""
    from repro.launch.train import _make_graph_task

    cfg = get_config(arch)
    args = argparse.Namespace(task="node", graph_nodes=nodes,
                              graph_clusters=4)
    return cfg, _make_graph_task(args, cfg)


@pytest.fixture(scope="module")
def slim():
    return _task("graphormer_slim", _smoke().SLIM_NODES)


@pytest.fixture(scope="module")
def gt():
    return _task("gt", _smoke().GT_NODES)


def _fits(compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes)
    assert total <= V5E_HBM_BYTES, m
    return compiled.as_text()


@pytest.mark.parametrize("op", ["fwd", "grad"])
@pytest.mark.parametrize("layout", ["shared", "per_graph"])
@pytest.mark.parametrize("variant", ["unbiased", "biased", "biased_fused"])
def test_cluster_kernel_compiles(one_chip, slim, op, layout, variant):
    cfg, task = slim
    b = task.prep.batch
    S, H = b["feat"].shape[1], cfg.n_heads
    Dh = cfg.head_dim + (-cfg.head_dim % kops.LANE)   # dispatch lane-pads

    def sds(shape, dtype):
        if layout == "shared" and len(shape) > 1 and shape[0] == 1:
            shape = shape[1:]                # drop the batch dim
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q = jax.ShapeDtypeStruct((1, S, H, Dh), jnp.bfloat16, sharding=one_chip)
    bi = sds(b["block_idx"].shape, jnp.int32)
    bit = sds(b["block_idx_t"].shape, jnp.int32)
    bu = bt = None
    if variant != "unbiased":
        bu = sds(b["buckets"].shape, jnp.int8)
        bt = jax.ShapeDtypeStruct((H, task.layout.n_buckets), jnp.float32,
                                  sharding=one_chip)
    fuse = variant == "biased_fused"

    if op == "fwd":
        def f(q, k, v, bi, bu, bt, bit):
            return cluster_attention(q, k, v, bi, bu, bt,
                                     return_residuals=True, fuse_bias=fuse)
    else:
        def f(q, k, v, bi, bu, bt, bit):
            def loss(q, k, v, bt):
                o = cluster_attention_vjp(q, k, v, bi, bu, bt, bit,
                                          fuse_bias=fuse)
                return o.astype(jnp.float32).sum()
            return jax.grad(loss, argnums=(0, 1, 2, 3) if bt is not None
                            else (0, 1, 2))(q, k, v, bt)

    compiled = jax.jit(f).lower(q, q, q, bi, bu, bt, bit).compile()
    assert "tpu_custom_call" in _fits(compiled)


@pytest.mark.parametrize("op", ["fwd", "grad"])
def test_compacted_kernels_compile_at_cell_size(one_chip, op):
    """The benchmark cells' shape: S = 6,944 in blocks of 32 with one
    global row that lists all 217 k-blocks (``mb = mt = 220``), the
    others nearly empty. Each call's scalar prefetch (its stream and
    ``block_idx``, a word a slot each: 381,920 bytes) and the stream
    builder's SMEM must fit, and the live count must pass Mosaic as a
    dynamic grid bound, forward and through ``jax.grad``."""
    from repro.core.reformation import transpose_block_idx

    S, H, bq, nq = 6944, 8, 32, 217
    bi = np.full((nq, 220), -1, np.int32)
    bi[0, :nq] = np.arange(nq)
    bi[1:, 0], bi[1:, 1] = 0, np.arange(1, nq)
    bit = transpose_block_idx(bi, nq)
    assert bit.shape == (nq, 220, 2)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q = sds((1, S, H, kops.LANE), jnp.bfloat16)
    args = (q, q, q, sds((1,) + bi.shape, jnp.int32),
            sds((1,) + bi.shape + (bq, bq), jnp.int8), sds((H, 3), jnp.float32),
            sds((1,) + bit.shape, jnp.int32))

    if op == "fwd":
        def f(q, k, v, bi, bu, bt, bit):
            return cluster_attention(q, k, v, bi, bu, bt,
                                     return_residuals=True)
    else:
        def f(q, k, v, bi, bu, bt, bit):
            def loss(q, k, v, bt):
                o = cluster_attention_vjp(q, k, v, bi, bu, bt, bit)
                return o.astype(jnp.float32).sum()
            return jax.grad(loss, argnums=(0, 1, 2, 3))(q, k, v, bt)

    compiled = jax.jit(f).lower(*args).compile()
    assert "tpu_custom_call" in _fits(compiled)


@pytest.mark.parametrize("arch,variant", [
    ("graphormer_slim", "sparse"),
    ("graphormer_slim", "dense"),
    ("gt", "sparse"),
])
def test_train_step_compiles(one_chip, dispatch_to_kernel, slim, gt,
                             tmp_path, arch, variant):
    """The Trainer's jitted step — forward, backward and optimizer — as
    ``chip_smoke.py`` runs it, fitting one chip's HBM."""
    cfg, task = slim if arch == "graphormer_slim" else gt
    trainer = Trainer(build(cfg), TrainerConfig(steps=1,
                                                ckpt_dir=str(tmp_path)),
                      task=task)

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    state = jax.tree.map(sds, jax.eval_shape(trainer.init_state))
    batch = {k: sds(v) for k, v in task.prep.batch.items()}
    fault = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    compiled = trainer._steps[variant].lower(state, batch, fault).compile()
    hlo = _fits(compiled)
    assert ("tpu_custom_call" in hlo) == (variant == "sparse")


@pytest.mark.parametrize("program", ["sparse_step", "eval"])
def test_sharded_program_compiles(topo, dispatch_to_kernel, slim, tmp_path,
                                  program):
    """``--mesh-model 4`` over the described host's four chips, as
    ``chip_smoke.py --four-chips`` runs it: the sparse step and the
    post-training eval both reach the cluster kernel inside the Ulysses
    shard_map. A kernel call left to GSPMD is refused (Mosaic calls
    cannot be partitioned automatically)."""
    cfg, task = slim
    mesh = compat.make_mesh((1, 4), ("data", "model"), devices=topo.devices)
    recipe = recipe_for(ShapeConfig("graph", "train", task.layout.seq_len, 1),
                        mesh)
    trainer = Trainer(build(cfg), TrainerConfig(steps=1,
                                                ckpt_dir=str(tmp_path)),
                      task=task, mesh=mesh, recipe=recipe)
    replicated = NamedSharding(mesh, PartitionSpec())

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=replicated)

    state = jax.tree.map(sds, jax.eval_shape(trainer.init_state))
    batch = {k: sds(v) for k, v in task.prep.batch.items()}
    with trainer.trace_ctx():   # what Trainer.evaluate enters
        if program == "sparse_step":
            fault = jax.ShapeDtypeStruct((), jnp.float32, sharding=replicated)
            lowered = trainer._steps["sparse"].lower(state, batch, fault)
        else:
            lowered = task._metrics_fn().lower(state["params"], batch)
        hlo = _fits(lowered.compile())
    assert "all-to-all" in hlo and "tpu_custom_call" in hlo
