"""Graph transformer models (Graphormer_slim/large, GT) on the TorchGT
stack: degree/SPD encodings + dual-interleaved attention over the
cluster-sparse layout + Ulysses graph parallelism.

Batch layout (built by data/graph_pipeline.py):
  feat       (B, S, F)      node features, zeros at global/pad positions
  in_deg     (B, S) int32   clipped degrees (0 at global/pad)
  out_deg    (B, S) int32
  lap_pe     (B, S, Kpe)    (GT only)
  block_idx  (B, nq, mb)    cluster-sparse layout
  buckets    (B, nq, mb, bq, bk) int8  (optional; bias/mask)
  labels     (B, S) int32   -1 = masked (global tokens, padding, test nodes)
  dense_bias (1|B, H, S, S) (optional; only for the dense interleave step
                             on small graphs)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.nn import param as nnp
from repro.parallel import axes as pax
from repro.parallel.cluster_parallel import (can_shard_cluster,
                                             sharded_cluster_attention)

F32 = jnp.float32
PE_DIM = 8


def _n_buckets(cfg) -> int:
    # SPD: hop counts 0..max_spd + the global-token virtual-distance bucket
    return (cfg.max_spd + 2) if cfg.graph_bias == "spd" else 3


def graph_defs(cfg):
    D = cfg.d_model
    layer = {
        "attn_norm": L.rmsnorm_defs(D),
        "attn": L.attention_defs(cfg),
        "mlp_norm": L.rmsnorm_defs(D),
        "mlp": L.mlp_defs(cfg),
    }
    defs = {
        "feat_proj": nnp.fan_in((cfg.feat_dim, D), (None, "embed")),
        "global_tok": nnp.normal((max(cfg.n_global, 1), D), (None, "embed")),
        "layers": nnp.stack(layer, cfg.n_layers),
        "final_norm": L.rmsnorm_defs(D),
        "head": nnp.fan_in((D, cfg.n_classes), ("embed", "classes")),
    }
    if cfg.family == "graph" and cfg.name.startswith("graphormer"):
        defs["z_in"] = nnp.embed((cfg.max_degree, D), ("degree", "embed"))
        defs["z_out"] = nnp.embed((cfg.max_degree, D), ("degree", "embed"))
    if cfg.graph_bias:
        defs["bias_table"] = nnp.zeros((cfg.n_heads, _n_buckets(cfg)),
                                       ("bias_heads", None))
    if cfg.name.startswith("gt"):
        defs["pe_proj"] = nnp.fan_in((PE_DIM, D), (None, "embed"))
    return defs


def _graph_attn(p, cfg, h, batch, dense: bool, bias_table, streams=None):
    """Sparse steps go through the kernel dispatch layer (kernels/ops.py):
    oracle on CPU, Pallas cluster kernel on TPU / under REPRO_FORCE_PALLAS.
    Under a model-axis mesh the sparse path composes with the Ulysses a2a
    via sharded_cluster_attention, which also head-shards bias_table."""
    from repro.kernels import ops as kops  # lazy: kops imports model layers

    q, k, v = L.project_qkv(p, cfg, h, jnp.arange(h.shape[1]))
    if dense:
        bias = batch.get("dense_bias")
        attn_fn = lambda a, b, c: L.chunked_attention(
            a, b, c, causal=False, bias=bias)
    else:
        bi = batch["block_idx"]
        bu = batch.get("buckets")
        bit = batch.get("block_idx_t")  # transposed layout (dK/dV bwd)
        bq_ = h.shape[1] // bi.shape[1]
        bk_ = bu.shape[-1] if bu is not None else bq_
        attn_fn = lambda a, b, c: kops.cluster_attention(
            a, b, c, bi, bu, bias_table, bit, causal=False, streams=streams)

    ctx = pax.current()
    if ctx is not None:
        recipe, mesh = ctx
        pm = mesh.shape.get("model", 1)
        if recipe.ulysses and not dense and pm > 1 and can_shard_cluster(
                cfg.n_heads, cfg.kv_heads, h.shape[1], pm, bq_, bk_):
            o = sharded_cluster_attention(
                q, k, v, bi, bu, bias_table, bit, mesh=mesh, bq=bq_,
                bk=bk_, dp_axes=("data", "pod"))
            return L.out_proj(p, o)
        # non-shardable sparse shapes fall through to the plain dispatch
        # call below (GSPMD decides the layout). Deliberately NOT a
        # ulysses_attention with a closed-over pattern: the closure would
        # replicate bias_table, and cluster_sparse_attention on H/pm local
        # heads would silently read head-0's rows of the full table.
    return L.out_proj(p, attn_fn(q, k, v))




def graph_forward(p, cfg, batch, dense: bool):
    dtype = jnp.dtype(cfg.dtype)
    feat = batch["feat"].astype(dtype)
    h = jnp.einsum("bsf,fd->bsd", feat, p["feat_proj"].astype(dtype))
    if "z_in" in p:
        h = h + jnp.take(p["z_in"], batch["in_deg"], axis=0).astype(dtype)
        h = h + jnp.take(p["z_out"], batch["out_deg"], axis=0).astype(dtype)
    if "pe_proj" in p:
        h = h + jnp.einsum("bsk,kd->bsd", batch["lap_pe"].astype(dtype),
                           p["pe_proj"].astype(dtype))
    if cfg.n_global:
        # overwrite the leading n_global positions with the global tokens.
        # Deliberately NOT a concatenate: concat along the (model-)sharded
        # sequence dim with unaligned piece boundaries miscompiles under
        # XLA SPMD on JAX 0.4.x (wrong values, no error); the masked
        # gather+where form partitions trivially and is numerically
        # identical.
        g = p["global_tok"].astype(dtype)
        pos = jnp.arange(h.shape[1])
        gseq = jnp.take(g, jnp.minimum(pos, g.shape[0] - 1), axis=0)[None]
        h = jnp.where((pos < cfg.n_global)[None, :, None], gseq, h)
    h = pax.logical(h, "batch", "seq_outer", "embed")
    bias_table = p.get("bias_table")
    streams = None
    if not dense and pax.current() is None:
        # the kernels' compacted streams, once for every layer (outside
        # the rematerialized body, so the backward does not rebuild
        # them); the sharded path builds its own per device
        from repro.kernels import ops as kops
        bi, bu = batch["block_idx"], batch.get("buckets")
        streams = kops.cluster_streams(
            bi, batch.get("block_idx_t"), seq_len=h.shape[1],
            bk=bu.shape[-1] if bu is not None else h.shape[1] // bi.shape[-2])

    def body(h, pp):
        a = L.rmsnorm(pp["attn_norm"], h, cfg.norm_eps)
        h = h + _graph_attn(pp["attn"], cfg, a, batch, dense, bias_table,
                            streams)
        m = L.rmsnorm(pp["mlp_norm"], h, cfg.norm_eps)
        h = h + L.mlp(pp["mlp"], m)
        return pax.logical(h, "batch", "seq_outer", "embed"), None

    h, _ = jax.lax.scan(jax.checkpoint(body) if cfg.remat != "none" else body,
                        h, p["layers"])
    return L.rmsnorm(p["final_norm"], h, cfg.norm_eps)


def apply_head(p, h):
    """The one classification-head projection every task head rides:
    (B, S, D) hidden states -> (B, S, n_classes) logits."""
    return jnp.einsum("bsd,dc->bsc", h, p["head"].astype(h.dtype))


def graph_loss(p, cfg, batch, dense: bool = False):
    """Node-level masked cross-entropy (labels -1 ignored); graph-level
    tasks put the label on the global-token position."""
    h = graph_forward(p, cfg, batch, dense)
    logits = apply_head(p, h).astype(F32)
    labels = batch["labels"]
    mask = (labels >= 0).astype(F32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None],
                             axis=-1)[..., 0]
    loss = ((logz - ll) * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    acc = ((logits.argmax(-1) == labels) * mask).sum() \
        / jnp.maximum(mask.sum(), 1.0)
    return loss, {"xent": loss, "acc": acc}


def with_dense_bias(p, cfg, batch):
    """Batch copy with ``dense_bias`` materialized from the scattered
    ``dense_buckets`` array (when present). The bias is built inside the
    trace from an *array input* — data, not a static constant — so
    elastic re-layout never retraces the dense step."""
    from repro.core.dual_attention import dense_bias_from_buckets

    b = dict(batch)
    if "dense_bias" not in b and b.get("dense_buckets") is not None \
            and p.get("bias_table") is not None:
        b["dense_bias"] = dense_bias_from_buckets(
            b["dense_buckets"], p["bias_table"], cfg.n_heads)
    return b


def graph_loss_dense(p, cfg, batch):
    """Dense interleave step (§III-B): fully-connected attention, biased
    where the sparse pattern defines structure."""
    return graph_loss(p, cfg, with_dense_bias(p, cfg, batch), dense=True)


def graph_predict(p, cfg, batch, dense: bool = False):
    return apply_head(p, graph_forward(p, cfg, batch, dense))


def build_graph_model(cfg):
    from repro.models.api import Model

    return Model(
        cfg=cfg,
        param_defs=graph_defs(cfg),
        loss_variants={
            "sparse": lambda p, b: graph_loss(p, cfg, b, dense=False),
            # the dense-interleave variant (§III-B); tasks schedule it
            "dense": lambda p, b: graph_loss_dense(p, cfg, b),
        },
        prefill=lambda p, b: (graph_predict(p, cfg, b), {}),
        decode=None,  # graph transformers have no autoregressive decode
        cache_defs=None,
    )
