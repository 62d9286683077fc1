"""Faults planted in the program under a benchmark run, for the tests
that see ``correct`` come out false. Each replaces one function of the
timed path for the duration of a test (pytest's monkeypatch)."""

import jax
import jax.numpy as jnp


def plant(monkeypatch, fault: str | None, parts: int = 4):
    import repro.core.graph_model as gm
    import repro.kernels.ops as kops
    import repro.parallel.cluster_parallel as cp
    from repro.optim.adamw import AdamW

    if fault is None:
        return
    if fault == "stale":
        # a step that returns its state unchanged
        monkeypatch.setattr(AdamW, "update",
                            lambda self, grads, state, params:
                            (params, state))
    elif fault == "half_batch":
        # half of the batch left out, the mean taken over the rest
        orig = gm.graph_loss

        def half(p, cfg, batch, dense=False):
            b = dict(batch)
            lab = b["labels"]
            S = lab.shape[-1]
            b["labels"] = jnp.where(jnp.arange(S) < S // 2, lab, -1)
            return orig(p, cfg, b, dense)

        monkeypatch.setattr(gm, "graph_loss", half)
    elif fault == "token":
        # one token's answer altered where the kernel produces it
        orig = kops.cluster_attention

        def token(*a, **k):
            return orig(*a, **k).at[:, 0].set(0.0)

        monkeypatch.setattr(kops, "cluster_attention", token)
    elif fault == "exchange":
        # the all-to-all between chips left out: each device keeps its
        # own sequence shard and its own head chunk
        def s2h(ql, kl, vl, *, axis, r=1):
            i = jax.lax.axis_index(axis)

            def f(x):
                h = x.shape[2] // parts
                x = jnp.tile(x, (1, parts, 1, 1))
                return jax.lax.dynamic_slice_in_dim(x, i * h, h, axis=2)
            return f(ql), f(kl), f(vl)

        def h2s(ol, *, axis):
            i = jax.lax.axis_index(axis)
            s = ol.shape[1] // parts
            x = jax.lax.dynamic_slice_in_dim(ol, i * s, s, axis=1)
            return jnp.tile(x, (1, 1, parts, 1))

        monkeypatch.setattr(cp, "seq_to_head_a2a", s2h)
        monkeypatch.setattr(cp, "head_to_seq_a2a", h2s)
    else:
        raise ValueError(fault)
