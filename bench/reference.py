"""Plain reference of the graph transformers the benchmark trains
(Graphormer_slim, GT): forward, loss, gradients and the AdamW update in
float32 at ``highest`` matmul precision, written from the published
description and imported from nowhere in the program.

It reads a configuration file (bench/configs/<name>.json), the run's
seed and the inputs the harness builds from the benchmark's own graph.
The set of admitted (query, key) pairs is the layout's: the reformation
decides which pairs a sparse step attends over, and the reference
attends over exactly those, densely, in blocks of query rows.

Weights come from the seed by the same arithmetic as the program's
initialiser (a per-leaf key folded from a hash of the leaf's path; normal
draws scaled by 0.02 or 1/sqrt(fan-in); zeros and ones), so the two start
from the same point without the reference reading any array the program
made.

``quant`` rounds every matmul operand to a lower precision first, with a
per-tensor scale and float32 gradients (the control: float8 e4m3 for a
configuration that states bfloat16). ``fault``
plants one of the faults the comparison has to catch: ``half_batch``
(labels of the second half of the sequence left out, the mean over the
rest), ``token`` (the global token's attention output zeroed where the
sparse attention produces it), ``exchange`` (sparse attention restricted
to keys on the query's own quarter of the sequence, as if the all-to-all
between four chips were left out).
"""

from __future__ import annotations

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
B1, B2, EPS = 0.9, 0.95, 1e-8        # AdamW moments and epsilon
FAULTS = (None, "half_batch", "token", "exchange")


# ------------------------------------------------------------- weights

def param_defs(c: dict) -> dict:
    """path -> (shape, init, scale-or-fan). Leaf names follow the
    program's parameter tree so the comparison pairs leaves by path."""
    m = c["model"]
    D, H, Dh, L = m["d_model"], m["n_heads"], m["d_head"], m["n_layers"]
    KV = m.get("n_kv_heads") or H
    F, C = m["d_ff"], m["n_classes"]
    d = {
        ("feat_proj",): ((m["feat_dim"], D), "fan_in", m["feat_dim"]),
        ("global_tok",): ((max(m["n_global"], 1), D), "normal", 0.02),
        ("layers", "attn_norm", "scale"): ((L, D), "ones", 0),
        ("layers", "attn", "wq"): ((L, D, H, Dh), "fan_in", D),
        ("layers", "attn", "wk"): ((L, D, KV, Dh), "fan_in", D),
        ("layers", "attn", "wv"): ((L, D, KV, Dh), "fan_in", D),
        ("layers", "attn", "wo"): ((L, H, Dh, D), "fan_in", H),
        ("layers", "mlp_norm", "scale"): ((L, D), "ones", 0),
        ("layers", "mlp", "w_gate"): ((L, D, F), "fan_in", D),
        ("layers", "mlp", "w_up"): ((L, D, F), "fan_in", D),
        ("layers", "mlp", "w_down"): ((L, F, D), "fan_in", F),
        ("final_norm", "scale"): ((D,), "ones", 0),
        ("head",): ((D, C), "fan_in", D),
    }
    if "degree" in c["encodings"]:
        d[("z_in",)] = ((m["max_degree"], D), "normal", 0.02)
        d[("z_out",)] = ((m["max_degree"], D), "normal", 0.02)
    if "lap_pe" in c["encodings"]:
        d[("pe_proj",)] = ((c["lap_pe_dim"], D), "fan_in", c["lap_pe_dim"])
    if c["bias_buckets"]:
        d[("bias_table",)] = ((H, c["bias_buckets"]), "zeros", 0)
    return d


def _path_hash(path) -> int:
    return int.from_bytes(hashlib.blake2b(
        "/".join(path).encode(), digest_size=4).digest(), "little")


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = v
    return out


def init_params(c: dict, seed: int) -> dict:
    """All weights in one jitted call on the default device."""
    defs = param_defs(c)

    @jax.jit
    def make(key):
        flat = {}
        for path, (shape, kind, s) in defs.items():
            if kind == "zeros":
                flat[path] = jnp.zeros(shape, F32)
            elif kind == "ones":
                flat[path] = jnp.ones(shape, F32)
            else:
                k = jax.random.fold_in(key, _path_hash(path))
                scale = s if kind == "normal" else float(s) ** -0.5
                flat[path] = jax.random.normal(k, shape, F32) * scale
        return _nest(flat)

    return make(jax.random.PRNGKey(seed))


# ------------------------------------------------------------- model

def _round(a, quant):
    """``a`` rounded to ``quant`` under a per-tensor scale that puts its
    largest magnitude at the format's largest value, as low-precision
    matmuls are fed. The gradient passes straight through in float32."""
    s = float(jnp.finfo(quant).max) / jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
    q = (a * s).astype(quant).astype(F32) / s
    return a + jax.lax.stop_gradient(q - a)


def _mm(spec, a, b, quant):
    if quant is not None:
        a, b = _round(a, quant), _round(b, quant)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _row_chunk(S: int, cap: int = 512) -> int:
    for r in range(min(cap, S), 0, -1):
        if S % r == 0:
            return r
    return S


def attention(q, k, v, buckets, table, *, dense, quant, fault, parts=4):
    """q, k, v: (S, H, Dh); buckets: (S, S) int8, -1 where the layout
    admits no pair; table: (H, n_buckets) or None. Sparse: softmax over
    the admitted pairs, bias ``table[h, bucket]``; a row with no admitted
    key gives 0. Dense: every pair, bias where the layout has a bucket
    and 0 elsewhere. Computed in blocks of query rows."""
    S, H, Dh = q.shape
    R = _row_chunk(S)
    scale = Dh ** -0.5
    shard = S // parts

    @jax.checkpoint
    def rows(args):
        qi, bi, r0 = args                       # (R,H,Dh), (R,S), ()
        s = _mm("rhd,khd->hrk", qi, k, quant) * scale
        bias = jnp.zeros_like(s)
        if table is not None:
            for j in range(table.shape[1]):
                bias = jnp.where((bi == j)[None], table[:, j, None, None],
                                 bias)
        if dense:
            p = jax.nn.softmax(s + bias, axis=-1)
        else:
            ok = bi >= 0
            if fault == "exchange":
                qs = (r0 + jnp.arange(R)) // shard
                ok = ok & (qs[:, None] == jnp.arange(S)[None, :] // shard)
            s = jnp.where(ok[None], s + bias, -jnp.inf)
            mx = s.max(-1, keepdims=True)
            dead = jnp.isneginf(mx)
            p = jnp.where(dead, 0.0, jnp.exp(s - jnp.where(dead, 0.0, mx)))
            p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
        return _mm("hrk,khd->rhd", p, v, quant)

    n = S // R
    out = jax.lax.map(rows, (q.reshape(n, R, H, Dh),
                             buckets.reshape(n, R, S),
                             jnp.arange(n) * R))
    out = out.reshape(S, H, Dh)
    if fault == "token" and not dense:
        out = out.at[0].set(0.0)
    return out


def loss_fn(p, x, c, *, dense, quant=None, fault=None):
    """Masked node cross-entropy of one full-graph forward pass."""
    m = c["model"]
    eps = m["norm_eps"]
    ng = m["n_global"]
    S = x["feat"].shape[0]
    h = _mm("sf,fd->sd", x["feat"], p["feat_proj"], quant)
    if "z_in" in p:
        h = h + p["z_in"][x["in_deg"]] + p["z_out"][x["out_deg"]]
    if "pe_proj" in p:
        h = h + _mm("sk,kd->sd", x["lap_pe"], p["pe_proj"], quant)
    pos = jnp.arange(S)
    if ng:
        g = p["global_tok"][jnp.minimum(pos, ng - 1)]
        h = jnp.where((pos < ng)[:, None], g, h)
    table = p.get("bias_table")

    @jax.checkpoint
    def layer(h, lp):
        a = _rmsnorm(h, lp["attn_norm"]["scale"], eps)
        q = _mm("sd,dhk->shk", a, lp["attn"]["wq"], quant)
        k = _mm("sd,dhk->shk", a, lp["attn"]["wk"], quant)
        v = _mm("sd,dhk->shk", a, lp["attn"]["wv"], quant)
        o = attention(q, k, v, x["buckets"], table, dense=dense,
                      quant=quant, fault=fault)
        h = h + _mm("shk,hkd->sd", o, lp["attn"]["wo"], quant)
        a = _rmsnorm(h, lp["mlp_norm"]["scale"], eps)
        gate = _mm("sd,df->sf", a, lp["mlp"]["w_gate"], quant)
        up = _mm("sd,df->sf", a, lp["mlp"]["w_up"], quant)
        h = h + _mm("sf,fd->sd", jax.nn.silu(gate) * up,
                    lp["mlp"]["w_down"], quant)
        return h, None

    h, _ = jax.lax.scan(layer, h, p["layers"])
    h = _rmsnorm(h, p["final_norm"]["scale"], eps)
    logits = _mm("sd,dc->sc", h, p["head"], quant)
    labels = x["labels"]
    if fault == "half_batch":
        labels = jnp.where(pos < S // 2, labels, -1)
    mask = (labels >= 0).astype(F32)
    logz = jax.nn.logsumexp(logits, -1)
    ll = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[:, None],
                             -1)[:, 0]
    return ((logz - ll) * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def lr_at(t, opt: dict):
    """Warm-up then cosine to a 0.1 floor over ``horizon`` steps; ``t`` is
    the 1-based update count."""
    peak, warm, total = opt["lr"], opt["warmup"], opt["horizon"]
    frac = jnp.clip((t - warm) / max(total - warm, 1), 0.0, 1.0)
    cos = 0.1 * peak + 0.9 * peak * 0.5 * (1 + jnp.cos(jnp.pi * frac))
    return jnp.where(t < warm, peak * t / max(warm, 1), cos)


@functools.partial(jax.jit, static_argnames=("c_key", "opt", "dense",
                                             "quant", "fault"))
def _step(p, mom, vel, t, x, *, c_key, opt, dense, quant, fault):
    c = _CONFIGS[c_key]
    opt = dict(opt)
    loss, g = jax.value_and_grad(loss_fn)(p, x, c, dense=dense,
                                          quant=quant, fault=fault)
    lr = lr_at(t, opt)
    c1, c2 = 1.0 - B1 ** t, 1.0 - B2 ** t
    mom = jax.tree.map(lambda m_, g_: B1 * m_ + (1 - B1) * g_, mom, g)
    vel = jax.tree.map(lambda v_, g_: B2 * v_ + (1 - B2) * g_ * g_, vel, g)
    p = jax.tree.map(
        lambda p_, m_, v_: p_ - lr * ((m_ / c1) / (jnp.sqrt(v_ / c2) + EPS)
                                      + opt["weight_decay"] * p_),
        p, mom, vel)
    return loss, g, p, mom, vel


_CONFIGS: dict = {}


def train(c: dict, opt: dict, x: dict, seed: int, variants,
          *, quant=None, fault=None) -> dict:
    """Run ``len(variants)`` AdamW steps ("dense" or "sparse" each) from
    the seed's weights. Returns host float64 readings: each step's loss,
    each step's gradient tree, the initial and the final parameters."""
    key = c["arch"] + "/" + c["model"]["name"]
    _CONFIGS[key] = c
    opt = tuple((k, float(opt[k])) for k in ("lr", "warmup", "horizon",
                                             "weight_decay"))
    p = init_params(c, seed)
    p0 = to_host(p)
    mom = jax.tree.map(jnp.zeros_like, p)
    vel = jax.tree.map(jnp.zeros_like, p)
    losses, grads = [], []
    for i, var in enumerate(variants):
        loss, g, p, mom, vel = _step(p, mom, vel, jnp.float32(i + 1), x,
                                     c_key=key, opt=opt,
                                     dense=var == "dense", quant=quant,
                                     fault=fault)
        losses.append(float(loss))
        grads.append(to_host(g))
    return {"losses": losses, "grads": grads, "p0": p0, "p_end": to_host(p)}


def to_host(tree) -> dict:
    return jax.tree.map(lambda a: np.asarray(a, np.float64), tree)
