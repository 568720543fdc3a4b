"""Plain float32 reference of the benchmark's models and optimizer.

Written from the published equations and the configuration file alone;
it imports nothing of the system under test.  Two families:

* ``dense``: a pre-norm decoder block (RMSNorm, causal softmax attention
  with rotary positions, tanh-GELU MLP), as the system runs GPT-3 Medium.
* ``ssm``: a Mamba2 block (in-projection, causal depthwise convolution,
  SSD in its quadratic "dual" form, gated RMSNorm, out-projection).

Parameters travel in the system's layer split: layer 0 holds the
embedding, layers 1..L the blocks, layer L+1 the final norm and the head.
A tied embedding is split into two copies that train apart from then on,
which is what a pipeline whose first and last stages differ does.

A configuration names this file as its ``reference``; ``sizes`` picks
from the configuration file the sizes the equations below read.
``init_params`` makes the weights on the device, in one jitted call,
from a key; the harness hands the same tree to the system and keeps it
for the reference.  ``run_steps`` trains the reference for a few steps
on given batches and returns what the correctness check compares: each
step's loss, every leaf's norm of the first step's clipped gradient, and
every leaf's norm of the parameters' change over all the steps.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np


# ----------------------------------------------------------------------
# Sizes
# ----------------------------------------------------------------------
def sizes(cfg: Dict) -> Dict:
    """The sizes the reference reads, from a configuration file."""
    m = {k: cfg[k] for k in ("family", "num_layers", "d_model", "vocab_size",
                             "rms_norm_eps", "tie_embeddings")}
    if cfg["family"] == "ssm":
        m["ssm"] = dict(cfg["ssm"])
    else:
        m.update({k: cfg[k] for k in ("num_heads", "head_dim", "d_ff",
                                      "rope_theta")})
    return m


# ----------------------------------------------------------------------
# Weights
# ----------------------------------------------------------------------
def _normal(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


def _dense_block(m: Dict, key) -> Dict:
    d, H, hd, f = m["d_model"], m["num_heads"], m["head_dim"], m["d_ff"]
    ks = jax.random.split(key, 6)
    return {
        "ln1": jnp.ones((d,), jnp.float32),
        "attn": {"wq": _normal(ks[0], (d, H * hd), d ** -0.5),
                 "wk": _normal(ks[1], (d, H * hd), d ** -0.5),
                 "wv": _normal(ks[2], (d, H * hd), d ** -0.5),
                 "wo": _normal(ks[3], (H * hd, d), (H * hd) ** -0.5)},
        "ln2": jnp.ones((d,), jnp.float32),
        "mlp": {"up": _normal(ks[4], (d, f), d ** -0.5),
                "down": _normal(ks[5], (f, d), f ** -0.5)},
    }


def ssm_dims(m: Dict):
    s = m["ssm"]
    d_inner = s["expand"] * m["d_model"]
    heads = d_inner // s["head_dim"]
    conv_dim = d_inner + 2 * s["n_groups"] * s["state_size"]
    return s, d_inner, heads, conv_dim


def _ssm_block(m: Dict, key) -> Dict:
    s, d_inner, heads, conv_dim = ssm_dims(m)
    d = m["d_model"]
    ks = jax.random.split(key, 5)
    in_dim = 2 * d_inner + 2 * s["n_groups"] * s["state_size"] + heads
    # dt drawn log-uniform in [1e-3, 1e-1] and stored as its inverse
    # softplus; A = -exp(A_log) with A_log = log U[1, 16] (Mamba2 init)
    dt = jnp.exp(jax.random.uniform(ks[2], (heads,), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    return {
        "ln1": jnp.ones((d,), jnp.float32),
        "mamba": {
            "in_proj": _normal(ks[0], (d, in_dim), d ** -0.5),
            "conv_w": _normal(ks[1], (s["conv_width"], conv_dim), 0.2),
            "conv_b": jnp.zeros((conv_dim,), jnp.float32),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(jax.random.uniform(ks[3], (heads,), jnp.float32,
                                                1.0, 16.0)),
            "D": jnp.ones((heads,), jnp.float32),
            "norm_w": jnp.ones((d_inner,), jnp.float32),
            "out_proj": _normal(ks[4], (d_inner, d), d_inner ** -0.5),
        },
    }


def init_params(m: Dict, key) -> Dict:
    """The full tree in the system's layout: ``blocks`` stacked on a
    leading layer axis, ``head`` absent when the embedding is tied."""
    block = _ssm_block if m["family"] == "ssm" else _dense_block
    k_emb, k_blocks, k_head = jax.random.split(key, 3)
    blocks = [block(m, k)
              for k in jax.random.split(k_blocks, m["num_layers"])]
    tree = {"embed": {"table": _normal(k_emb, (m["vocab_size"],
                                               m["d_model"]), 0.02)},
            "blocks": jax.tree.map(lambda *xs: jnp.stack(xs), *blocks),
            "final_norm": jnp.ones((m["d_model"],), jnp.float32)}
    if not m["tie_embeddings"]:
        tree["head"] = {"table": _normal(k_head, (m["vocab_size"],
                                                  m["d_model"]), 0.02)}
    return tree


def split_layers(m: Dict, tree: Dict) -> List[Dict]:
    """[embed, block_0 .. block_{L-1}, tail], copying a tied embedding
    into the head."""
    layers = [{"embed": tree["embed"]}]
    for i in range(m["num_layers"]):
        layers.append(jax.tree.map(lambda t: t[i], tree["blocks"]))
    head = tree.get("head", jax.tree.map(jnp.copy, tree["embed"]))
    layers.append({"final_norm": tree["final_norm"], "head": head})
    return layers


def leaf_names(layers: Sequence[Dict]) -> List[str]:
    return [f"{l}{jax.tree_util.keystr(path)}"
            for l, lt in enumerate(layers)
            for path, _ in jax.tree_util.tree_flatten_with_path(lt)[0]]


# ----------------------------------------------------------------------
# Forward
# ----------------------------------------------------------------------
def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def rope(x, theta):
    """Rotary positions on [S, H, D], rotating the two halves of D."""
    S, _, D = x.shape
    freqs = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           -1).astype(x.dtype)


def dense_block(m: Dict, p: Dict, x):
    """x: [S, d] (one sequence)."""
    S = x.shape[0]
    H, hd, eps = m["num_heads"], m["head_dim"], m["rms_norm_eps"]
    h = rms_norm(x, p["ln1"], eps)
    q = rope((h @ p["attn"]["wq"]).reshape(S, H, hd), m["rope_theta"])
    k = rope((h @ p["attn"]["wk"]).reshape(S, H, hd), m["rope_theta"])
    v = (h @ p["attn"]["wv"]).reshape(S, H, hd)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal[None], scores.astype(jnp.float32), -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    o = jnp.einsum("hqk,khd->qhd", probs, v).reshape(S, H * hd)
    x = x + o @ p["attn"]["wo"]
    h = rms_norm(x, p["ln2"], eps)
    return x + gelu_tanh(h @ p["mlp"]["up"]) @ p["mlp"]["down"]


def ssm_block(m: Dict, p: Dict, x):
    """x: [S, d] (one sequence).  y_t = sum_{s<=t} (C_t . B_s)
    exp(sum_{s<r<=t} dt_r A) dt_s x_s + D x_t, per head."""
    s, d_inner, heads, conv_dim = ssm_dims(m)
    S = x.shape[0]
    N, P, W = s["state_size"], s["head_dim"], s["conv_width"]
    pm = p["mamba"]
    h = rms_norm(x, p["ln1"], m["rms_norm_eps"])
    proj = h @ pm["in_proj"]
    z = proj[:, :d_inner]
    xbc = proj[:, d_inner:d_inner + conv_dim]
    dt_raw = proj[:, d_inner + conv_dim:]
    padded = jnp.concatenate([jnp.zeros((W - 1, conv_dim), x.dtype), xbc])
    conv = sum(padded[k:k + S] * pm["conv_w"][k] for k in range(W))
    xbc = jax.nn.silu(conv + pm["conv_b"])
    xs = xbc[:, :d_inner].reshape(S, heads, P)
    B = xbc[:, d_inner:d_inner + N]           # one group, shared by heads
    C = xbc[:, d_inner + N:]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + pm["dt_bias"].astype(jnp.float32))   # [S, H]
    A = -jnp.exp(pm["A_log"].astype(jnp.float32))
    cum = jnp.cumsum(dt * A, axis=0)                            # [S, H]
    seg = cum.T[:, :, None] - cum.T[:, None, :]                 # [H, t, s]
    causal = jnp.tril(jnp.ones((S, S), bool))
    decay = jnp.exp(jnp.where(causal[None], seg, -jnp.inf))
    cb = (C @ B.T).astype(jnp.float32)                          # [t, s]
    w = (cb[None] * decay * dt.T[:, None, :]).astype(x.dtype)   # [H, t, s]
    y = jnp.einsum("hts,shp->thp", w, xs)
    y = y + pm["D"].astype(x.dtype)[None, :, None] * xs
    y = y.reshape(S, d_inner) * jax.nn.silu(z)
    y = rms_norm(y, pm["norm_w"], m["rms_norm_eps"])
    return x + y @ pm["out_proj"]


def sequence_nll(m: Dict, layers: Sequence[Dict], tokens, labels):
    """Mean next-token NLL of one sequence over its first S-1 positions
    (labels[t] is the target of position t; the last is not scored)."""
    block = ssm_block if m["family"] == "ssm" else dense_block
    x = layers[0]["embed"]["table"][tokens]
    for lp in layers[1:-1]:
        x = jax.checkpoint(lambda p, x: block(m, p, x))(lp, x)
    tail = layers[-1]
    x = rms_norm(x, tail["final_norm"], m["rms_norm_eps"])
    logits = (x @ tail["head"]["table"].T).astype(jnp.float32)[:-1]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:-1, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


# ----------------------------------------------------------------------
# Training
# ----------------------------------------------------------------------
def adamw(opt: Dict, p, g, m, v, t: int):
    """One AdamW step on one leaf; decay only on matrices."""
    b1, b2 = opt["beta1"], opt["beta2"]
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mhat = m / (1 - b1 ** t)
    vhat = v / (1 - b2 ** t)
    delta = mhat / (jnp.sqrt(vhat) + opt["eps"])
    if p.ndim >= 2:
        delta = delta + opt["weight_decay"] * p.astype(jnp.float32)
    new = (p.astype(jnp.float32) - opt["lr"] * delta).astype(p.dtype)
    return new, m, v


def _norms(tree) -> jax.Array:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32))))
                      for l in jax.tree.leaves(tree)])


def run_steps(m: Dict, opt: Dict, tree: Dict, batches) -> Dict:
    """Train from ``tree`` on ``batches`` (a list of (tokens, labels)
    arrays of shape [rows, S]): per step, the mean over rows of each
    row's NLL and gradient, global-norm clipping, AdamW.  Rows go one at
    a time and each block is rematerialised, so the reference fits
    beside nothing else on the device.

    Returns ``losses`` [steps], ``grad_norms`` (every leaf's norm of the
    first step's clipped gradient) and ``change_norms`` (every leaf's
    norm of the parameters' change over all steps), numpy arrays in
    ``leaf_names`` order."""
    with jax.default_matmul_precision("highest"):
        layers = split_layers(m, tree)
        start = jax.tree.map(jnp.copy, layers)
        row_grad = jax.jit(jax.value_and_grad(
            lambda ls, t, l: sequence_nll(m, ls, t, l)))
        add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))

        @jax.jit
        def update(ls, gsum, mom, vel, rows, t):
            g = jax.tree.map(lambda x: x.astype(jnp.float32) / rows, gsum)
            norm = jnp.sqrt(jnp.sum(_norms(g) ** 2))
            scale = jnp.minimum(1.0, opt["clip_norm"]
                                / jnp.maximum(norm, 1e-12))
            g = jax.tree.map(lambda x: x * scale, g)
            out = jax.tree.map(lambda p, g, a, b: adamw(opt, p, g, a, b, t),
                               ls, g, mom, vel)
            pick = lambda i: jax.tree.map(  # noqa: E731
                lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
            return pick(0), pick(1), pick(2), _norms(g)

        mom = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), layers)
        vel = jax.tree.map(jnp.copy, mom)
        losses, grad_norms = [], None
        for t, (tokens, labels) in enumerate(batches, start=1):
            total, gsum = 0.0, None
            for r in range(tokens.shape[0]):
                nll, g = row_grad(layers, jnp.asarray(tokens[r]),
                                  jnp.asarray(labels[r]))
                total += float(nll)
                gsum = g if gsum is None else add(gsum, g)
            losses.append(total / tokens.shape[0])
            layers, mom, vel, gn = update(layers, gsum, mom, vel,
                                          float(tokens.shape[0]), t)
            del gsum
            if grad_norms is None:
                grad_norms = np.asarray(gn)
        change = jax.jit(lambda a, b: _norms(jax.tree.map(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32),
            a, b)))(layers, start)
        return {"losses": np.asarray(losses), "grad_norms": grad_norms,
                "change_norms": np.asarray(change)}
