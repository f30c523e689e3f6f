"""Plain float32 reference of a dense decoder with grouped-query attention.

Llama-style block as published for Yi-6B (arXiv:2403.04652) and
H2O-Danube-1.8B (arXiv:2401.16818): RMSNorm, rotary embedding on the
two halves of each head, causal grouped-query attention with an
optional sliding window, SwiGLU feed-forward, untied output head.
Plain ``jax.numpy`` in float32 under ``highest`` matmul precision, with
no cache, no batching of requests and no kernels. Attention runs in
blocks of queries so that long sequences fit.

Weights live in this module's own layout (``init_weights``), made on
the device in one jitted call from the seed, and ``to_program_params``
maps them onto the program's tree. ``quant="fp8"`` computes every matrix
product from float8 (e4m3) operands with a scale per row or column: the
lower-precision control that the comparison has to reject. The counts
are ``bench/lib/flops.py``'s dense formulas. The contract this module
keeps is in ``bench/reference/__init__.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from bench.lib.flops import (  # noqa: F401  (the contract's counts)
    decode_step_bytes,
    decode_step_flops,
    forward_flops,
    param_count,
    train_flops_per_token,
)

NEG_INF = -1e30
Q_BLOCK = 512
FP8_MAX = 448.0


def dims(model: dict) -> dict:
    d = model["d_model"]
    dh = model.get("head_dim") or d // model["num_heads"]
    return {
        "L": model["num_layers"], "d": d, "H": model["num_heads"],
        "Hkv": model["num_kv_heads"], "Dh": dh, "F": model["d_ff"],
        "V": model["vocab_size"],
    }


def shapes(model: dict) -> dict:
    """The weight shapes of this module's layout."""
    k = dims(model)
    L, d, H, Hkv, Dh, F, V = (k[x] for x in ("L", "d", "H", "Hkv", "Dh", "F", "V"))
    return {
        "embed": (V, d), "head": (V, d), "final_norm": (d,),
        "layers": {
            "norm1": (L, d), "wq": (L, d, H * Dh), "wk": (L, d, Hkv * Dh),
            "wv": (L, d, Hkv * Dh), "wo": (L, H * Dh, d), "norm2": (L, d),
            "gate": (L, d, F), "up": (L, d, F), "down": (L, F, d),
        },
    }


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any integer seed (wider than 32 bits too)."""
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, jnp.uint32(int(seed) & 0xFFFFFFFF))
    return jax.random.fold_in(k, jnp.uint32((int(seed) >> 32) & 0xFFFFFFFF))


def init(key: jax.Array, model: dict) -> dict:
    """Embedding and head N(0, 0.02); projections N(0, fan_in^-1/2);
    norm scales 1."""
    shp = shapes(model)
    flat, tree = jax.tree.flatten(shp, is_leaf=lambda s: isinstance(s, tuple))
    paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(
        shp, is_leaf=lambda s: isinstance(s, tuple))[0]]
    out = []
    for i, (path, s) in enumerate(zip(paths, flat)):
        if "norm" in path:
            out.append(jnp.ones(s, jnp.float32))
            continue
        std = 0.02 if ("embed" in path or "head" in path) else s[-2] ** -0.5
        out.append(jax.random.normal(jax.random.fold_in(key, i), s, jnp.float32) * std)
    return jax.tree.unflatten(tree, out)


def init_weights(model: dict, seed: int) -> dict:
    """All weights in float32, on the device, from one jitted call."""
    return _init_jit(seed_key(seed), _freeze(model))


def _freeze(model: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in model.items() if not isinstance(v, (list, dict))))


@functools.partial(jax.jit, static_argnums=1)
def _init_jit(key, frozen):
    return init(key, dict(frozen))


def to_program_params(w: dict, cfg) -> dict:
    """A tree in this module's layout in the program's parameter tree
    (one scanned group of identical dense layers)."""
    groups = cfg.layer_groups()
    if len(groups) != 1 or len(groups[0][0]) != 1:
        raise ValueError(f"{cfg.name}: expected one group of identical layers")
    ly = w["layers"]
    layer = {
        "norm1": {"scale": ly["norm1"]},
        "mixer": {k: ly[k] for k in ("wq", "wk", "wv", "wo")},
        "norm2": {"scale": ly["norm2"]},
        "ffn": {k: ly[k] for k in ("gate", "up", "down")},
    }
    return {
        "embed": {"table": w["embed"]},
        "final_norm": {"scale": w["final_norm"]},
        "groups": [[layer]],
        "lm_head": {"table": w["head"]},
    }


# -- arithmetic ----------------------------------------------------------------


def _fp8(x: jax.Array, axis: int) -> jax.Array:
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(a: jax.Array, b: jax.Array, quant: str | None) -> jax.Array:
    """``a @ b`` over the last axis of ``a`` and the first of ``b``."""
    if quant == "fp8":
        a, b = _fp8(a, -1), _fp8(b, 0)
    elif quant is not None:
        raise ValueError(f"unknown quant {quant!r}")
    return jnp.tensordot(a, b, axes=1, precision=lax.Precision.HIGHEST)


def rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, positions, theta):
    """Rotary embedding of ``x`` (S, heads, Dh): the first and second
    halves of each head are the two coordinates of each pair."""
    dh = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = positions[:, None].astype(jnp.float32) * freqs  # (S, Dh/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window, quant):
    """Causal GQA over (S, H, Dh) queries and (S, Hkv, Dh) keys/values,
    ``Q_BLOCK`` queries at a time."""
    S, H, Dh = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    kt = k.transpose(1, 2, 0)  # (Hkv, Dh, S)
    vt = v.transpose(1, 0, 2)  # (Hkv, S, Dh)
    cols = jnp.arange(S)
    outs = []
    for b0 in range(0, S, Q_BLOCK):
        qb = q[b0 : b0 + Q_BLOCK]
        n = qb.shape[0]
        qg = qb.reshape(n, Hkv, G, Dh).transpose(1, 2, 0, 3)  # (Hkv, G, n, Dh)
        s = jax.vmap(lambda a, b: mm(a, b, quant))(qg.reshape(Hkv, G * n, Dh), kt)
        s = s.reshape(Hkv, G, n, S) * Dh ** -0.5
        rows = (b0 + jnp.arange(n))[:, None]
        mask = cols[None, :] <= rows
        if window:
            mask &= cols[None, :] > rows - window
        s = jnp.where(mask, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jax.vmap(lambda a, b: mm(a, b, quant))(p.reshape(Hkv, G * n, S), vt)
        outs.append(o.reshape(Hkv, G, n, Dh).transpose(2, 0, 1, 3).reshape(n, H * Dh))
    return jnp.concatenate(outs, 0)


def hidden(w: dict, model: dict, tokens: jax.Array, quant: str | None = None) -> jax.Array:
    """Final-norm hidden states (S, d) of one sequence."""
    k = dims(model)
    eps = model.get("norm_eps", 1e-5)
    theta = model.get("rope_theta", 1e4)
    window = model.get("sliding_window")
    S = tokens.shape[0]
    pos = jnp.arange(S)
    x = w["embed"][tokens]

    def layer(x, p):
        h = rmsnorm(x, p["norm1"], eps)
        q = rope(mm(h, p["wq"], quant).reshape(S, k["H"], k["Dh"]), pos, theta)
        kk = rope(mm(h, p["wk"], quant).reshape(S, k["Hkv"], k["Dh"]), pos, theta)
        v = mm(h, p["wv"], quant).reshape(S, k["Hkv"], k["Dh"])
        x = x + mm(attention(q, kk, v, window, quant), p["wo"], quant)
        h = rmsnorm(x, p["norm2"], eps)
        f = jax.nn.silu(mm(h, p["gate"], quant)) * mm(h, p["up"], quant)
        return x + mm(f, p["down"], quant), None

    x, _ = lax.scan(jax.checkpoint(layer), x, w["layers"])
    return rmsnorm(x, w["final_norm"], eps)


@functools.partial(jax.jit, static_argnums=(1, 4, 5))
def _window_logits(w, frozen, tokens, start, n, quant):
    model = dict(frozen)
    h = hidden(w, model, tokens, quant)
    hs = lax.dynamic_slice_in_dim(h, start, n, 0)
    return mm(hs, w["head"].T, quant)


def logits_at(w: dict, model: dict, tokens, start: int, n: int, quant: str | None = None):
    """Logits (n, V) at positions ``[start, start + n)`` of ``tokens``.
    Pad ``tokens`` to one length per cell so that this compiles once;
    padding after the read positions does not change them (causal)."""
    return _window_logits(w, _freeze(model), tokens, jnp.int32(start), int(n), quant)


# -- training objective ---------------------------------------------------------


def loss(w: dict, model: dict, tokens, labels, z_loss: float, quant: str | None = None):
    """Mean next-token cross-entropy plus ``z_loss`` times the mean
    squared log-partition, over a (B, S) batch."""

    def one(t, y):
        lg = mm(hidden(w, model, t, quant), w["head"].T, quant)
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, y[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - gold), jnp.sum(lse * lse)

    ce, zz = jax.lax.map(lambda ty: one(*ty), (tokens, labels))
    n = tokens.size
    return (jnp.sum(ce) + z_loss * jnp.sum(zz)) / n


# -- plain AdamW ------------------------------------------------------------------


def adamw_lr(opt: dict, step):
    """Linear warm-up to ``peak_lr`` over ``warmup_steps``, then cosine
    decay to ``min_lr_ratio`` of it at ``decay_steps``."""
    step = jnp.asarray(step, jnp.float32)
    warm = opt["peak_lr"] * step / max(1, opt["warmup_steps"])
    t = jnp.clip((step - opt["warmup_steps"]) / max(1, opt["decay_steps"] - opt["warmup_steps"]), 0.0, 1.0)
    cos = opt["peak_lr"] * (opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"]) * 0.5 * (1 + jnp.cos(jnp.pi * t)))
    return jnp.where(step < opt["warmup_steps"], warm, cos)


def train_step(w, mu, nu, tokens, labels, step, *, model: dict, opt: dict,
               quant: str | None = None):
    """One AdamW step (global-norm clipping, decoupled weight decay) on
    the mean loss of the batch; ``step`` counts from 1. Returns the new
    (w, mu, nu), the loss and the per-leaf norms of the clipped gradient
    the optimizer used."""
    lval, g = jax.value_and_grad(lambda p: loss(p, model, tokens, labels, opt["z_loss"], quant))(w)
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    g = jax.tree.map(lambda x: x * jnp.minimum(1.0, opt["grad_clip"] / (gnorm + 1e-9)), g)
    b1, b2 = opt["b1"], opt["b2"]
    mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
    nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
    lr = adamw_lr(opt, step)
    t = jnp.asarray(step, jnp.float32)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    w = jax.tree.map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + opt["eps"]) + opt["weight_decay"] * p),
        w, mu, nu)
    return w, mu, nu, lval, jax.tree.map(lambda x: jnp.sqrt(jnp.sum(x * x)), g)
