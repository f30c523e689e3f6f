"""Shared neural-net layers (pure functions over param pytrees).

Conventions:
* params are nested dicts of arrays, initialized in f32; compute casts
  to bf16 (``COMPUTE_DTYPE``) at the matmul boundary, norms/softmax in
  f32. ``cast`` of an array already in bf16 adds no op, so a serving
  copy may hold in bf16 the leaves read only through ``cast``
  (``launch.steps.serving_params``);
* initializers take an explicit PRNG key;
* all functions are shape-polymorphic over leading batch dims where
  reasonable.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

COMPUTE_DTYPE = jnp.bfloat16


def cast(x: jax.Array) -> jax.Array:
    return x.astype(COMPUTE_DTYPE)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_init(d: int) -> dict:
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm(params: dict, x: jax.Array, eps: float = 1e-5,
            bf16: bool = False) -> jax.Array:
    if bf16:
        return _rmsnorm_bf16(params["scale"], x, eps)
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * lax.rsqrt(var + eps) * params["scale"]
    return out.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rmsnorm_bf16(scale: jax.Array, x: jax.Array, eps: float) -> jax.Array:
    """RMSNorm whose forward AND backward keep every (B,S,d) tensor in
    the input dtype; f32 appears only in rowwise scalars (variance and
    the g·s·x reduction). Without this, the autodiff backward of the
    f32-variance path materializes several f32 (B,S,d) cotangents per
    norm — the dominant memory-term contributor in training (§Perf).
    """
    y, _ = _rmsnorm_bf16_fwd(scale, x, eps)
    return y


def _rmsnorm_inv(scale, x, eps):
    var = jnp.einsum("...d,...d->...", x, x,
                     preferred_element_type=jnp.float32)[..., None]
    var = var / x.shape[-1]
    return lax.rsqrt(var + eps)  # (..., 1) f32


def _rmsnorm_bf16_fwd(scale, x, eps):
    inv = _rmsnorm_inv(scale, x, eps)
    y = x * (inv.astype(x.dtype) * scale.astype(x.dtype))
    return y, (scale, x, inv)


def _rmsnorm_bf16_bwd(eps, res, g):
    scale, x, inv = res
    d = x.shape[-1]
    sb = scale.astype(x.dtype)
    # rowwise t = sum_i g_i s_i x_i  (f32 accumulation, scalar per row)
    t = jnp.einsum("...d,...d->...", g * sb, x,
                   preferred_element_type=jnp.float32)[..., None]
    coeff = (inv ** 3) * (t / d)  # (..., 1) f32
    dx = inv.astype(x.dtype) * sb * g - x * coeff.astype(x.dtype)
    # dscale reduces over all leading dims (f32 accumulation)
    gx = (g * x).astype(jnp.float32) * inv
    dscale = gx.reshape(-1, d).sum(0)
    return dscale.astype(scale.dtype), dx


_rmsnorm_bf16.defvjp(_rmsnorm_bf16_fwd, _rmsnorm_bf16_bwd)


def gated_rmsnorm(params: dict, x: jax.Array, z: jax.Array, eps: float = 1e-5) -> jax.Array:
    """Mamba-2's RMSNorm(x * silu(z))."""
    xf = x.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * lax.rsqrt(var + eps) * params["scale"]
    return out.astype(x.dtype)


def layernorm_init(d: int) -> dict:
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


def layernorm(params: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    out = (xf - mu) * lax.rsqrt(var + eps) * params["scale"] + params["bias"]
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def embedding_init(key: jax.Array, vocab: int, d: int) -> dict:
    return {"table": jax.random.normal(key, (vocab, d), jnp.float32) * 0.02}


def embed(params: dict, tokens: jax.Array) -> jax.Array:
    return cast(params["table"])[tokens]


def unembed(params: dict, x: jax.Array) -> jax.Array:
    """Logits in f32 (loss numerics)."""
    return jnp.einsum(
        "...d,vd->...v", x.astype(jnp.float32), params["table"].astype(jnp.float32)
    )


# ---------------------------------------------------------------------------
# Dense FFNs
# ---------------------------------------------------------------------------


def swiglu_init(key: jax.Array, d: int, ff: int) -> dict:
    kg, ku, kd = jax.random.split(key, 3)
    s_in, s_out = d ** -0.5, ff ** -0.5
    return {
        "gate": jax.random.normal(kg, (d, ff), jnp.float32) * s_in,
        "up": jax.random.normal(ku, (d, ff), jnp.float32) * s_in,
        "down": jax.random.normal(kd, (ff, d), jnp.float32) * s_out,
    }


def swiglu(params: dict, x: jax.Array) -> jax.Array:
    h = jax.nn.silu(x @ cast(params["gate"])) * (x @ cast(params["up"]))
    return h @ cast(params["down"])


def gelu_mlp_init(key: jax.Array, d: int, ff: int) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "fc1": jax.random.normal(k1, (d, ff), jnp.float32) * d ** -0.5,
        "b1": jnp.zeros((ff,), jnp.float32),
        "fc2": jax.random.normal(k2, (ff, d), jnp.float32) * ff ** -0.5,
        "b2": jnp.zeros((d,), jnp.float32),
    }


def gelu_mlp(params: dict, x: jax.Array) -> jax.Array:
    h = jax.nn.gelu(x @ cast(params["fc1"]) + cast(params["b1"]))
    return h @ cast(params["fc2"]) + cast(params["b2"])


# ---------------------------------------------------------------------------
# RoPE (+ M-RoPE)
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def apply_rope(
    x: jax.Array,  # (..., S, H, D)
    positions: jax.Array,  # (..., S)
    theta: float,
) -> jax.Array:
    """Standard rotary embedding over the last dim (pairs split as
    [0:D/2], [D/2:D], llama convention)."""
    D = x.shape[-1]
    freqs = rope_freqs(D, theta)  # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    cos = jnp.cos(angles)[..., None, :]  # (..., S, 1, D/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2 :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], -1)
    return out.astype(x.dtype)


def apply_mrope(
    x: jax.Array,  # (B, S, H, D)
    positions: jax.Array,  # (3, B, S) — temporal / height / width
    theta: float,
    sections: tuple[int, int, int],
) -> jax.Array:
    """Qwen2-VL multimodal RoPE: the D/2 frequency slots are split into
    three sections, each rotated by its own position stream."""
    D = x.shape[-1]
    assert sum(sections) == D // 2, (sections, D)
    freqs = rope_freqs(D, theta)  # (D/2,)
    # per-frequency-slot position source
    sec_ids = jnp.repeat(
        jnp.arange(3), jnp.asarray(sections), total_repeat_length=D // 2
    )  # (D/2,) in {0,1,2}
    pos = jnp.take_along_axis(
        positions.astype(jnp.float32),  # (3, B, S)
        sec_ids[:, None, None].repeat(positions.shape[1], 1).repeat(positions.shape[2], 2),
        axis=0,
    )  # (D/2, B, S)
    angles = jnp.moveaxis(pos, 0, -1) * freqs  # (B, S, D/2)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2 :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], -1)
    return out.astype(x.dtype)
