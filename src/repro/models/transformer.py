"""Model assembly: layer blocks, scan-compiled layer groups, forward,
chunked loss, prefill and decode.

Layer stacks are compiled into (pattern, repeat) groups
(``ModelConfig.layer_groups``): each group's params are stacked along a
leading ``repeat`` dim and the group runs as one ``lax.scan`` whose body
applies the (possibly heterogeneous) pattern once — so HLO size and
compile time are O(pattern), not O(num_layers), and activation remat is
applied per scan body. KV caches mirror the same (group, position,
stacked) structure.

Losses never materialize (B, S, V) logits: the cross-entropy is computed
in sequence chunks with vocab kept TP-sharded (`hints.maybe_shard`).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax

from . import attention as attn
from . import mamba2 as mb
from . import moe as moe_mod
from .config import LayerSpec, ModelConfig
from .layers import (
    cast,
    embed,
    embedding_init,
    gelu_mlp,
    gelu_mlp_init,
    rmsnorm,
    rmsnorm_init,
    swiglu,
    swiglu_init,
)
from repro.parallel.hints import BATCH, TP, maybe_shard

Params = dict
PyTree = Any

REMAT_POLICIES: dict[str, Any] = {
    "none": None,
    "dots": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
    "full": jax.checkpoint_policies.nothing_saveable,
}


# ---------------------------------------------------------------------------
# Single layer
# ---------------------------------------------------------------------------


def layer_init(key: jax.Array, spec: LayerSpec, cfg: ModelConfig) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p: Params = {"norm1": rmsnorm_init(cfg.d_model)}
    if spec.mixer == "gqa":
        p["mixer"] = attn.gqa_init(k1, cfg)
    elif spec.mixer == "mla":
        p["mixer"] = attn.mla_init(k1, cfg)
    else:  # mamba
        p["mixer"] = mb.mamba2_init(k1, cfg)
    if spec.cross_attention:
        p["norm_ca"] = rmsnorm_init(cfg.d_model)
        p["cross"] = attn.cross_attn_init(k3, cfg)
    if spec.ffn != "none":
        p["norm2"] = rmsnorm_init(cfg.d_model)
        if spec.ffn == "dense":
            p["ffn"] = (
                swiglu_init(k2, cfg.d_model, cfg.d_ff)
                if cfg.ffn_activation == "swiglu"
                else gelu_mlp_init(k2, cfg.d_model, cfg.d_ff)
            )
        else:
            p["ffn"] = moe_mod.moe_init(k2, cfg)
    return p


def layer_apply(
    params: Params,
    spec: LayerSpec,
    cfg: ModelConfig,
    x: jax.Array,
    positions: jax.Array,
    *,
    enc: jax.Array | None = None,
    causal: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Full-sequence layer. Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    with jax.named_scope("attn"):
        h = rmsnorm(params["norm1"], x, cfg.norm_eps, bf16=cfg.bf16_norm)
        if spec.mixer == "gqa":
            h = attn.gqa_apply(params["mixer"], h, positions, cfg, causal=causal)
        elif spec.mixer == "mla":
            h = attn.mla_apply(params["mixer"], h, positions, cfg, causal=causal)
        else:
            h = mb.mamba2_apply(params["mixer"], h, cfg)
        x = x + h
        x = maybe_shard(x, BATCH, None, None)
        if spec.cross_attention:
            assert enc is not None
            h = rmsnorm(params["norm_ca"], x, cfg.norm_eps, bf16=cfg.bf16_norm)
            x = x + attn.cross_attn_apply(params["cross"], h, enc, cfg)
    if spec.ffn != "none":
        with jax.named_scope("mlp"):
            h = rmsnorm(params["norm2"], x, cfg.norm_eps, bf16=cfg.bf16_norm)
            if spec.ffn == "dense":
                h = (
                    swiglu(params["ffn"], h)
                    if cfg.ffn_activation == "swiglu"
                    else gelu_mlp(params["ffn"], h)
                )
            else:
                h, aux = moe_mod.moe_apply(params["ffn"], h, cfg)
            x = x + h
            x = maybe_shard(x, BATCH, None, None)
    return x, aux


def layer_init_cache(
    spec: LayerSpec, cfg: ModelConfig, batch: int, max_seq: int
) -> Params:
    if spec.mixer in ("gqa",):
        return attn.gqa_init_cache(cfg, batch, max_seq)
    if spec.mixer == "mla":
        return attn.mla_init_cache(cfg, batch, max_seq)
    return mb.mamba2_init_cache(cfg, batch)


def layer_decode(
    params: Params,
    spec: LayerSpec,
    cfg: ModelConfig,
    x: jax.Array,  # (B, 1, d)
    pos: jax.Array,
    cache: Params,
    *,
    enc: jax.Array | None = None,
) -> tuple[jax.Array, Params]:
    with jax.named_scope("attn"):
        h = rmsnorm(params["norm1"], x, cfg.norm_eps, bf16=cfg.bf16_norm)
        if spec.mixer == "gqa":
            h, cache = attn.gqa_decode(params["mixer"], h, pos, cache, cfg)
        elif spec.mixer == "mla":
            h, cache = attn.mla_decode(params["mixer"], h, pos, cache, cfg)
        else:
            h, cache = mb.mamba2_decode(params["mixer"], h, cache, cfg)
        x = x + h
        if spec.cross_attention:
            assert enc is not None
            h = rmsnorm(params["norm_ca"], x, cfg.norm_eps, bf16=cfg.bf16_norm)
            x = x + attn.cross_attn_apply(params["cross"], h, enc, cfg)
    if spec.ffn != "none":
        with jax.named_scope("mlp"):
            h = rmsnorm(params["norm2"], x, cfg.norm_eps, bf16=cfg.bf16_norm)
            if spec.ffn == "dense":
                h = (
                    swiglu(params["ffn"], h)
                    if cfg.ffn_activation == "swiglu"
                    else gelu_mlp(params["ffn"], h)
                )
            else:
                h, _ = moe_mod.moe_apply(params["ffn"], h, cfg)
            x = x + h
    return x, cache


# ---------------------------------------------------------------------------
# Groups (scan over stacked layers)
# ---------------------------------------------------------------------------


def _stack_trees(trees: list[PyTree]) -> PyTree:
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def groups_init(
    key: jax.Array, cfg: ModelConfig, groups=None
) -> list[list[Params]]:
    groups = cfg.layer_groups() if groups is None else groups
    out = []
    li = 0
    for pattern, reps in groups:
        per_pos: list[list[Params]] = [[] for _ in pattern]
        for r in range(reps):
            for pi, spec in enumerate(pattern):
                per_pos[pi].append(
                    layer_init(jax.random.fold_in(key, li), spec, cfg)
                )
                li += 1
        out.append([_stack_trees(ps) for ps in per_pos])
    return out


def groups_apply(
    gparams: list[list[Params]],
    cfg: ModelConfig,
    x: jax.Array,
    positions: jax.Array,
    *,
    enc: jax.Array | None = None,
    causal: bool = True,
    remat: str = "dots",
    groups=None,
) -> tuple[jax.Array, jax.Array]:
    groups = cfg.layer_groups() if groups is None else groups
    aux_total = jnp.zeros((), jnp.float32)
    policy = REMAT_POLICIES[remat]

    for (pattern, reps), stacked in zip(groups, gparams):

        def body(carry, layer_params, pattern=pattern):
            h, aux = carry
            for spec, p in zip(pattern, layer_params):
                h, a = layer_apply(
                    p, spec, cfg, h, positions, enc=enc, causal=causal
                )
                aux = aux + a
            return (h, aux), None

        if remat != "none":
            body = jax.checkpoint(body, policy=policy)
        if reps == 1:
            (x, aux_total), _ = body(
                (x, aux_total), [jax.tree.map(lambda t: t[0], s) for s in stacked]
            )
        else:
            (x, aux_total), _ = lax.scan(body, (x, aux_total), stacked)
    return x, aux_total


def groups_init_cache(
    cfg: ModelConfig, batch: int, max_seq: int, groups=None
) -> list[list[Params]]:
    groups = cfg.layer_groups() if groups is None else groups
    out = []
    for pattern, reps in groups:
        out.append(
            [
                _stack_trees(
                    [layer_init_cache(spec, cfg, batch, max_seq) for _ in range(reps)]
                )
                for spec in pattern
            ]
        )
    return out


def groups_decode(
    gparams: list[list[Params]],
    caches: list[list[Params]],
    cfg: ModelConfig,
    x: jax.Array,
    pos: jax.Array,
    *,
    enc: jax.Array | None = None,
    groups=None,
) -> tuple[jax.Array, list[list[Params]]]:
    groups = cfg.layer_groups() if groups is None else groups
    new_caches: list[list[Params]] = []
    for (pattern, reps), stacked, cstacked in zip(groups, gparams, caches):

        def body(h, xs, pattern=pattern):
            layer_params, layer_caches = xs
            new_lc = []
            for spec, p, c in zip(pattern, layer_params, layer_caches):
                h, c2 = layer_decode(p, spec, cfg, h, pos, c, enc=enc)
                new_lc.append(c2)
            return h, new_lc

        if reps == 1:
            p0 = [jax.tree.map(lambda t: t[0], s) for s in stacked]
            c0 = [jax.tree.map(lambda t: t[0], s) for s in cstacked]
            x, nc = body(x, (p0, c0))
            new_caches.append([jax.tree.map(lambda t: t[None], c) for c in nc])
        else:
            x, nc = lax.scan(body, x, (stacked, cstacked))
            new_caches.append(nc)
    return x, new_caches


# ---------------------------------------------------------------------------
# Full model: init / forward / loss / prefill / decode
# ---------------------------------------------------------------------------


def model_init(key: jax.Array, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 6)
    p: Params = {
        "embed": embedding_init(ks[0], cfg.vocab_size, cfg.d_model),
        "final_norm": rmsnorm_init(cfg.d_model),
        "groups": groups_init(ks[1], cfg),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = embedding_init(ks[2], cfg.vocab_size, cfg.d_model)
    if cfg.pos_scheme == "learned":
        p["pos_emb"] = (
            jax.random.normal(ks[3], (cfg.max_position_embeddings, cfg.d_model))
            * 0.02
        )
    if cfg.is_encdec:
        enc_cfg = encoder_config(cfg)
        p["encoder"] = {
            "groups": groups_init(ks[4], enc_cfg, enc_cfg.layer_groups()),
            "final_norm": rmsnorm_init(cfg.d_model),
            "pos_emb": jax.random.normal(ks[5], (cfg.encoder_seq_len, cfg.d_model))
            * 0.02,
        }
    return p


def encoder_config(cfg: ModelConfig) -> ModelConfig:
    """Whisper encoder stack: bidirectional GQA + GeLU FFN, no MoE."""
    return dataclasses.replace(
        cfg,
        num_layers=cfg.encoder_layers,
        num_experts=0,
        attn_period=0,
        encoder_layers=0,  # the encoder itself is not enc-dec
        pos_scheme="learned",
    )


def encode(params: Params, cfg: ModelConfig, frames: jax.Array,
           remat: str = "dots") -> jax.Array:
    """Whisper encoder over precomputed frame embeddings (stub frontend)."""
    enc_cfg = encoder_config(cfg)
    T = frames.shape[1]
    x = cast(frames) + cast(params["encoder"]["pos_emb"][:T])
    pos = jnp.broadcast_to(jnp.arange(T), frames.shape[:2])
    x, _ = groups_apply(
        params["encoder"]["groups"], enc_cfg, x, pos,
        causal=False, remat=remat,
    )
    return rmsnorm(params["encoder"]["final_norm"], x, cfg.norm_eps, bf16=cfg.bf16_norm)


def forward_hidden(
    params: Params,
    cfg: ModelConfig,
    batch: dict,
    *,
    remat: str = "dots",
) -> tuple[jax.Array, jax.Array]:
    """Returns (hidden (B,S,d) after final norm, aux_loss)."""
    if "embeds" in batch:  # vlm: precomputed patch/token embeddings
        x = cast(batch["embeds"])
        positions = batch["positions"]  # (3, B, S) M-RoPE
        B, S = x.shape[:2]
    else:
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = embed(params["embed"], tokens)
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = maybe_shard(x, BATCH, None, None)
    if cfg.pos_scheme == "learned":
        x = x + cast(params["pos_emb"][:S])
    enc = None
    if cfg.is_encdec:
        enc = encode(params, cfg, batch["enc_frames"], remat=remat)
    x, aux = groups_apply(
        params["groups"], cfg, x, positions, enc=enc, remat=remat
    )
    return rmsnorm(params["final_norm"], x, cfg.norm_eps, bf16=cfg.bf16_norm), aux


def _head_table(params: Params, cfg: ModelConfig) -> jax.Array:
    return (params["embed"] if cfg.tie_embeddings else params["lm_head"])["table"]


def loss_fn(
    params: Params,
    cfg: ModelConfig,
    batch: dict,
    *,
    remat: str = "dots",
    loss_chunks: int = 8,
    z_loss: float = 1e-4,
) -> tuple[jax.Array, dict]:
    """Next-token CE, computed in sequence chunks with TP-sharded vocab."""
    hidden, aux = forward_hidden(params, cfg, batch, remat=remat)
    labels = batch["labels"]
    B, S, d = hidden.shape
    chunks = loss_chunks
    while S % chunks:
        chunks -= 1
    hs = hidden.reshape(B, chunks, S // chunks, d).swapaxes(0, 1)
    ls = labels.reshape(B, chunks, S // chunks).swapaxes(0, 1)
    with jax.named_scope("lm_head"):
        table = _head_table(params, cfg).astype(jnp.float32)

        def chunk_loss(carry, xs):
            h, lbl = xs  # (B, sc, d), (B, sc)
            logits = jnp.einsum("bsd,vd->bsv", h.astype(jnp.float32), table)
            logits = maybe_shard(logits, BATCH, None, TP)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, lbl[..., None], axis=-1)[..., 0]
            ce = (lse - gold).sum()
            zl = (lse ** 2).sum() * z_loss
            return carry + ce + zl, None

        total, _ = lax.scan(chunk_loss, jnp.zeros((), jnp.float32), (hs, ls))
    ntok = B * S
    loss = total / ntok + aux
    return loss, {"loss": loss, "ce": total / ntok, "aux": aux}


# -- serving ---------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_seq: int) -> dict:
    cache: dict = {"layers": groups_init_cache(cfg, batch, max_seq)}
    if cfg.is_encdec:
        cache["enc"] = jnp.zeros(
            (batch, cfg.encoder_seq_len, cfg.d_model), jnp.bfloat16
        )
    return cache


def layer_prefill(
    params: Params,
    spec: LayerSpec,
    cfg: ModelConfig,
    x: jax.Array,
    positions: jax.Array,
    max_seq: int,
    *,
    enc: jax.Array | None = None,
) -> tuple[jax.Array, Params]:
    """Full-sequence layer that also emits its decode cache."""
    with jax.named_scope("attn"):
        h = rmsnorm(params["norm1"], x, cfg.norm_eps, bf16=cfg.bf16_norm)
        if spec.mixer == "gqa":
            h, cache = attn.gqa_prefill(params["mixer"], h, positions, cfg, max_seq)
        elif spec.mixer == "mla":
            h, cache = attn.mla_prefill(params["mixer"], h, positions, cfg, max_seq)
        else:
            h, cache = mb.mamba2_prefill(params["mixer"], h, cfg)
        x = x + h
        x = maybe_shard(x, BATCH, None, None)
        if spec.cross_attention:
            assert enc is not None
            h = rmsnorm(params["norm_ca"], x, cfg.norm_eps, bf16=cfg.bf16_norm)
            x = x + attn.cross_attn_apply(params["cross"], h, enc, cfg)
    if spec.ffn != "none":
        with jax.named_scope("mlp"):
            h = rmsnorm(params["norm2"], x, cfg.norm_eps, bf16=cfg.bf16_norm)
            if spec.ffn == "dense":
                h = (
                    swiglu(params["ffn"], h)
                    if cfg.ffn_activation == "swiglu"
                    else gelu_mlp(params["ffn"], h)
                )
            else:
                h, _ = moe_mod.moe_apply(params["ffn"], h, cfg)
            x = x + h
            x = maybe_shard(x, BATCH, None, None)
    return x, cache


def prefill(
    params: Params,
    cfg: ModelConfig,
    batch: dict,
    max_seq: int,
    *,
    remat: str = "dots",
) -> tuple[jax.Array, dict]:
    """Process the prompt, build the decode cache, return last-token
    logits. The cache is filled directly from the full-sequence
    projections (no second pass)."""
    if "embeds" in batch:
        x = cast(batch["embeds"])
        positions = batch["positions"]
        B, S = x.shape[:2]
    else:
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = embed(params["embed"], tokens)
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = maybe_shard(x, BATCH, None, None)
    if cfg.pos_scheme == "learned":
        x = x + cast(params["pos_emb"][:S])
    enc = None
    if cfg.is_encdec:
        enc = encode(params, cfg, batch["enc_frames"], remat=remat)

    groups = cfg.layer_groups()
    caches: list[list[Params]] = []
    for (pattern, reps), stacked in zip(groups, params["groups"]):

        def body(h, layer_params, pattern=pattern):
            new_lc = []
            for spec, p in zip(pattern, layer_params):
                h, c = layer_prefill(
                    p, spec, cfg, h, positions, max_seq, enc=enc
                )
                new_lc.append(c)
            return h, new_lc

        if remat != "none":
            body = jax.checkpoint(body, policy=REMAT_POLICIES[remat])
        if reps == 1:
            x, lc = body(x, [jax.tree.map(lambda t: t[0], s) for s in stacked])
            caches.append([jax.tree.map(lambda t: t[None], c) for c in lc])
        else:
            x, lc = lax.scan(body, x, stacked)
            caches.append(lc)

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps, bf16=cfg.bf16_norm)
    with jax.named_scope("lm_head"):
        logits = jnp.einsum(
            "bd,vd->bv", x[:, -1].astype(jnp.float32),
            _head_table(params, cfg).astype(jnp.float32),
        )
    cache: dict = {"layers": caches}
    if cfg.is_encdec:
        cache["enc"] = enc.astype(jnp.bfloat16)
    return logits, cache


def decode_step(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,  # (B,) current token ids
    pos: jax.Array,  # scalar int32 — or (B,) per-slot absolute positions
    cache: dict,
) -> tuple[jax.Array, dict]:
    """One decode step: returns (logits (B, V), new cache).

    With a ``(B,)`` ``pos`` every batch row advances at its own absolute
    position (continuous batching); the scalar path is unchanged."""
    x = embed(params["embed"], tokens[:, None])  # (B, 1, d)
    if cfg.pos_scheme == "learned":
        pe = cast(params["pos_emb"][pos])
        x = x + (pe[:, None, :] if pe.ndim == 2 else pe[None, None, :])
    enc = cache.get("enc")
    x, new_layers = groups_decode(
        params["groups"], cache["layers"], cfg, x, pos, enc=enc
    )
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps, bf16=cfg.bf16_norm)
    with jax.named_scope("lm_head"):
        logits = jnp.einsum(
            "bd,vd->bv", x[:, 0].astype(jnp.float32),
            _head_table(params, cfg).astype(jnp.float32),
        )
        logits = maybe_shard(logits, BATCH, TP)
    new_cache = dict(cache)
    new_cache["layers"] = new_layers
    return logits, new_cache
