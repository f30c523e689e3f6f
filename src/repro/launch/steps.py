"""jit-able step builders: train / prefill / serve, with sharding specs.

``build_cell`` is the single entry used by the dry-run, the trainer and
the benchmarks: given (arch config, shape, mesh) it returns the step
function plus fully-resolved in/out shardings and ShapeDtypeStruct
arguments — everything needed to ``jit(...).lower().compile()`` without
allocating a single parameter.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs as C
from repro.configs.shapes import Shape, input_specs
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.models.layers import COMPUTE_DTYPE
from repro.optim import adamw
from repro.parallel import sharding as shd
from repro.parallel.collectives import torrent_grad_reduce

PyTree = Any


@dataclasses.dataclass
class Cell:
    """One (arch × shape × mesh) dry-run/benchmark cell."""

    cfg: ModelConfig
    shape: Shape
    mesh: jax.sharding.Mesh
    step_fn: Callable
    args: tuple  # ShapeDtypeStructs (or concrete arrays)
    in_shardings: tuple
    out_shardings: Any
    donate_argnums: tuple[int, ...] = ()
    num_chains: int | str = 1  # effective K after VARIANTS resolution ("auto" = model-picked)
    ar_algo: str = "rs_ag"  # multi-ring all-reduce schedule (rs_ag | rotation)
    compress_grads: bool = False  # int8 wire on the DP grad reduction
    bucket_bytes: int | None = None  # bucketed backward-overlapped reduce
    topology: str | None = None  # tiered link-graph spec for auto-K planning

    def lower(self):
        jitted = jax.jit(
            self.step_fn,
            in_shardings=self.in_shardings,
            out_shardings=self.out_shardings,
            donate_argnums=self.donate_argnums,
        )
        with jax.set_mesh(self.mesh):
            return jitted.lower(*self.args)


def _sanitize(spec: P | None, mesh) -> P:
    """Drop axes the mesh doesn't have (e.g. 'pod' on single-pod)."""
    if spec is None:
        return P()
    names = set(mesh.axis_names)
    out = []
    for el in spec:
        if el is None:
            out.append(None)
        elif isinstance(el, tuple):
            kept = tuple(a for a in el if a in names)
            # canonicalize: a 1-tuple equals its bare name on current
            # jax but not on the 0.4.x line — emit the bare name.
            out.append(kept[0] if len(kept) == 1 else (kept if kept else None))
        else:
            out.append(el if el in names else None)
    return P(*out)


def _named(mesh, specs):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, _sanitize(s, mesh)),
        specs,
        is_leaf=lambda x: isinstance(x, P) or x is None,
    )


def make_train_step(
    cfg: ModelConfig,
    opt_cfg: adamw.OptConfig,
    *,
    remat: str = "dots",
    collectives: str = "xla",
    num_chains: int | str = 1,
    ar_algo: str = "rs_ag",
    compress_grads: bool = False,
    error_feedback: bool = False,
    bucket_bytes: int | None = None,
    topology: str | None = None,
    mesh=None,
    batch_specs=None,
    loss_chunks: int = 8,
    microbatches: int = 1,
):
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    ``microbatches > 1`` runs gradient accumulation: the global batch is
    split along dim 0 and scanned, dividing the activation working set
    by M at unchanged math (equal microbatches ⇒ mean-of-means == global
    mean) — the HBM-fit lever for the large training cells (§Perf).

    ``num_chains`` (with ``collectives="torrent"``) selects the
    multi-chain Chainwrite gradient reduction: K concurrent sub-rings
    per DP reduction (``parallel.collectives.torrent_grad_reduce``);
    ``"auto"`` picks K per gradient leaf from the calibrated
    ``all_reduce_latency`` model. ``ar_algo`` selects the multi-ring
    schedule (``"rs_ag"`` fused reduce-scatter/all-gather, the
    bandwidth-optimal default, or ``"rotation"``). Both are sweepable
    next to ``collectives=`` from the dry-run CLI (``--num-chains``,
    ``--ar-algo``) and via ``VARIANTS`` bundles.

    ``bucket_bytes`` (``collectives="torrent"`` only) switches the DP
    reduction to bucketed, backward-overlapped dispatch: gradient
    leaves group into size-targeted dtype-uniform buckets
    (``parallel.collectives.assign_buckets``) and each bucket issues
    ONE chain all-reduce in reverse-topological order — the first
    buckets' collectives are emitted before the fusions producing the
    remaining gradients, so XLA's scheduler can run them behind the
    rest of backward (evidence: ``launch.hlo_breakdown.overlap_stats``;
    modeled timeline: ``core.simulator.overlap_timeline``). Composes
    with ``num_chains``/``ar_algo``/``compress_grads``.

    ``compress_grads`` ships the DP gradient reduction int8-quantized
    per wire hop (``torrent_grad_reduce(wire_dtype="int8")``) — it
    composes with ``num_chains``/``ar_algo`` and requires
    ``collectives="torrent"``. ``error_feedback`` (requires
    ``compress_grads``) changes the signature to ``(params, opt_state,
    ef_state, batch) -> (params, opt_state, ef_state, metrics)``,
    carrying each DP rank's quantization residual across steps
    (EF-SGD; state from ``parallel.collectives.ef_residual_init``).

    ``topology`` (``collectives="torrent"`` only) is a
    ``core.topology`` spec string (e.g. ``"pods=4:interpod_bw=0.25"``)
    that models the DP ring as a tiered link graph for the
    ``num_chains="auto"`` selection — the hierarchical pod-aligned
    schedule then competes on modeled latency. Advisory: specs that do
    not fit the reduced axis degrade to the uniform ring.
    """
    if compress_grads and collectives != "torrent":
        raise ValueError(
            'compress_grads=True requires collectives="torrent" '
            "(the int8 wire is a property of the Chainwrite schedule; "
            "the XLA backend has no compressed all-reduce)"
        )
    if error_feedback and not compress_grads:
        raise ValueError(
            "error_feedback=True requires compress_grads=True: with an "
            "exact wire there is no quantization residual to feed back"
        )
    if error_feedback and microbatches > 1:
        raise ValueError(
            "error_feedback with microbatches > 1 is not supported: the "
            "residual is per wire reduction, not per accumulation step"
        )
    if bucket_bytes is not None and collectives != "torrent":
        raise ValueError(
            'bucket_bytes requires collectives="torrent" (bucketed '
            "dispatch is a property of the Chainwrite reduction; the "
            "XLA backend buckets internally)"
        )
    if topology is not None and collectives != "torrent":
        raise ValueError(
            'topology requires collectives="torrent" (the link-graph '
            "spec steers the Chainwrite ring planner; the XLA backend "
            "has no topology knob)"
        )
    wire_dtype = "int8" if compress_grads else None

    def grad_fn_local(params, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: T.loss_fn(p, cfg, batch, remat=remat, loss_chunks=loss_chunks),
            has_aux=True,
        )(params)
        return grads, metrics

    def model_specs(params):
        """The params' layout over the model axis (as ``param_pspecs``
        shards them), so the Torrent reduce keeps each leaf sharded;
        None when no model axis splits them."""
        tp = 1 if mesh is None else dict(mesh.shape).get("model", 1)
        if tp == 1:
            return None
        return jax.tree.map(
            lambda s: _sanitize(s, mesh),
            shd.param_pspecs(params, cfg, tp=tp),
            is_leaf=lambda x: isinstance(x, P),
        )

    def grad_fn(params, batch):
        if collectives == "torrent":
            return torrent_grad_reduce(
                grad_fn_local, mesh, batch_specs,
                num_chains=num_chains, algo=ar_algo,
                wire_dtype=wire_dtype, bucket_bytes=bucket_bytes,
                topology=topology, param_specs=model_specs(params),
            )(params, batch)
        return grad_fn_local(params, batch)

    if error_feedback:

        def train_step_ef(params, opt_state, ef_state, batch):
            reduce_ef = torrent_grad_reduce(
                grad_fn_local, mesh, batch_specs,
                num_chains=num_chains, algo=ar_algo,
                wire_dtype=wire_dtype, error_feedback=True,
                bucket_bytes=bucket_bytes, topology=topology,
                param_specs=model_specs(params),
            )
            grads, metrics, new_ef = reduce_ef(params, batch, ef_state)
            with jax.named_scope("optimizer"):
                new_params, new_opt, om = adamw.update(
                    opt_cfg, grads, opt_state, params
                )
            return new_params, new_opt, new_ef, {**metrics, **om}

        return train_step_ef

    def train_step(params, opt_state, batch):
        if microbatches > 1:
            M = microbatches
            split = jax.tree.map(
                lambda x: x.reshape((M, x.shape[0] // M) + x.shape[1:]), batch
            )

            def body(acc, mbatch):
                grads, metrics = grad_fn(params, mbatch)
                acc = jax.tree.map(jnp.add, acc, grads)
                return acc, metrics

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            acc, ms = jax.lax.scan(body, zeros, split)
            grads = jax.tree.map(lambda g: g / M, acc)
            metrics = jax.tree.map(lambda m: m.mean(0), ms)
        else:
            grads, metrics = grad_fn(params, batch)
        with jax.named_scope("optimizer"):
            new_params, new_opt, om = adamw.update(opt_cfg, grads, opt_state, params)
        return new_params, new_opt, {**metrics, **om}

    return train_step


def make_prefill_step(cfg: ModelConfig, max_seq: int, *, remat: str = "dots"):
    def prefill_step(params, batch):
        return T.prefill(params, cfg, batch, max_seq, remat=remat)

    return prefill_step


def make_serve_step(cfg: ModelConfig):
    def serve_step(params, tokens, pos, cache):
        logits, new_cache = T.decode_step(params, cfg, tokens, pos, cache)
        next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tokens, new_cache

    return serve_step


def make_slot_prefill_step(cfg: ModelConfig, max_seq: int, *, remat: str = "dots"):
    """Per-slot prefill for continuous batching: one (1, S) prompt in,
    (first greedy token (1,), single-row cache) out.

    Unlike :func:`make_prefill_step` this never touches the other slots'
    state — the serve loop writes the returned cache row into the live
    batch cache with :func:`write_cache_slot`, so an admission cannot
    disturb in-flight requests."""

    def slot_prefill_step(params, tokens):
        logits, cache = T.prefill(
            params, cfg, {"tokens": tokens}, max_seq, remat=remat
        )
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    return slot_prefill_step


# Ops that only move or select a weight's elements: a leaf read through
# them is still read as its own values.
_SHAPE_ONLY = frozenset(
    {"squeeze", "reshape", "slice", "dynamic_slice", "gather", "transpose", "split"}
)


def _sub_invar(eqn, i: int):
    """(sub-jaxpr, invar) that operand ``i`` of a call-like ``eqn``
    binds to, or None where the walk does not follow it (a scan carry
    loops back into itself, so only consts and xs are followed)."""
    name, p = eqn.primitive.name, eqn.params
    if name == "scan":
        if p["num_consts"] <= i < p["num_consts"] + p["num_carry"]:
            return None
        sub = p["jaxpr"].jaxpr
    elif name == "jit":
        sub = p["jaxpr"].jaxpr
    elif name == "remat2":  # jax.checkpoint
        sub = p["jaxpr"]
    elif name == "closed_call":
        sub = p["call_jaxpr"].jaxpr
    else:
        return None
    return sub, sub.invars[i]


def _read_as_compute(jaxpr, var) -> tuple[bool, bool]:
    """(read at all, every read is a cast to ``COMPUTE_DTYPE``) for
    ``var`` in ``jaxpr``, following shape-only ops and call operands."""
    if any(v is var for v in jaxpr.outvars):
        return True, False
    read = False
    for eqn in jaxpr.eqns:
        for i, v in enumerate(eqn.invars):
            if v is not var:
                continue
            read = True
            name = eqn.primitive.name
            if name == "convert_element_type":
                ok = eqn.params["new_dtype"] == COMPUTE_DTYPE
            elif name in _SHAPE_ONLY:
                ok = i == 0 and all(_read_as_compute(jaxpr, o)[1] for o in eqn.outvars)
            else:
                sub = _sub_invar(eqn, i)
                ok = sub is not None and _read_as_compute(*sub)[1]
            if not ok:
                return True, False
    return read, True


def serving_params(cfg: ModelConfig, params: PyTree, *, batch: int,
                   prompt_len: int, max_seq: int) -> PyTree:
    """``params`` in the dtypes the serve programs read them in.

    Both programs (:func:`make_serve_step` on a ``batch``-slot cache and
    :func:`make_slot_prefill_step` on a ``prompt_len`` prompt) are traced
    on abstract values. A float32 leaf is converted once, here, to
    ``COMPUTE_DTYPE`` when both programs read it and every read is a
    cast to that dtype, possibly after shape-only indexing or through a
    scan, jit or checkpoint. Every other leaf (norm scales, a head read
    in float32, SSM decay and conv weights, a router, a table read both
    ways) is returned as it is. Rounding once gives the bf16 values each
    step would have made, and ``layers.cast`` of a bf16 array adds no
    op, so the programs compute the same numbers without the casts."""
    shapes = jax.eval_shape(lambda: params)
    cache = jax.eval_shape(lambda: T.init_cache(cfg, batch, max_seq))
    rows = jax.ShapeDtypeStruct((batch,), jnp.int32)
    prompt = jax.ShapeDtypeStruct((1, prompt_len), jnp.int32)
    programs = (
        jax.make_jaxpr(make_serve_step(cfg))(shapes, rows, rows, cache),
        jax.make_jaxpr(make_slot_prefill_step(cfg, max_seq))(shapes, prompt),
    )

    def cast_once(k, x):
        return x.dtype == jnp.float32 and all(
            all(_read_as_compute(p.jaxpr, p.jaxpr.invars[k])) for p in programs
        )

    leaves, tree = jax.tree.flatten(params)
    return jax.tree.unflatten(tree, [
        x.astype(COMPUTE_DTYPE) if cast_once(k, x) else x for k, x in enumerate(leaves)
    ])


def write_cache_slot(cache: PyTree, one_cache: PyTree, slot: int) -> PyTree:
    """Scatter a batch=1 cache (from ``make_slot_prefill_step``) into row
    ``slot`` of a live multi-slot cache. Leaves are (reps, B, ...)."""
    return jax.tree.map(
        lambda full, one: full.at[:, slot].set(one[:, 0].astype(full.dtype)),
        cache,
        one_cache,
    )


# ---------------------------------------------------------------------------
# Cell assembly (dry-run entry)
# ---------------------------------------------------------------------------


# Named optimization bundles for the §Perf hillclimb. "baseline" is the
# paper-faithful configuration; each variant is one recorded change.
# Entries are ModelConfig field overrides, except the step-builder
# knobs "num_chains" and "ar_algo" (popped by build_cell and routed to
# make_train_step) so the multi-chain Chainwrite reduction sweeps next
# to ``collectives=``.
VARIANTS: dict[str, dict] = {
    "baseline": {},
    # multi-chain Chainwrite DP reduction (K=2 concurrent sub-rings,
    # fused RS+AG schedule); only meaningful with collectives="torrent".
    "k2": {"num_chains": 2},
    # K=2 with PR 1's full-payload rotation schedule — the regression
    # twin that keeps the (S+K-2)-payload wire behaviour sweepable.
    "k2-rot": {"num_chains": 2, "ar_algo": "rotation"},
    # model-driven K: all_reduce_latency picks per gradient leaf.
    "k-auto": {"num_chains": "auto"},
    # chunked online-softmax attention (flash twin) — kills the S²
    # score materialization that dominates every memory term.
    "chunked": {"attn_impl": "chunked"},
    # + absorbed MLA decode + bf16 MoE wire + bf16 norms + row-wise
    # (DP×EP-shardable) MoE dispatch.
    "opt": {
        "attn_impl": "chunked", "mla_absorb": True,
        "moe_bf16_wire": True, "bf16_norm": True, "moe_row_dispatch": True,
    },
    # Torrent expert-parallel MoE: tokens stay DP-sharded, experts
    # partition over the DP axes, dispatch/combine run as explicit
    # scheduled chain all-to-alls (models.moe.moe_apply_ep via the
    # ChainProgram planner) instead of GSPMD reshardings. Sweepable
    # next to collectives=; falls back to the flat path when the DP
    # group doesn't divide experts/batch.
    "moe-ep": {"moe_ep_dispatch": True},
    # moe-ep with the K=2 multi-chain all-to-all exchange.
    "moe-ep-k2": {"moe_ep_dispatch": True, "moe_ep_chains": 2},
    # int8-compressed DP gradient reduction (wire_dtype="int8" through
    # torrent_grad_reduce — per-hop quantized frames + f32 scale
    # sideband, 4× fewer payload bytes); collectives="torrent" only.
    "int8-ar": {"compress_grads": True},
    # int8 wire on the K=2 multi-chain schedule — compression and
    # multi-chain compose since the wire became an IR dimension.
    "int8-ar-k2": {"compress_grads": True, "num_chains": 2},
    # Torrent EP MoE with int8-quantized token dispatch/return.
    "moe-ep-int8": {"moe_ep_dispatch": True, "moe_ep_int8_wire": True},
    # bucketed, backward-overlapped DP grad reduce: 4 MiB dtype-grouped
    # buckets dispatched in reverse-topological order, model-picked K
    # per bucket; collectives="torrent" only.
    "bucketed": {"bucket_bytes": 4 << 20, "num_chains": "auto"},
    # bucketed dispatch with the int8 wire — buckets, compression and
    # auto-K compose (each prices the compressed bucket bytes).
    "bucketed-int8": {
        "bucket_bytes": 4 << 20, "num_chains": "auto",
        "compress_grads": True,
    },
    # tiered link-graph planning: the DP ring is modeled as 2 pods with
    # 4× slower inter-pod links, so num_chains="auto" scores the
    # hierarchical pod-aligned schedule; collectives="torrent" only.
    # The relative pods=2 spec applies wherever 2 divides the DP axis
    # and degrades to the uniform ring elsewhere.
    "tiered": {
        "topology": "pods=2:interpod_bw=0.25", "num_chains": "auto",
    },
    # opt + query-sequence-sharded attention (heads ∤ TP archs).
    "opt-seq": {
        "attn_impl": "chunked", "mla_absorb": True,
        "moe_bf16_wire": True, "bf16_norm": True, "moe_row_dispatch": True,
        "attn_seq_shard": True,
    },
}


def build_cell(
    arch: str,
    shape_name: str,
    mesh: jax.sharding.Mesh,
    *,
    collectives: str = "xla",
    num_chains: int | str = 1,
    ar_algo: str = "rs_ag",
    compress_grads: bool = False,
    bucket_bytes: int | None = None,
    topology: str | None = None,
    remat: str = "dots",
    smoke: bool = False,
    variant: str = "baseline",
) -> Cell:
    cfg = C.get_smoke_config(arch) if smoke else C.get_config(arch)
    overrides = dict(VARIANTS.get(variant) or {})
    variant_k = overrides.pop("num_chains", None)
    if variant_k is not None:
        if num_chains not in (1, variant_k):
            raise ValueError(
                f"variant {variant!r} sets num_chains={variant_k} but "
                f"num_chains={num_chains} was passed explicitly"
            )
        num_chains = variant_k
    variant_algo = overrides.pop("ar_algo", None)
    if variant_algo is not None:
        if ar_algo not in ("rs_ag", variant_algo):
            raise ValueError(
                f"variant {variant!r} sets ar_algo={variant_algo!r} but "
                f"ar_algo={ar_algo!r} was passed explicitly"
            )
        ar_algo = variant_algo
    variant_cg = overrides.pop("compress_grads", None)
    if variant_cg is not None:
        if compress_grads not in (False, variant_cg):
            raise ValueError(
                f"variant {variant!r} sets compress_grads={variant_cg} but "
                f"compress_grads={compress_grads} was passed explicitly"
            )
        compress_grads = variant_cg
    variant_bb = overrides.pop("bucket_bytes", None)
    if variant_bb is not None:
        if bucket_bytes not in (None, variant_bb):
            raise ValueError(
                f"variant {variant!r} sets bucket_bytes={variant_bb} but "
                f"bucket_bytes={bucket_bytes} was passed explicitly"
            )
        bucket_bytes = variant_bb
    variant_topo = overrides.pop("topology", None)
    if variant_topo is not None:
        if topology not in (None, variant_topo):
            raise ValueError(
                f"variant {variant!r} sets topology={variant_topo!r} but "
                f"topology={topology!r} was passed explicitly"
            )
        topology = variant_topo
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = C.SHAPES[shape_name]
    tp = mesh.shape.get("model", 1)
    dp = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)

    params_shape = jax.eval_shape(
        lambda: T.model_init(jax.random.PRNGKey(0), cfg)
    )
    pspecs = shd.param_pspecs(params_shape, cfg, tp=tp)
    specs = input_specs(cfg, shape)

    if shape.kind == "train":
        opt_cfg = adamw.OptConfig()
        opt_shape = jax.eval_shape(lambda: adamw.init(params_shape))
        ospecs = shd.opt_pspecs(pspecs, params_shape, data_size=mesh.shape.get("data", 1))
        bspecs = shd.batch_pspecs(cfg, shape)
        bspecs_clean = jax.tree.map(
            lambda s: _sanitize(s, mesh), bspecs,
            is_leaf=lambda x: isinstance(x, P) or x is None,
        )
        step = make_train_step(
            cfg, opt_cfg, remat=remat, collectives=collectives,
            num_chains=num_chains, ar_algo=ar_algo,
            compress_grads=compress_grads, bucket_bytes=bucket_bytes,
            topology=topology,
            mesh=mesh, batch_specs=bspecs_clean,
        )
        return Cell(
            cfg=cfg, shape=shape, mesh=mesh, step_fn=step,
            args=(params_shape, opt_shape, specs["batch"]),
            in_shardings=(
                _named(mesh, pspecs), _named(mesh, ospecs), _named(mesh, bspecs)
            ),
            out_shardings=(
                _named(mesh, pspecs), _named(mesh, ospecs), None
            ),
            donate_argnums=(0, 1),
            num_chains=num_chains,
            ar_algo=ar_algo,
            compress_grads=compress_grads,
            bucket_bytes=bucket_bytes,
            topology=topology,
        )

    if shape.kind == "prefill":
        bspecs = shd.batch_pspecs(cfg, shape)
        cache_shape = jax.eval_shape(
            lambda: T.init_cache(cfg, shape.global_batch, specs["max_seq"])
        )
        cspecs = shd.cache_pspecs(cache_shape, cfg, shape, tp=tp)
        step = make_prefill_step(cfg, specs["max_seq"], remat=remat)
        return Cell(
            cfg=cfg, shape=shape, mesh=mesh, step_fn=step,
            args=(params_shape, specs["batch"]),
            in_shardings=(_named(mesh, pspecs), _named(mesh, bspecs)),
            out_shardings=(
                NamedSharding(mesh, _sanitize(P(shd.BATCH_AXES, None), mesh)),
                _named(mesh, cspecs),
            ),
        )

    # decode
    cspecs = shd.cache_pspecs(specs["cache"], cfg, shape, tp=tp)
    long_ctx = shape.global_batch == 1
    tok_spec = P() if long_ctx else _sanitize(P(shd.BATCH_AXES), mesh)
    step = make_serve_step(cfg)
    return Cell(
        cfg=cfg, shape=shape, mesh=mesh, step_fn=step,
        args=(params_shape, specs["tokens"], specs["pos"], specs["cache"]),
        in_shardings=(
            _named(mesh, pspecs),
            NamedSharding(mesh, tok_spec),
            NamedSharding(mesh, P()),
            _named(mesh, cspecs),
        ),
        out_shardings=(
            NamedSharding(mesh, tok_spec),
            _named(mesh, cspecs),
        ),
        donate_argnums=(3,),
    )
