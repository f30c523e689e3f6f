"""Collectives backend seam: "xla" (fabric does replication — the
network-layer-multicast analogue) vs "torrent" (Chainwrite: explicitly
scheduled ppermute rings at the application layer).

The flagship integration point is the data-parallel gradient reduction:
``torrent_grad_reduce`` runs the whole grad computation under a
*subset* ``shard_map`` (manual over the DP axes, auto over ``model``)
so the DP reduction is OURS — a scheduled, bucketed, optionally
int8-compressed chain all-reduce — while TP sharding inside the model
stays GSPMD-managed. Options mirror the paper's knobs:

* ``scheduler`` — chain order from core.scheduling over the DP ring;
* ``hierarchical`` — reduce within a pod, then across pods (two short
  chains instead of one long one: (16-1)+(2-1) hops vs 31);
* ``num_chains`` — multi-chain Chainwrite: the flat DP ring is split
  into K disjoint equal sub-rings that reduce concurrently, then
  exchange across rings (``core.chainwrite.multi_chain_all_reduce``).
  ``hierarchical`` over a (pod, data) mesh is exactly the
  ``num_chains = #pods`` special case of this schedule on the
  flattened DP axis — K=2 for the production two-pod system.
  ``num_chains="auto"`` picks K per gradient leaf from the calibrated
  ``core.simulator.all_reduce_latency`` model (modeled bytes *and*
  cycles for the chosen ``algo``);
* ``algo`` — multi-ring all-reduce schedule: ``"rs_ag"`` (default,
  fused per-ring reduce-scatter/all-gather + cross-ring shard
  rotation — ≈ (2·(S-1)+(K-1))/S payloads of wire per device) or
  ``"rotation"`` (PR 1's full-payload rotations — fewer steps,
  (S+K-2) payloads of wire; only wins for tiny payloads);
* ``wire_dtype`` — lossy wire compression as an IR dimension:
  ``wire_dtype="int8"`` plans the SAME multi-chain schedules with every
  hop shipped as an int8 frame + f32 scale (4× fewer payload bytes;
  per-hop quantize → dequantize → f32 accumulate in the executor), so
  compression composes with ``num_chains``, ``algo``, ``hierarchical``
  and the recovery pricing instead of overriding them;
* ``error_feedback`` — EF-SGD (Seide et al.): each DP rank keeps the
  local residual of the lossy wire and adds it back into the next
  step's gradient before compression, restoring convergence. Requires
  a lossy ``wire_dtype``; state rides as an explicit residual pytree
  (``ef_residual_init`` / ``ef_residual_specs``);
* ``bucket_bytes`` — bucketed, backward-overlapped reduction: gradient
  leaves are partitioned into size-targeted, dtype-grouped buckets
  (:func:`assign_buckets`) walked in REVERSE leaf order — the
  reverse-topological approximation of backward production order — and
  each bucket is reduced as ONE chain all-reduce over a chunk-aligned
  flat payload (:func:`bucket_shard_layout`), so the per-collective cfg
  overhead is amortized over many leaves and the first buckets' chains
  can run while the rest of backward is still producing gradients
  (dispatch-order scheduling; XLA is free to interleave each bucket's
  collective with the remaining backward fusions — the overlap
  evidence is counted by ``launch.hlo_breakdown.overlap_stats`` and
  the modeled timeline lives in ``core.simulator.overlap_timeline``).
  Chunk alignment keeps every element's ring fold order identical to
  the per-leaf reduce, so the bucketed path is BIT-identical at the
  exact (f32) wire (fold-order identity is pinned against the numpy
  twin; compiled artifacts can still pick up 1-ulp excess precision
  when XLA FMA-contracts the gradient producer into the combine adds —
  a backend freedom independent of bucketing); it composes with
  ``num_chains="auto"`` (K resolved per bucket from the bucket's
  bytes), ``algo``, ``hierarchical`` and ``compress_grads`` (per-leaf
  EF residuals, bucketed int8 wire).

Since the ChainProgram refactor the OTHER ring collectives are exposed
through the same seam: ``torrent_all_to_all`` (the MoE expert-dispatch
exchange — see ``models.moe.moe_apply_ep``), ``torrent_reduce_scatter``
and ``torrent_all_gather`` each accept ``num_chains`` and route through
``core.chainwrite.multi_chain_*`` (K disjoint sub-rings planned by
``core.program``; K=1 is the classic scheduled ring).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import chainwrite as cw
from repro.core import simulator as sim
from repro.core.scheduling import (
    SCHEDULERS,
    FailureSpec,
    normalize_failed,
    partition_schedule,
    reform_chain,
)
from repro.core.simulator import SourceFailedError
from repro.core.topology import MeshTopology, parse_topology_spec
from repro.core import program as prg
from repro.parallel import hints
from repro.runtime.compression import dequantize, quantize

PyTree = Any


class MultiChainPlan:
    """Host-side multi-chain broadcast plan with endpoint-only
    re-forming — the integration seam between the Torrent fault model
    and ``runtime.failure.resilient_loop``.

    The destination set is partitioned into K link-disjoint-preferring
    sub-chains (``core.scheduling.partition_schedule``). On a node
    failure, :meth:`reform` splices the dead member(s) — one node or a
    concurrent failure *set* — out of their sub-chains and re-orders
    each orphaned suffix
    (``core.scheduling.reform_chain`` — torus-aware), so the next
    :meth:`broadcast` is the degraded collective over the survivors:
    recovery is just a new chain schedule (the XDMA property — no NoC
    change), and a training step retries instead of restarting the
    whole collective from a checkpoint. Pass ``plan.reform`` as
    ``resilient_loop(reform_fn=...)``.
    """

    def __init__(
        self,
        topo: MeshTopology,
        head: int,
        destinations,
        *,
        num_chains: int | None = None,
        scheduler: str = "tsp",
        max_chains: int = 4,
    ) -> None:
        self.topo = topo
        self.head = int(head)
        self.scheduler = scheduler
        self.chains: list[list[int]] = [
            list(c)
            for c in partition_schedule(
                topo, list(destinations), self.head,
                num_chains=num_chains, scheduler=scheduler,
                max_chains=max_chains,
            )
        ]
        self.failed: list[int] = []

    @property
    def survivors(self) -> list[int]:
        return [d for c in self.chains for d in c]

    def reform(self, node: FailureSpec) -> bool:
        """Re-form around the dead member(s) ``node`` — one node id or
        a set of concurrently dead members; True when handled.

        Only the sub-chains containing dead members change (each
        orphaned suffix is re-scheduled from its surviving tail, one
        ``reform_chain`` per affected chain — exactly the schedule
        ``core.program.plan_recovery`` prices); every other sub-chain
        keeps its schedule verbatim. The *head* dying is total loss —
        no survivor banked the payload — and raises
        :class:`~repro.core.simulator.SourceFailedError` so
        ``resilient_loop`` falls back to checkpoint rollback. Unknown
        nodes (already failed or never a member) return False, without
        touching the plan, so the caller can fall back too.
        """
        dead = set(normalize_failed(node))
        if self.head in dead:
            raise SourceFailedError(
                f"node {self.head} is the plan head: total loss, "
                "re-forming cannot recover the source"
            )
        live = {d for c in self.chains for d in c}
        if dead - live:  # unknown/already-failed: leave the plan alone
            return False
        reformed: list[list[int]] = []
        for chain in self.chains:
            chain_dead = [d for d in chain if d in dead]
            if not chain_dead:
                reformed.append(chain)
                continue
            new = reform_chain(
                self.topo, chain, chain_dead, self.head,
                scheduler=self.scheduler,
            )
            if new:
                reformed.append(new)
        self.chains = reformed
        self.failed.extend(sorted(dead))
        return True

    def broadcast(self, x, axis_name, *, num_frames: int = 1):
        """The (possibly degraded) multi-chain broadcast over the
        current survivor schedule. Must run inside ``shard_map``."""
        if not self.chains:
            # every destination failed: only the head keeps its payload
            idx = cw._axis_index(axis_name)
            return jnp.where(idx == self.head, x, jnp.zeros_like(x))
        return cw.multi_chain_broadcast(
            x, axis_name, self.head, self.chains, num_frames=num_frames
        )


def ring_order_for_axis(axis_size: int, scheduler: str = "tsp") -> tuple[int, ...]:
    """Chain order for a DP ring: schedule the axis's devices as a 1-D
    NoC (linear neighbours), which the TSP/greedy scheduler traverses
    with 1 hop per destination — the ICI-torus-matched snake order."""
    if axis_size <= 2 or scheduler == "naive":
        return tuple(range(axis_size))
    topo = MeshTopology(axis_size, 1)
    order = SCHEDULERS[scheduler](topo, list(range(1, axis_size)), source=0)
    return (0, *order)


def sub_ring_orders(
    axis_size: int, num_chains: int, scheduler: str = "tsp"
) -> list[tuple[int, ...]]:
    """Split the scheduled DP ring into ``num_chains`` contiguous
    sub-rings for ``multi_chain_all_reduce``. Contiguous slices of the
    snake order keep every intra-ring hop at 1 physical link on the
    ICI torus (the multi-chain analogue of ``ring_order_for_axis``)."""
    if axis_size % num_chains:
        raise ValueError(
            f"num_chains={num_chains} must divide the DP group size {axis_size}"
        )
    ring = ring_order_for_axis(axis_size, scheduler)
    size = axis_size // num_chains
    return [tuple(ring[i * size : (i + 1) * size]) for i in range(num_chains)]


def _dp_axes(mesh) -> tuple[str, ...]:
    return hints.dp_axes(mesh.axis_names)


def _axis_orders(
    axis_name, num_chains: int, scheduler: str
) -> list[tuple[int, ...]]:
    """Resolve the K sub-ring partition of a manual axis at trace time
    (K=1 -> the single snake ring). Must run inside ``shard_map``."""
    size = cw._axis_size(axis_name)
    if num_chains <= 1 or size <= num_chains:
        return [ring_order_for_axis(size, scheduler)]
    return sub_ring_orders(size, num_chains, scheduler)


def torrent_all_to_all(
    x, axis_name, *, num_chains: int = 1, scheduler: str = "tsp",
    wire_dtype: str | None = None,
):
    """Scheduled-ring all-to-all over a manual axis (the MoE
    expert-dispatch exchange): ``x`` has leading dim = axis size, chunk
    ``x[j]`` is destined to device ``j``; returns ``out[s]`` = the
    chunk device ``s`` sent here. ``num_chains > 1`` uses the K-ring
    schedule (same wire bytes — a chunk train cannot shrink — but
    ring-local/position-paired hops). ``wire_dtype="int8"`` ships every
    hop of the chunk train quantized (int8 frame + f32 scale). Must run
    inside ``shard_map``."""
    orders = _axis_orders(axis_name, num_chains, scheduler)
    if len(orders) == 1:
        return cw.chain_all_to_all(x, axis_name, orders[0], wire_dtype=wire_dtype)
    return cw.multi_chain_all_to_all(x, axis_name, orders, wire_dtype=wire_dtype)


def torrent_reduce_scatter(
    x, axis_name, *, num_chains: int = 1, scheduler: str = "tsp"
):
    """Scheduled-ring reduce-scatter over a manual axis: ``x`` has
    leading dim = axis size; returns this device's fully reduced
    chunk. Must run inside ``shard_map``."""
    orders = _axis_orders(axis_name, num_chains, scheduler)
    if len(orders) == 1:
        return cw.chain_reduce_scatter(x, axis_name, orders[0])
    return cw.multi_chain_reduce_scatter(x, axis_name, orders)


def torrent_all_gather(
    x, axis_name, *, num_chains: int = 1, scheduler: str = "tsp",
    tiled: bool = False,
):
    """Scheduled-ring all-gather over a manual axis (device-id indexed
    stack, or concatenation with ``tiled=True``). Must run inside
    ``shard_map``."""
    orders = _axis_orders(axis_name, num_chains, scheduler)
    if len(orders) == 1:
        return cw.chain_all_gather(x, axis_name, orders[0], tiled=tiled)
    return cw.multi_chain_all_gather(x, axis_name, orders, tiled=tiled)


def _ring_topology(
    axis_size: int, topology: "str | MeshTopology | None"
) -> MeshTopology:
    """Resolve the (optional) topology knob for one DP ring of
    ``axis_size`` devices: ``None`` -> the uniform 1-D ring; a spec
    string -> ``core.topology.parse_topology_spec``; a topology object
    passes through. The knob is ADVISORY: a spec that does not apply to
    this axis (wrong node count, pods that do not divide it) degrades
    to the uniform ring instead of erroring, so one VARIANTS entry can
    span meshes whose data-axis sizes differ."""
    if topology is None:
        return MeshTopology(axis_size, 1)
    if isinstance(topology, MeshTopology):
        topo = topology
    else:
        try:
            topo = parse_topology_spec(str(topology), num_nodes=axis_size)
        except ValueError:
            return MeshTopology(axis_size, 1)
    if topo.num_nodes != axis_size:
        return MeshTopology(axis_size, 1)
    return topo


@functools.lru_cache(maxsize=None)
def auto_ring_chains(
    axis_size: int,
    size_bytes: int,
    scheduler: str = "tsp",
    algo: str = "rs_ag",
    wire_dtype: str | None = None,
    max_chains: int = 4,
    topo: MeshTopology | None = None,
) -> tuple[int, tuple[tuple[int, ...], ...]]:
    """Model-driven (K, sub_rings) for one DP reduction of
    ``size_bytes`` over ``axis_size`` devices — the ``num_chains=
    "auto"`` resolver. Delegates to the algo-aware
    ``core.simulator.choose_num_chains(collective="all_reduce")`` on
    the 1-D ring topology (the same snake construction as
    ``ring_order_for_axis``, so intra-ring hops stay 1 physical link),
    or on ``topo`` when given — a tiered topology makes the pod-aligned
    hierarchical schedule a candidate, and the cache keys on the frozen
    topology object itself, so a weighted graph can never alias the
    uniform ring of the same shape.
    ``wire_dtype`` prices the candidate schedules with the compressed
    frame bytes (int8 payload + f32 scale sideband), so the chosen K
    matches what actually goes over the wire.
    Cached: the choice is static per (shape, axis) and runs at trace
    time for every gradient leaf.
    """
    if axis_size <= 2:
        return 1, (tuple(range(axis_size)),)
    if topo is None:
        topo = MeshTopology(axis_size, 1)
    elif topo.num_nodes != axis_size:
        raise ValueError(
            f"topology has {topo.num_nodes} nodes for a ring of {axis_size}"
        )
    k, rings = sim.choose_num_chains(
        topo, 0, list(range(1, axis_size)), int(size_bytes),
        scheduler=scheduler, max_chains=max_chains,
        collective="all_reduce", algo=algo, wire_dtype=wire_dtype,
    )
    return k, tuple(tuple(r) for r in rings)


# ---------------------------------------------------------------------------
# Bucket assembly (backward-overlapped gradient reduction)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GradBucket:
    """One reduction bucket: the leaf indices it owns (positions in the
    flattened gradient tree, descending = reverse-topological dispatch
    order), their common dtype, and their total unpadded bytes."""

    indices: tuple[int, ...]
    dtype: str
    num_bytes: int


def assign_buckets(leaves: Sequence, bucket_bytes: int) -> tuple[GradBucket, ...]:
    """Partition gradient leaves into dtype-grouped, size-targeted
    buckets in REVERSE leaf order (reverse-topological ≈ backward
    production order: the last parameters' grads are produced first, so
    the first bucket closes — and its chain reduce can dispatch — while
    the rest of backward is still running).

    ``leaves`` need only ``.shape``/``.dtype`` (arrays or
    ``ShapeDtypeStruct``s). Invariants (property-tested in
    tests/test_bucketed_reduce.py): every leaf index appears in exactly
    one bucket; bucket bytes sum to the leaves' total; a bucket never
    mixes dtypes; a bucket exceeds ``bucket_bytes`` only when it holds a
    single oversized leaf (the one-leaf-slack rule — the target is
    respected by closing before adding, never by splitting a leaf).
    """
    target = int(bucket_bytes)
    if target <= 0:
        raise ValueError(f"bucket_bytes must be positive, got {bucket_bytes}")
    buckets: list[GradBucket] = []
    idxs: list[int] = []
    cur_dtype = ""
    cur_bytes = 0

    def close() -> None:
        nonlocal idxs, cur_dtype, cur_bytes
        if idxs:
            buckets.append(GradBucket(tuple(idxs), cur_dtype, cur_bytes))
        idxs, cur_dtype, cur_bytes = [], "", 0

    for i in reversed(range(len(leaves))):
        dt = jnp.dtype(leaves[i].dtype)
        nbytes = math.prod(leaves[i].shape) * dt.itemsize
        if idxs and (dt.name != cur_dtype or cur_bytes + nbytes > target):
            close()
        idxs.append(i)
        cur_dtype = dt.name
        cur_bytes += nbytes
    close()
    return tuple(buckets)


def all_reduce_shards(axis_size: int, num_chains: int, algo: str) -> int:
    """Chunk-address shard count of the planned all-reduce schedule —
    ``plan_all_reduce(...).addr_shards`` read off the plan itself.
    Symbolic addressing makes planning O(L) per step, so asking the
    planner is cheap; ``addr_shards`` depends only on the (L, K, algo)
    shape, never on ring identity, so canonical contiguous sub-rings
    stand in for the scheduled ones. K=1 uses device-id chunks
    (L shards, either algo); multi-ring rotation carries the whole
    payload as one slot; multi-ring rs_ag addresses by ring position
    (S = L/K shards)."""
    L, k = int(axis_size), max(1, int(num_chains))
    size = L // k
    orders = tuple(
        tuple(range(i * size, (i + 1) * size)) for i in range(k)
    )
    return prg.plan_all_reduce(L, orders, algo=algo).addr_shards


def bucket_shard_layout(
    num_elems: Sequence[int], shards: int
) -> tuple[tuple[int, ...], int]:
    """Chunk-aligned bucket layout: leaf i occupies ``shards`` rows of
    ``ceil(n_i / shards)`` elements (zero-padded), concatenated along
    the row axis. Aligning every leaf's chunk boundaries to the
    schedule's shard count keeps each element's ring fold order
    identical to the per-leaf reduce — that is what makes the bucketed
    path bit-identical at the exact wire. Returns ``(widths,
    total_elems)`` with ``total_elems = shards * sum(widths)`` (the
    payload size the wire and the cost model both see)."""
    widths = tuple(-(-int(n) // int(shards)) for n in num_elems)
    return widths, int(shards) * sum(widths)


def resolve_ring_chains(
    axis_size: int,
    nbytes: int,
    *,
    num_chains: int | str = 1,
    scheduler: str = "tsp",
    algo: str = "rs_ag",
    wire_dtype: str | None = None,
    max_chains: int = 4,
    topology: "str | MeshTopology | None" = None,
) -> tuple[int, tuple[tuple[int, ...], ...]]:
    """(K, sub_rings) for one DP reduction — the module-level twin of
    ``torrent_grad_reduce``'s per-reduction resolution, shared with the
    overlap/step-time model (``launch.roofline.modeled_train_overlap``)
    so modeled schedules stay in lockstep with what the executor runs
    (the EXACT modeled-vs-HLO byte match depends on it).

    ``topology`` (spec string or topology object, see
    :func:`_ring_topology`) only steers the ``num_chains="auto"``
    model: a tiered topology makes the hierarchical pod-aligned split a
    scored candidate. Explicit ``num_chains`` keeps the contiguous
    snake splits, which on a 1-D tiered ring are already pod-aligned."""
    if num_chains == "auto":
        k, rings = auto_ring_chains(
            axis_size, nbytes, scheduler, algo, wire_dtype, max_chains,
            _ring_topology(axis_size, topology),
        )
        if k > 1:
            return k, rings
    elif (
        isinstance(num_chains, int)
        and num_chains > 1
        and axis_size > num_chains
    ):
        return num_chains, tuple(
            sub_ring_orders(axis_size, num_chains, scheduler)
        )
    return 1, (ring_order_for_axis(axis_size, scheduler),)


def ef_residual_init(params: PyTree, dp_size: int) -> PyTree:
    """Zero error-feedback residual state for
    ``torrent_grad_reduce(error_feedback=True)``: one f32 residual per
    gradient leaf PER DP RANK, carried as a global ``(dp_size, *shape)``
    array whose leading dim is sharded over the DP axes
    (:func:`ef_residual_specs`)."""
    return jax.tree.map(
        lambda p: jnp.zeros((int(dp_size),) + tuple(p.shape), jnp.float32),
        params,
    )


def ef_residual_specs(mesh, params: PyTree) -> PyTree:
    """PartitionSpecs for :func:`ef_residual_init` state: dim 0 manual
    over the DP axes (each rank owns its own residual row)."""
    dp = _dp_axes(mesh)
    return jax.tree.map(lambda _: P(dp), params)


def torrent_grad_reduce(
    grad_fn: Callable[..., tuple[PyTree, PyTree]],
    mesh,
    batch_specs: PyTree,
    *,
    scheduler: str = "tsp",
    hierarchical: bool = True,
    num_chains: int | str = 1,
    algo: str = "rs_ag",
    wire_dtype: str | None = None,
    error_feedback: bool = False,
    bucket_bytes: int | None = None,
    topology: "str | MeshTopology | None" = None,
    param_specs: PyTree | None = None,
) -> Callable[..., tuple[PyTree, PyTree]]:
    """Wrap ``grad_fn(params, batch) -> (grads, metrics)`` (grads LOCAL
    to the batch shard) so grads come back chain-all-reduced over the DP
    axes. The gradients are computed in a subset shard_map (manual over
    the DP axes, model-axis sharding automatic) and reduced in a second
    shard_map manual over every axis.

    ``param_specs`` (the params' PartitionSpecs over the non-DP axes,
    e.g. ``model``) is the layout the reduce keeps each leaf in, so a
    device reduces only its own slice. Without it a leaf sharded over
    ``model`` is gathered whole before the reduce: twice the wire bytes,
    and a full-size temporary per leaf.

    ``num_chains > 1`` switches each DP reduction to the multi-chain
    schedule (K concurrent sub-rings; see module docstring). It must
    divide the group size being reduced. ``num_chains="auto"`` picks K
    per gradient leaf from the ``all_reduce_latency`` model for the
    chosen ``algo`` and ``wire_dtype`` (modeled bytes and cycles).

    ``wire_dtype="int8"`` runs the SAME schedules with each hop shipped
    quantized — it composes with ``num_chains``, ``algo`` and
    ``hierarchical`` (a 2-axis hierarchical reduction quantizes once
    per wire hop, never a second whole-payload pass on the outer ring).

    ``error_feedback=True`` (requires a lossy ``wire_dtype``) changes
    the wrapped signature to ``wrapped(params, batch, residual) ->
    (grads, metrics, new_residual)``: each DP rank adds its carried
    residual into the local gradient before the compressed reduction
    and banks the new local quantization error — the Seide-style local
    proxy for the distributed wire error (the per-hop errors inside the
    ring are not recoverable per rank; the first-quantization residual
    is the standard EF-SGD approximation). Residual state comes from
    :func:`ef_residual_init` / :func:`ef_residual_specs` and should be
    checkpointed alongside the optimizer state.

    ``bucket_bytes`` switches to the bucketed, backward-overlapped
    reduction (module docstring): leaves are grouped by
    :func:`assign_buckets` and each bucket reduces as one chunk-aligned
    chain all-reduce, dispatched in reverse-topological bucket order.
    ``num_chains="auto"`` then resolves K per BUCKET (from the bucket's
    total bytes) instead of per leaf; EF residuals stay per leaf.

    ``topology`` (a ``core.topology`` spec string such as
    ``"pods=4:interpod_bw=0.25"``, or a topology object) models the DP
    ring as a tiered link graph for the ``num_chains="auto"``
    selection, making the hierarchical pod-aligned schedule a scored
    candidate; see :func:`resolve_ring_chains`. Advisory: specs that
    do not fit the reduced axis degrade to the uniform ring."""
    if algo not in cw.ALL_REDUCE_ALGOS:
        raise ValueError(
            f"unknown algo {algo!r}; expected {cw.ALL_REDUCE_ALGOS}"
        )
    if num_chains != "auto" and not isinstance(num_chains, int):
        raise ValueError(f'num_chains must be an int or "auto", got {num_chains!r}')
    wire_dtype = prg.normalize_wire_dtype(wire_dtype)
    if error_feedback and wire_dtype is None:
        raise ValueError(
            "error_feedback=True requires a lossy wire_dtype "
            '(e.g. wire_dtype="int8"): with an exact wire there is no '
            "quantization residual to feed back"
        )
    if bucket_bytes is not None and int(bucket_bytes) <= 0:
        raise ValueError(f"bucket_bytes must be positive, got {bucket_bytes}")
    dp = _dp_axes(mesh)

    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]

    def _axis_len(axis) -> int:
        size = 1
        for a in (axis if isinstance(axis, tuple) else (axis,)):
            size *= mesh.shape[a]
        return size

    def _rings_for(size: int, nbytes: int):
        """(K, sub_rings) for one axis reduction of ``nbytes``."""
        return resolve_ring_chains(
            size, nbytes, num_chains=num_chains, scheduler=scheduler,
            algo=algo, wire_dtype=wire_dtype, topology=topology,
        )

    def _ar(x, axis, k, rings):
        if k > 1:
            return cw.multi_chain_all_reduce(
                x, axis, rings, algo=algo, wire_dtype=wire_dtype
            )
        return cw.chain_all_reduce(x, axis, rings[0], wire_dtype=wire_dtype)

    def _ar_stages():
        if hierarchical and len(dp) == 2:
            return [dp[1], dp[0]]  # within pod ("data"), then across pods
        return [dp if len(dp) > 1 else dp[0]]

    def reduce_one(g, r=None):
        flat = g.reshape(-1)
        new_r = None
        if r is not None:
            flat = flat.astype(jnp.float32) + r.reshape(-1)
            q, s = quantize(flat)
            new_r = (flat - dequantize(q, s)).reshape(g.shape)

        def ar(x, axis):
            k, rings = _rings_for(
                _axis_len(axis), x.size * x.dtype.itemsize
            )
            return _ar(x, axis, k, rings)

        with jax.named_scope("chain"):
            if hierarchical and len(dp) == 2:
                flat = ar(flat, dp[1])  # within pod ("data")
                flat = ar(flat, dp[0])  # across pods
            else:
                flat = ar(flat, dp if len(dp) > 1 else dp[0])
        # shards hold grads of their LOCAL mean loss; the chain sums them,
        # so divide by the DP group size to recover the global-mean grad
        # (drop-in parity with the "xla" backend).
        reduced = (flat / dp_size).reshape(g.shape).astype(g.dtype)
        return reduced if r is None else (reduced, new_r)

    def _reduce_bucket_flats(flats):
        """One bucket = ONE chain all-reduce: chunk-align each flat leaf
        to the schedule's shard count, concatenate along the row axis,
        reduce the whole payload, slice the leaves back out. Returns the
        per-leaf reduced flats (un-averaged)."""
        nbytes = sum(f.size * f.dtype.itemsize for f in flats)
        stages = _ar_stages()
        plans = [
            (axis,) + _rings_for(_axis_len(axis), nbytes) for axis in stages
        ]
        shards = all_reduce_shards(_axis_len(stages[0]), plans[0][1], algo)
        widths, _ = bucket_shard_layout([f.size for f in flats], shards)
        padded = [
            jnp.pad(f, (0, shards * m - f.size)).reshape(shards, m)
            for f, m in zip(flats, widths)
        ]
        payload = (
            padded[0] if len(padded) == 1 else jnp.concatenate(padded, axis=1)
        ).reshape(-1)
        with jax.named_scope("chain"):
            for axis, k, rings in plans:
                payload = _ar(payload, axis, k, rings)
        mat = payload.reshape(shards, -1)
        outs, off = [], 0
        for f, m in zip(flats, widths):
            outs.append(mat[:, off : off + m].reshape(-1)[: f.size])
            off += m
        return outs

    def reduce_bucketed(grads, res=None):
        """Bucketed tree reduce: buckets dispatch in reverse-topological
        order (assign_buckets walks leaves last-to-first), so the
        schedule XLA sees issues each bucket's collective as soon as its
        leaves' grads exist — the dispatch-order half of the overlap
        story. Returns grads, or (grads, new_residuals) under EF."""
        leaves, treedef = jax.tree.flatten(grads)
        res_leaves = (
            jax.tree.flatten(res)[0] if res is not None else [None] * len(leaves)
        )
        out = [None] * len(leaves)
        new_res = [None] * len(leaves)
        for b in assign_buckets(leaves, bucket_bytes):
            flats = []
            for i in b.indices:
                g, r = leaves[i], res_leaves[i]
                flat = g.reshape(-1)
                if r is not None:
                    flat = flat.astype(jnp.float32) + r.reshape(-1)
                    q, s = quantize(flat)
                    new_res[i] = (flat - dequantize(q, s)).reshape(g.shape)
                flats.append(flat)
            for i, rf in zip(b.indices, _reduce_bucket_flats(flats)):
                g = leaves[i]
                out[i] = (rf / dp_size).reshape(g.shape).astype(g.dtype)
        grads_out = jax.tree.unflatten(treedef, out)
        if res is None:
            return grads_out
        return grads_out, jax.tree.unflatten(treedef, new_res)

    def reduce_tree(grads, res=None):
        """Reduce the gradient tree (and, under EF, bank the residuals):
        returns grads, or (grads, new_residuals)."""
        if bucket_bytes is not None:
            return reduce_bucketed(grads, res)
        if res is None:
            return jax.tree.map(reduce_one, grads)
        pairs = jax.tree.map(reduce_one, grads, res)
        is_pair = lambda x: isinstance(x, tuple)  # noqa: E731
        return (
            jax.tree.map(lambda pair: pair[0], pairs, is_leaf=is_pair),
            jax.tree.map(lambda pair: pair[1], pairs, is_leaf=is_pair),
        )

    dp_spec = dp if len(dp) > 1 else dp[0]
    is_spec = lambda x: isinstance(x, P)  # noqa: E731

    def _avg_metrics(metrics):
        # metrics are per-shard means -> average over the DP group
        return jax.tree.map(
            lambda m: jax.lax.psum(m, dp) / dp_size, metrics
        )

    def local_grads(params, batch):
        """Each DP rank's gradients, stacked along a leading DP dim."""

        def inner(params, batch):
            grads, metrics = grad_fn(params, batch)
            return jax.tree.map(lambda g: g[None], grads), _avg_metrics(metrics)

        return jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(), params), batch_specs),
            out_specs=(jax.tree.map(lambda _: P(dp_spec), params), P()),
            axis_names=set(dp),
            check_vma=False,
        )(params, batch)

    def reduce_rows(rows, res_rows=None):
        """Reduce the stacked ``(dp, *shape)`` gradients (and bank the
        EF residual rows) in a shard_map manual over every mesh axis.
        Each leaf keeps its ``param_specs`` layout over the non-DP
        axes, so every device reduces only its own slice."""
        specs = param_specs
        if specs is None:
            specs = jax.tree.map(lambda g: P(*([None] * (g.ndim - 1))), rows)
        row_specs = jax.tree.map(lambda s: P(dp_spec, *s), specs, is_leaf=is_spec)
        first = lambda t: jax.tree.map(lambda x: x[0], t)  # noqa: E731

        if res_rows is None:
            return jax.shard_map(
                lambda g: reduce_tree(first(g)),
                mesh=mesh, in_specs=(row_specs,), out_specs=specs,
                check_vma=False,
            )(rows)

        def body(g, r):
            grads, new_r = reduce_tree(first(g), first(r))
            return grads, jax.tree.map(lambda x: x[None], new_r)

        return jax.shard_map(
            body, mesh=mesh, in_specs=(row_specs, row_specs),
            out_specs=(specs, row_specs), check_vma=False,
        )(rows, res_rows)

    if not error_feedback:

        def wrapped(params, batch):
            rows, metrics = local_grads(params, batch)
            with jax.named_scope("grad_reduce"):
                return reduce_rows(rows), metrics

        return wrapped

    def wrapped_ef(params, batch, residual):
        rows, metrics = local_grads(params, batch)
        with jax.named_scope("grad_reduce"):
            grads, new_res = reduce_rows(rows, residual)
        return grads, metrics, new_res

    return wrapped_ef
