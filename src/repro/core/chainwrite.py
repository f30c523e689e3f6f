"""Chainwrite collectives — the paper's P2MP mechanism on TPU ICI.

The paper moves data replication out of the NoC routers and into the DMA
endpoints: data traverses a *scheduled chain* of destinations, each hop
an ordinary P2P transfer. On TPU the only true P2P primitive is
``jax.lax.ppermute`` (collective-permute), so every Chainwrite pattern
maps to chains of ppermutes inside ``shard_map``.

Since the ChainProgram refactor there is exactly ONE interpreter here:
:func:`execute_program` runs any :class:`~repro.core.program.
ChainProgram` step by step (one fused ppermute per step; a pipeline
head's same-step fan-out gets per-edge permutes because XLA requires
unique permute sources). Every public collective is a thin
``plan_* -> execute_program`` wrapper whose signature is unchanged from
the pre-IR versions:

* :func:`chain_broadcast` / :func:`multi_chain_broadcast` /
  :func:`degraded_multi_chain_broadcast` — P2MP multicast down one or
  K link-disjoint sub-chains, with optional frame pipelining
  (``num_frames``: payload frames stream through the chains
  store-and-forward, F + L - 2 slots instead of F·L — the paper's
  §III-C stream duplicator).
* :func:`chain_all_gather` / :func:`chain_reduce_scatter` /
  :func:`chain_all_reduce` / :func:`chain_all_to_all` — ring
  collectives over an explicitly *scheduled* ring order.
* :func:`multi_chain_all_reduce` — K disjoint equal sub-rings;
  ``algo="rs_ag"`` (fused per-ring reduce-scatter → cross-ring shard
  rotation → fused per-ring all-gather, ≈ (2·(S-1)+(K-1))/S payloads
  of wire per device) or ``algo="rotation"`` (S+K-2 full-payload
  steps).
* :func:`multi_chain_reduce_scatter` / :func:`multi_chain_all_gather` /
  :func:`multi_chain_all_to_all` — the K-ring generalizations that
  fall straight out of the planner (same total wire as the single
  ring, ring-local/position-paired hops).

All functions must be called inside ``shard_map`` with a manual axis.
``order``/``orders``/``chains`` are static tuples of device indices
along the axis; non-members of a partial chain participate in the SPMD
program but receive (and keep) zeros — the paper's "no change to the
interconnect" property: nothing outside the chain is touched.

The numpy twin of :func:`execute_program` is
:func:`repro.core.chainwrite_ref.interpret_program`; both interpret the
same program, so they agree BIT-exactly (the IR fixes the fold order).
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax

from . import program as prg
from .program import ALL_REDUCE_ALGOS, ChainProgram, validate_ring_partition
from .scheduling import FailureSpec, normalize_failed

# Wire-dtype numerics (safe: repro.runtime never imports this module).
from repro.runtime.compression import dequantize, quantize

Axis = str | tuple[str, ...]

# When True, ring/chain scans are fully unrolled. The dry-run sets this
# so every ppermute appears as its own HLO op and the §Roofline
# collective-bytes parser counts true wire traffic (a rolled scan's
# body is counted once regardless of trip count). The stepped program
# interpreter is always unrolled (its addressing tables are per-step
# static); only the frame-pipelined broadcast scan consults this.
_STATIC_UNROLL = False


def set_static_unroll(value: bool) -> None:
    global _STATIC_UNROLL
    _STATIC_UNROLL = bool(value)


def _scan(body, carry, xs):
    length = int(xs.shape[0]) if hasattr(xs, "shape") else len(xs)
    return lax.scan(
        body, carry, xs, unroll=length if _STATIC_UNROLL else 1
    )


def _axis_index(axis_name: Axis) -> jax.Array:
    """Linearized index over one axis name or a tuple of axis names."""
    if isinstance(axis_name, (tuple, list)):
        idx = lax.axis_index(axis_name[0])
        for name in axis_name[1:]:
            idx = idx * lax.axis_size(name) + lax.axis_index(name)
        return idx
    return lax.axis_index(axis_name)


def chain_edges(order: Sequence[int], *, wrap: bool = False) -> list[tuple[int, int]]:
    """Directed ppermute pairs for a chain (optionally closed ring)."""
    edges = [(int(a), int(b)) for a, b in zip(order, order[1:])]
    if wrap and len(order) > 1:
        edges.append((int(order[-1]), int(order[0])))
    return edges


def _ppermute(x: jax.Array, axis_name: Axis, perm: list[tuple[int, int]]) -> jax.Array:
    return lax.ppermute(x, axis_name, perm)


# ---------------------------------------------------------------------------
# The generic SPMD program executor
# ---------------------------------------------------------------------------


def _fanout(
    buf: jax.Array, axis_name: Axis, edges: Sequence[tuple[int, int]], idx
) -> jax.Array:
    """One program step's hop. Unique-source edges fuse into a single
    ppermute; each repeated source (the pipeline head's same-step
    fan-out) costs its own permute (XLA's unique-source rule) — the
    split :meth:`Step.num_permutes` accounts for."""
    if not edges:
        return jnp.zeros_like(buf)
    seen: set[int] = set()
    fused: list[tuple[int, int]] = []
    extra: list[tuple[int, int]] = []
    for e in edges:
        if e[0] in seen:
            extra.append(e)
        else:
            seen.add(e[0])
            fused.append(e)
    new = _ppermute(buf, axis_name, fused)
    for e in extra:
        r = _ppermute(buf, axis_name, [e])
        new = jnp.where(idx == e[1], r, new)
    return new


class _AddrCtx:
    """Trace-time symbolic address evaluation: this device's ring
    position / ring index as traced scalars, built lazily on the first
    symbolic table. A *canonical* partition (``groups[j] == range(j·S,
    (j+1)·S)`` covering the axis) derives them arithmetically from
    ``idx`` — ZERO ring-length-sized HLO constants; an irregular
    partition gathers from (L,)-sized constant maps (still O(L) where
    dense tables were O(L²))."""

    def __init__(self, prog: ChainProgram, idx) -> None:
        self._prog = prog
        self._idx = idx
        self._ready = False

    def _build(self) -> None:
        if self._ready:
            return
        ctx = self._prog.ring_ctx()
        self.K, self.S = ctx.K, ctx.S
        idx = self._idx
        if ctx.canonical:
            self._pos = idx % ctx.S
            self._ring = idx // ctx.S
            self._mask = None
            self._flat = None
        else:
            L = self._prog.num_devices
            pos_np = [0] * L
            ring_np = [0] * L
            mem_np = [False] * L
            for d, p in ctx.pos.items():
                pos_np[d], mem_np[d] = p, True
            for d, r in ctx.ring_of.items():
                ring_np[d] = r
            self._pos = jnp.asarray(pos_np)[idx]
            self._ring = jnp.asarray(ring_np)[idx]
            self._mask = jnp.asarray(mem_np)[idx]
            self._flat = jnp.asarray(
                [ctx.orders[r][q] for r in range(ctx.K) for q in range(ctx.S)]
            )
        self._ready = True

    def dims(self) -> tuple[int, int]:
        self._build()
        return self.K, self.S

    def pos(self):
        self._build()
        return self._pos

    def ring(self):
        self._build()
        return self._ring

    def member(self, flat_idx):
        """Device id of ring ``flat_idx // S``, position ``% S``."""
        self._build()
        if self._flat is None:
            return flat_idx
        # A one-hot select, not a gather: XLA:CPU turns a small gather
        # inside a scan body into a fused dynamic-slice whose generated
        # name can collide with the scan's own slice and fail to codegen.
        hit = flat_idx[..., None] == jnp.arange(self._flat.shape[0])
        return jnp.sum(jnp.where(hit, self._flat, 0), axis=-1)

    def mask_row(self, row):
        """-1 out the row on devices outside every ring group."""
        self._build()
        if self._mask is None:
            return row
        return jnp.where(self._mask, row, -1)


def _row_ids(table, actx, idx):
    """Traced (width,) int32 slot/shard addresses of this device's row
    (-1 = none) — dense tables gather from the embedded constant, the
    symbolic forms compute from ``idx`` and the coefficients."""
    if isinstance(table, tuple):
        return jnp.asarray(table)[idx]
    if isinstance(table, prg.AtDevices):
        none = jnp.full((table.width,), -1, jnp.int32)
        if not table.devices:
            return none
        hit = jnp.any(jnp.asarray(sorted(set(table.devices))) == idx)
        return jnp.where(
            hit, jnp.full((table.width,), table.value, jnp.int32), none
        )
    if isinstance(table, prg.Diag):
        inner = _row_ids(table.inner, actx, idx)[0]
        return jnp.where(jnp.arange(table.width) == idx, inner, -1).astype(
            jnp.int32
        )
    if isinstance(table, prg.Affine):
        row = (
            table.a * actx.pos() + table.c * actx.ring()
            + table.e * jnp.arange(table.width) + table.b
        ) % table.m
        return actx.mask_row(row.astype(jnp.int32))
    if isinstance(table, prg.MemberLookup):
        K, S = actx.dims()
        cols = jnp.arange(table.width)
        r = (table.ar * actx.ring() + table.er * cols + table.br) % K
        q = (table.ap * actx.pos() + table.ep * cols + table.bp) % S
        return actx.mask_row(actx.member(r * S + q).astype(jnp.int32))
    raise TypeError(f"unknown table type {type(table).__name__}")


def _rows_from(table, idx, source, keep=None, actx=None):
    """Per-device row select: ``result[j] = source[table[self][j]]``,
    with ``-1`` giving ``keep[j]`` (same-width) or zeros."""
    t = _row_ids(table, actx, idx)  # (width,)
    safe = jnp.clip(t, 0, source.shape[0] - 1)
    rows = source[safe]
    mask = (t >= 0).reshape((-1,) + (1,) * (source.ndim - 1))
    if keep is not None and keep.shape[0] == prg.table_width(table):
        return jnp.where(mask, rows, keep)
    return jnp.where(mask, rows, jnp.zeros_like(rows))


def _hop(buf, axis_name, edges, idx, wire):
    """Ship ``buf`` over one step's edges. ``wire="int8"`` quantizes
    before the hop and dequantizes after: the int8 frame and its f32
    scale travel as two ppermutes (the scale is the 4-byte sideband
    :meth:`ChainProgram.step_bytes` prices); non-target devices receive
    zeros for both, so dequantize reproduces the uncompressed zeros."""
    if wire == "int8":
        q, scale = quantize(buf)
        q = _fanout(q, axis_name, edges, idx)
        scale = _fanout(scale, axis_name, edges, idx)
        # No contraction barrier needed: quantize() truncates the scale
        # mantissa so q * scale is exact in f32, which makes an FMA of
        # dequantize-mul + accumulate-add bitwise equal to separate
        # rounding — the oracle replay stays exact either way.
        return dequantize(q, scale)
    return _fanout(buf, axis_name, edges, idx)


def _one_step(buf, out, shards, axis_name, idx, step, wire=None, actx=None):
    """One program step (the machine model of :mod:`repro.core.program`
    verbatim): load -> hop -> combine -> write."""
    if step.load is not None:
        buf = _rows_from(step.load, idx, out, keep=buf, actx=actx)
    buf = _hop(buf, axis_name, step.edges, idx, wire)
    if step.combine == prg.ADD:
        src = shards if step.add_from == "input" else out
        buf = buf + _rows_from(step.add_src, idx, src, actx=actx)
    if step.write is not None:
        out = _write_step(buf, out, step.write, step.width,
                          step.write_op, actx, idx)
    return buf, out


def _write_step(buf, out, table, width, write_op, actx, idx):
    """Apply one step's write table: dense tables keep the historical
    sparse/width-loop paths; symbolic tables write through ONE indexed
    update (Diag / width-1) or one vector scatter (full-width)."""
    if isinstance(table, tuple):
        sparse = _sparse_write(table)
        if sparse is not None:
            rows_tbl, slots_tbl = sparse
            return _write_one(
                buf, out, jnp.asarray(rows_tbl)[idx],
                jnp.asarray(slots_tbl)[idx], write_op,
            )
        t = jnp.asarray(table)[idx]  # (width,)
        return _write_dense(buf, out, t, width, write_op)
    if isinstance(table, prg.Diag):
        slot = _row_ids(table.inner, actx, idx)[0]
        return _write_one(buf, out, idx, slot, write_op)
    rows = _row_ids(table, actx, idx)
    if prg.table_width(table) == 1:
        return _write_one(buf, out, jnp.int32(0), rows[0], write_op)
    return _write_rows(buf, out, rows, write_op)


def _sparse_write(table):
    """When every device writes at most ONE buf row per step (e.g. the
    all_to_all peel: width L, one live slot), the write collapses to a
    single indexed update instead of a width-long guarded loop —
    keeping HLO size O(L) rather than O(L^2) for the chunk train.
    Returns per-device (buf_row, out_slot) tables, or None when some
    device writes multiple slots."""
    rows: list[int] = []
    slots: list[int] = []
    for drow in table:
        live = [(j, s) for j, s in enumerate(drow) if s >= 0]
        if len(live) > 1:
            return None
        j, s = live[0] if live else (0, -1)
        rows.append(j)
        slots.append(s)
    return tuple(rows), tuple(slots)


def _write_one(buf, out, row_t, slot_t, write_op):
    """out[slot] (op)= buf[row] for this device; slot < 0 is a no-op."""
    valid = slot_t >= 0
    row_c = jnp.clip(row_t, 0, buf.shape[0] - 1)
    val = lax.dynamic_index_in_dim(buf, row_c, 0, keepdims=False)
    slot_c = jnp.clip(slot_t, 0, out.shape[0] - 1)
    cur = lax.dynamic_index_in_dim(out, slot_c, 0, keepdims=False)
    new = val if write_op == prg.COPY else cur + val
    new = jnp.where(valid, new, cur)
    return lax.dynamic_update_index_in_dim(out, new, slot_c, 0)


def _write_rows(buf, out, slots, write_op):
    """Vectorized full-width write ``out[slots[j]] (op)= buf[j]`` for a
    traced slot row: live slots are distinct (an IR invariant), so this
    is one scatter; -1 rows land on a dummy slot that is dropped."""
    dummy = jnp.zeros((1,) + out.shape[1:], out.dtype)
    ext = jnp.concatenate([out, dummy], axis=0)
    tgt = jnp.where(slots >= 0, slots, out.shape[0])
    if write_op == prg.COPY:
        ext = ext.at[tgt].set(buf)
    else:
        ext = ext.at[tgt].add(buf)
    return ext[:-1]


def _write_dense(buf, out, slots, width, write_op):
    for j in range(width):
        slot = slots[j]
        valid = slot >= 0
        slot_c = jnp.clip(slot, 0, out.shape[0] - 1)
        cur = lax.dynamic_index_in_dim(out, slot_c, 0, keepdims=False)
        new = buf[j] if write_op == prg.COPY else cur + buf[j]
        new = jnp.where(valid, new, cur)
        out = lax.dynamic_update_index_in_dim(out, new, slot_c, 0)
    return out


def _stack_key(table):
    """Scan-compatibility key of an addressing table: steps stack into
    one ``lax.scan`` when only their per-step *offsets* differ (dense
    rows ride in the xs; symbolic offsets — Affine ``b``, MemberLookup
    ``br``/``bp`` — become scalar xs decoded in the body)."""
    if table is None:
        return None
    if isinstance(table, tuple):
        return "dense"
    if isinstance(table, prg.Affine):
        return ("affine", table.width, table.a, table.c, table.e, table.m)
    if isinstance(table, prg.MemberLookup):
        return ("member", table.width, table.ar, table.er, table.ap, table.ep)
    if isinstance(table, prg.Diag):
        return ("diag", table.width, _stack_key(table.inner))
    return ("at", table)  # AtDevices: only identical tables stack


def _uniform_runs(steps, wires=None):
    """Group consecutive steps that share edges/width/combine/write
    structure AND wire dtype (differing only in their addressing
    offsets/rows) so the executor can roll each group into one
    ``lax.scan`` — keeping the compiled HLO ring-length-independent as
    the pre-IR collectives were. Steps with a ``load`` (phase
    boundaries) run standalone. Returns ``[(wire, [steps...]), ...]``."""
    if wires is None:
        wires = [None] * len(steps)
    runs: list[tuple] = []
    key_prev = None
    for s, w in zip(steps, wires):
        key = (s.edges, s.width, s.combine, s.add_from,
               _stack_key(s.add_src), _stack_key(s.write), s.write_op, w)
        if s.load is None and runs and key_prev == key:
            runs[-1][1].append(s)
        else:
            runs.append((w, [s]))
        key_prev = key if s.load is None else None
    return runs


def _offset_xs(vals):
    """Per-step symbolic offsets as scan xs WITHOUT an O(T) constant:
    a constant sequence broadcasts a scalar, an arithmetic progression
    rides an iota; anything else (no planner emits one) falls back to
    the materialized vector."""
    T = len(vals)
    v0 = vals[0]
    if all(v == v0 for v in vals):
        return jnp.full((T,), v0, jnp.int32)
    db = vals[1] - v0
    if all(vals[i] == v0 + i * db for i in range(T)):
        return (v0 + db * jnp.arange(T)).astype(jnp.int32)
    return jnp.asarray(vals, jnp.int32)


def _stacked_rows(tables, actx, idx):
    """(xs, row_fn) for a uniform run's same-structure tables: the scan
    body calls ``row_fn(x_t)`` to recover step t's (width,) address
    row. Dense tables pre-gather this device's rows into (T, width) xs;
    symbolic tables ship only their per-step offsets."""
    t0 = tables[0]
    if isinstance(t0, tuple):
        return jnp.asarray(tables)[:, idx], lambda x: x
    if isinstance(t0, prg.Affine):
        xs = _offset_xs([t.b for t in tables])

        def fn(x, t0=t0):
            row = (
                t0.a * actx.pos() + t0.c * actx.ring()
                + t0.e * jnp.arange(t0.width) + x
            ) % t0.m
            return actx.mask_row(row.astype(jnp.int32))

        return xs, fn
    if isinstance(t0, prg.MemberLookup):
        K, S = actx.dims()
        xs = jnp.stack(
            [_offset_xs([t.br for t in tables]),
             _offset_xs([t.bp for t in tables])], axis=1,
        )
        cols = jnp.arange(t0.width)

        def fn(x, t0=t0):
            r = (t0.ar * actx.ring() + t0.er * cols + x[0]) % K
            q = (t0.ap * actx.pos() + t0.ep * cols + x[1]) % S
            return actx.mask_row(actx.member(r * S + q).astype(jnp.int32))

        return xs, fn
    if isinstance(t0, prg.AtDevices):
        # Identical across the run (the uniform-run key pins the whole
        # table): evaluate once, constant through the scan.
        row = _row_ids(t0, actx, idx)
        return jnp.zeros((len(tables),), jnp.int32), lambda x: row
    raise TypeError(f"unstackable table type {type(t0).__name__}")


def _scan_run(buf, out, shards, axis_name, idx, run, wire=None, actx=None):
    """Rolled execution of a uniform step run: per-step addressing
    stacks into the scan's ``xs`` — dense tables as pre-gathered rows,
    symbolic tables as scalar offsets decoded in the body — so the
    compiled HLO (and on canonical rings, its constant footprint) is
    independent of the run length."""
    s0 = run[0]
    T = len(run)
    zeros_T = jnp.zeros((T,), jnp.int32)

    add_fn = None
    add_xs = zeros_T
    if s0.add_src is not None:
        add_xs, add_fn = _stacked_rows([s.add_src for s in run], actx, idx)

    # Write modes: "one" (single indexed update: sparse dense tables,
    # Diag, width-1 symbolic), "dense" (width loop), "rows" (vector
    # scatter), or None.
    write_mode = None
    write_xs = zeros_T
    write_fn = None
    if s0.write is not None:
        w0 = s0.write
        if isinstance(w0, tuple):
            sparse_all = [_sparse_write(s.write) for s in run]
            if all(sp is not None for sp in sparse_all):
                write_mode = "one"
                rows_xs = jnp.asarray([sp[0] for sp in sparse_all])[:, idx]
                slots_xs = jnp.asarray([sp[1] for sp in sparse_all])[:, idx]
                write_xs = jnp.stack([rows_xs, slots_xs], axis=1)
                write_fn = lambda x: (x[0], x[1])  # noqa: E731
            else:
                write_mode = "dense"
                write_xs = jnp.asarray([s.write for s in run])[:, idx]
        elif isinstance(w0, prg.Diag):
            write_mode = "one"
            write_xs, inner_fn = _stacked_rows(
                [s.write.inner for s in run], actx, idx
            )
            write_fn = lambda x: (idx, inner_fn(x)[0])  # noqa: E731
        else:
            xs, fn = _stacked_rows([s.write for s in run], actx, idx)
            write_xs = xs
            if prg.table_width(w0) == 1:
                write_mode = "one"
                write_fn = lambda x: (jnp.int32(0), fn(x)[0])  # noqa: E731
            else:
                write_mode = "rows"
                write_fn = fn

    def body(carry, xs):
        buf, out = carry
        add_t, write_t = xs
        buf = _hop(buf, axis_name, s0.edges, idx, wire)
        if s0.combine == prg.ADD:
            src = shards if s0.add_from == "input" else out
            row = add_fn(add_t)
            safe = jnp.clip(row, 0, src.shape[0] - 1)
            rows = src[safe]
            mask = (row >= 0).reshape((-1,) + (1,) * (src.ndim - 1))
            buf = buf + jnp.where(mask, rows, jnp.zeros_like(rows))
        if write_mode == "one":
            row_t, slot_t = write_fn(write_t)
            out = _write_one(buf, out, row_t, slot_t, s0.write_op)
        elif write_mode == "dense":
            out = _write_dense(buf, out, write_t, s0.width, s0.write_op)
        elif write_mode == "rows":
            out = _write_rows(buf, out, write_fn(write_t), s0.write_op)
        return (buf, out), None

    (buf, out), _ = lax.scan(body, (buf, out), (add_xs, write_xs))
    return buf, out


def _run_stepped(shards: jax.Array, axis_name: Axis, prog: ChainProgram) -> jax.Array:
    """Interpret a program over pre-blocked input ``shards``
    (``(addr_shards, m, ...)`` per device); returns the
    ``(out_slots, m, ...)`` output slots.

    Uniform step runs (same edges/structure, different tables — the
    RS/AG/rotation/cross phases of the ring collectives) execute as one
    rolled ``lax.scan`` each, so compiled HLO size stays independent of
    the ring length; ``set_static_unroll(True)`` (the dry-run's
    HLO-byte-parsing mode) unrolls every step into its own ppermute.
    """
    idx = _axis_index(axis_name)
    wires = [prog.step_wire_dtype(s) for s in prog.steps]
    orig_dtype = shards.dtype
    if any(w is not None for w in wires):
        # The compressed wire accumulates in f32 (quantize/dequantize
        # are f32 numerics); integer payloads cannot round-trip.
        if not jnp.issubdtype(shards.dtype, jnp.floating):
            raise ValueError(
                f"wire_dtype='int8' requires a floating payload, "
                f"got {shards.dtype}"
            )
        shards = shards.astype(jnp.float32)
    actx = _AddrCtx(prog, idx)
    buf = _rows_from(prog.buf_init, idx, shards, actx=actx)
    out = _rows_from(prog.out_init, idx, shards, actx=actx)
    for wire, run in _uniform_runs(prog.steps, wires):
        if len(run) == 1 or _STATIC_UNROLL:
            for step in run:
                buf, out = _one_step(
                    buf, out, shards, axis_name, idx, step, wire, actx
                )
        else:
            buf, out = _scan_run(
                buf, out, shards, axis_name, idx, run, wire, actx
            )
    return out.astype(orig_dtype)


def _execute_pipeline(
    x: jax.Array, axis_name: Axis, prog: ChainProgram, num_frames: int
) -> jax.Array:
    """Broadcast-kind programs: the stepped interpreter for a single
    frame, or the store-and-forward frame-pipelined scan (all chains'
    edges applied every slot; one scan step per frame-hop slot,
    F + L - 2 total)."""
    if num_frames <= 1 or not prog.steps:
        return _run_stepped(x[None], axis_name, prog)[0]

    if x.shape[0] % num_frames != 0:
        raise ValueError(
            f"leading dim {x.shape[0]} not divisible by num_frames={num_frames}"
        )
    head = int(prog.head)
    idx = _axis_index(axis_name)
    is_head = idx == head
    x = jnp.where(is_head, x, jnp.zeros_like(x))
    frames = x.reshape((num_frames, x.shape[0] // num_frames) + x.shape[1:])

    # Static per-device chain position: 0 = head, p >= 1 = receiver of
    # step p-1 (its chain depth), max_len = non-member (out of range).
    max_len = len(prog.steps) + 1
    pos_np = [max_len] * prog.num_devices
    pos_np[head] = 0
    for t, step in enumerate(prog.steps):
        for _, dst in step.edges:
            pos_np[dst] = t + 1
    pos = jnp.asarray(pos_np)[idx]
    member = pos < max_len
    all_edges = [e for step in prog.steps for e in step.edges]
    T = num_frames + max_len - 2  # scan steps (longest chain's fill)

    def step(carry, t):
        buf, out = carry
        t_clamped = jnp.minimum(t, num_frames - 1)
        inject = lax.dynamic_index_in_dim(frames, t_clamped, axis=0, keepdims=False)
        buf = jnp.where(is_head & (t < num_frames), inject, buf)
        buf = _fanout(buf, axis_name, all_edges, idx)
        # After hop t, the member at chain position p holds frame t-(p-1).
        fidx = t - (pos - 1)
        valid = member & (pos > 0) & (fidx >= 0) & (fidx < num_frames)
        fidx_c = jnp.clip(fidx, 0, num_frames - 1)
        current = lax.dynamic_index_in_dim(out, fidx_c, axis=0, keepdims=False)
        new = jnp.where(valid, buf, current)
        out = lax.dynamic_update_index_in_dim(out, new, fidx_c, axis=0)
        return (buf, out), None

    buf0 = jnp.zeros_like(frames[0])
    out0 = jnp.where(is_head, frames, jnp.zeros_like(frames))
    (_, out), _ = _scan(step, (buf0, out0), jnp.arange(T))
    return out.reshape(x.shape)


def execute_program(
    x: jax.Array,
    axis_name: Axis,
    prog: ChainProgram,
    *,
    num_frames: int = 1,
    tiled: bool = False,
) -> jax.Array:
    """Run a :class:`ChainProgram` inside ``shard_map``.

    Handles the per-collective input blocking / output assembly around
    the one generic interpreter: ``broadcast`` takes/returns the whole
    payload (``num_frames`` pipelines it); ``all_gather`` stacks (or,
    ``tiled``, concatenates) device-id-indexed shards;
    ``reduce_scatter``/``all_to_all`` take ``(L, ...)`` chunk trains;
    ``all_reduce`` zero-pads the leading dim to the program's shard
    count and unpads on the way out.
    """
    L = prog.num_devices
    if _axis_size(axis_name) != L:
        raise ValueError(
            f"program planned for {L} devices, axis has {_axis_size(axis_name)}"
        )
    c = prog.collective
    if c == "broadcast":
        return _execute_pipeline(x, axis_name, prog, num_frames)
    if c == "all_gather":
        out = _run_stepped(x[None], axis_name, prog)
        if tiled:
            out = out.reshape((L * x.shape[0],) + x.shape[1:])
        return out
    if c in ("reduce_scatter", "all_to_all"):
        if x.shape[0] != L:
            raise ValueError(f"leading dim {x.shape[0]} != axis size {L}")
        out = _run_stepped(x, axis_name, prog)
        return out[0] if c == "reduce_scatter" else out
    if c == "all_reduce":
        S = prog.addr_shards
        lead = x.shape[0]
        pad = (-lead) % S
        xp = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1)) if pad else x
        m = xp.shape[0] // S
        # A flat shard is folded into 128-lane rows: the TPU compiler
        # spends ~10 s per 32M elements slicing rows of an (S, m) array,
        # and a fraction of a second on (S, m // 128, 128). Shards are
        # not padded to 128 lanes: that would put bytes on the wire
        # that program_wire_bytes (pinned exactly to the HLO) does not
        # count.
        row = (m // 128, 128) if x.ndim == 1 and m % 128 == 0 else (m,) + x.shape[1:]
        out = _run_stepped(xp.reshape((S,) + row), axis_name, prog)
        full = out.reshape((-1,) + x.shape[1:])
        return full[:lead] if pad else full
    raise ValueError(f"unknown collective {c!r}")


# ---------------------------------------------------------------------------
# P2MP broadcast (the paper's core operation)
# ---------------------------------------------------------------------------


def chain_broadcast(
    x: jax.Array,
    axis_name: Axis,
    order: Sequence[int],
    *,
    num_frames: int = 1,
) -> jax.Array:
    """Multicast ``x`` from device ``order[0]`` to every device in
    ``order`` by store-and-forward chaining (paper §III-A/§III-C).

    ``x`` must be materialized on the chain head (other devices pass a
    same-shaped array whose value is ignored). Devices on the axis that
    are not in ``order`` return zeros. With ``num_frames > 1`` the
    payload's leading dimension is sliced into frames that pipeline
    through the chain — one scan step per frame-hop slot, F + L - 2
    steps total.
    """
    order = tuple(int(o) for o in order)
    if len(order) == 0:
        raise ValueError("empty chain")
    prog = prg.plan_broadcast(
        _axis_size(axis_name), order[0], (order[1:],) if len(order) > 1 else ()
    )
    return execute_program(x, axis_name, prog, num_frames=num_frames)


def _validate_multi_chains(
    head: int, chains: Sequence[Sequence[int]]
) -> tuple[tuple[int, ...], ...]:
    clean = prg.validate_chains(head, chains)
    if not clean:
        raise ValueError("empty chain set")
    return clean


def multi_chain_broadcast(
    x: jax.Array,
    axis_name: Axis,
    head: int,
    chains: Sequence[Sequence[int]],
    *,
    num_frames: int = 1,
) -> jax.Array:
    """Multicast ``x`` from device ``head`` down K disjoint sub-chains
    concurrently (multi-chain Chainwrite; chains typically come from
    ``scheduling.partition_schedule``).

    ``chains`` are destination orders (head excluded, matching the
    scheduler convention); they must be pairwise disjoint. Devices on
    the axis in no chain return zeros, chain members (and the head)
    return the head's payload. ``num_frames > 1`` pipelines frames down
    every chain simultaneously; completion takes
    ``num_frames + max_chain_len - 1`` frame-hop slots instead of
    ``num_frames * max_chain_len``.

    K=1 computes exactly ``chain_broadcast(x, axis, (head, *chains[0]))``
    (they interpret the identical program).
    """
    chains = _validate_multi_chains(int(head), chains)
    prog = prg.plan_broadcast(_axis_size(axis_name), int(head), chains)
    return execute_program(x, axis_name, prog, num_frames=num_frames)


def degraded_chains(
    chains: Sequence[Sequence[int]], failed: FailureSpec
) -> list[tuple[int, ...]]:
    """Splice the ``failed`` member(s) out of their sub-chains
    (endpoint-only re-forming at the SPMD layer: no topology knowledge,
    relative order kept). ``failed`` is one node id or a set of
    concurrently dead members.

    Host-side callers that hold a :class:`~repro.core.topology.
    MeshTopology` should prefer ``scheduling.reform_chain`` per chain —
    it re-orders the orphaned suffix — and pass the result straight to
    :func:`multi_chain_broadcast`; this helper is the schedule-free
    fallback. Chains emptied by the splice are dropped.
    """
    dead = set(normalize_failed(failed))
    members = {int(d) for c in chains for d in c}
    missing = sorted(dead - members)
    if missing:
        raise ValueError(f"failed node(s) {missing} are in no chain")
    out: list[tuple[int, ...]] = []
    for c in chains:
        kept = tuple(int(d) for d in c if int(d) not in dead)
        if kept:
            out.append(kept)
    return out


def degraded_multi_chain_broadcast(
    x: jax.Array,
    axis_name: Axis,
    head: int,
    chains: Sequence[Sequence[int]],
    failed: FailureSpec,
    *,
    num_frames: int = 1,
) -> jax.Array:
    """:func:`multi_chain_broadcast` with the chain member(s) ``failed``
    (one node id or a set of concurrently dead members) dropped — the
    degraded collective a re-formed Chainwrite runs after node
    failures.

    Every *surviving* chain member (and the head) still receives the
    head's payload; the failed devices — like any non-member — return
    zeros, so the paper's "nothing outside the chain is touched"
    property extends to dead nodes. K=1 with the failure in the middle
    of the single chain degrades to the spliced shorter chain.
    """
    head = int(head)
    if head in set(normalize_failed(failed)):
        raise ValueError("the initiator (head) cannot be dropped")
    remaining = degraded_chains(chains, failed)
    if not remaining:  # every destination failed: head keeps its payload
        prog = prg.plan_broadcast(_axis_size(axis_name), head, ())
        return execute_program(x, axis_name, prog, num_frames=num_frames)
    return multi_chain_broadcast(
        x, axis_name, head, remaining, num_frames=num_frames
    )


# ---------------------------------------------------------------------------
# Ring collectives over a scheduled order
# ---------------------------------------------------------------------------


def _ring_args(
    axis_name: Axis, order: Sequence[int] | None
) -> tuple[int, tuple[int, ...]]:
    L = _axis_size(axis_name)
    order = tuple(range(L)) if order is None else tuple(int(o) for o in order)
    if sorted(order) != list(range(L)):
        raise ValueError("ring order must be a permutation of the whole axis")
    return L, order


def _ring_partition(
    axis_name: Axis, orders: Sequence[Sequence[int]]
) -> tuple[int, tuple[tuple[int, ...], ...]]:
    L = _axis_size(axis_name)
    return L, tuple(validate_ring_partition(L, orders))


def chain_all_gather(
    x: jax.Array,
    axis_name: Axis,
    order: Sequence[int] | None = None,
    *,
    tiled: bool = False,
) -> jax.Array:
    """Ring all-gather over a scheduled ring order.

    Every device contributes ``x``; returns the stacked (axis 0) —
    or, with ``tiled=True``, concatenated — shards indexed by *device
    id along the axis* (standard all_gather semantics, so this is a
    drop-in for ``lax.all_gather`` regardless of ring order).
    """
    L, order = _ring_args(axis_name, order)
    prog = prg.plan_all_gather(L, (order,))
    return execute_program(x, axis_name, prog, tiled=tiled)


def multi_chain_all_gather(
    x: jax.Array,
    axis_name: Axis,
    orders: Sequence[Sequence[int]],
    *,
    tiled: bool = False,
) -> jax.Array:
    """All-gather over K disjoint equal-size sub-rings: per-ring
    all-gather (S-1 fused 1-shard steps), then a cross-ring exchange of
    the gathered ring blocks (K-1 width-S steps) — (S-1) + (K-1)·S =
    L-1 shards of wire per device, exactly the single ring's, with
    every hop ring-local or position-paired. K=1 delegates to
    :func:`chain_all_gather`'s schedule."""
    L, orders = _ring_partition(axis_name, orders)
    prog = prg.plan_all_gather(L, orders)
    return execute_program(x, axis_name, prog, tiled=tiled)


def chain_reduce_scatter(
    x: jax.Array,
    axis_name: Axis,
    order: Sequence[int] | None = None,
) -> jax.Array:
    """Ring reduce-scatter over a scheduled ring order.

    ``x`` has leading dim L (one chunk per device id along the axis);
    returns the fully-reduced chunk owned by this device
    (``sum_over_devices(x)[my_id]``).
    """
    L, order = _ring_args(axis_name, order)
    prog = prg.plan_reduce_scatter(L, (order,))
    return execute_program(x, axis_name, prog)


def multi_chain_reduce_scatter(
    x: jax.Array,
    axis_name: Axis,
    orders: Sequence[Sequence[int]],
) -> jax.Array:
    """Reduce-scatter over K disjoint equal-size sub-rings: per-ring
    reduce-scatter of width-K chunk *groups* (S-1 steps), then a
    cross-ring reduce-scatter of each group (K-1 single-chunk steps) —
    (S-1)·K + (K-1) = L-1 chunks of wire per device, matching the
    single ring. K=1 delegates to :func:`chain_reduce_scatter`'s
    schedule."""
    L, orders = _ring_partition(axis_name, orders)
    prog = prg.plan_reduce_scatter(L, orders)
    return execute_program(x, axis_name, prog)


def chain_all_reduce(
    x: jax.Array,
    axis_name: Axis,
    order: Sequence[int] | None = None,
    *,
    wire_dtype: str | None = None,
) -> jax.Array:
    """Ring all-reduce = reduce-scatter + all-gather on the scheduled
    ring (bandwidth-optimal: 2·(L-1)/L of the payload per link).
    ``wire_dtype="int8"`` ships every hop quantized (per-hop int8 frame
    + f32 scale; f32 accumulation)."""
    L, order = _ring_args(axis_name, order)
    prog = prg.plan_all_reduce(L, (order,), wire_dtype=wire_dtype)
    return execute_program(x, axis_name, prog)


def multi_chain_all_reduce(
    x: jax.Array,
    axis_name: Axis,
    orders: Sequence[Sequence[int]],
    *,
    algo: str = "rs_ag",
    wire_dtype: str | None = None,
) -> jax.Array:
    """All-reduce over K disjoint equal-size sub-rings of the axis.

    ``algo="rs_ag"`` (default — bandwidth-optimal family): stage 1 is a
    fused per-ring reduce-scatter (S-1 steps; the K rings' edges are
    disjoint, so every step is ONE ppermute carrying 1/S-payload
    shards), stage 2 rotation-reduces the reduced *shards* across rings
    (K-1 steps, still 1/S payload: position r of ring c exchanges with
    position r of ring c+1), stage 3 is the fused per-ring all-gather
    (S-1 steps). Wire bytes per device ≈ (2·(S-1)+(K-1))/S · payload —
    at K=1 exactly ``chain_all_reduce``'s bandwidth-optimal
    2·(L-1)/L — while the per-ring chain length stays S, keeping the
    multi-chain latency win.

    ``algo="rotation"`` keeps PR 1's schedule: S-1 full-payload
    rotations within rings then K-1 across — fewer steps (S+K-2 vs
    2·(S-1)+(K-1)) but (S+K-2) full payloads of wire per device;
    preferable only when per-step overhead dominates (tiny payloads).
    ``core.simulator.all_reduce_latency`` models both and
    ``choose_num_chains(collective="all_reduce")`` picks K/algo-aware.

    Hierarchical (within-pod then cross-pod) all-reduce is exactly the
    K=#pods special case of either schedule on the flattened DP axis.

    ``orders``: K disjoint rings of equal size covering the whole axis
    (e.g. contiguous slices of ``ring_order_for_axis``). K=1 delegates
    to :func:`chain_all_reduce` (reduce-scatter + all-gather) for
    either ``algo``.
    """
    if algo not in ALL_REDUCE_ALGOS:
        raise ValueError(f"unknown algo {algo!r}; expected {ALL_REDUCE_ALGOS}")
    L, orders = _ring_partition(axis_name, orders)
    prog = prg.plan_all_reduce(L, orders, algo, wire_dtype=wire_dtype)
    return execute_program(x, axis_name, prog)


def chain_all_to_all(
    x: jax.Array,
    axis_name: Axis,
    order: Sequence[int] | None = None,
    *,
    wire_dtype: str | None = None,
) -> jax.Array:
    """Ring all-to-all (MoE dispatch): ``x`` has leading dim L, chunk
    ``x[d]`` is destined to device ``d``. Returns stacked chunks
    received from every device (``out[s]`` = chunk sent by device s).

    Implemented as L-1 rotations of the scheduled ring: at each step
    every device forwards the not-yet-delivered chunks one hop and
    keeps the chunk addressed to it — each chunk travels exactly its
    ring distance, the chain analogue of per-pair P2P transfers.
    """
    L, order = _ring_args(axis_name, order)
    prog = prg.plan_all_to_all(L, (order,), wire_dtype=wire_dtype)
    return execute_program(x, axis_name, prog)


def multi_chain_all_to_all(
    x: jax.Array,
    axis_name: Axis,
    orders: Sequence[Sequence[int]],
    *,
    wire_dtype: str | None = None,
) -> jax.Array:
    """All-to-all over K disjoint equal-size sub-rings: intra-ring
    rotations interleaved with cross-ring hops (K·(S-1) + (K-1) = L-1
    full-train steps — a chunk train cannot shrink, so the wire bytes
    match the single ring; every hop is ring-local or position-paired).
    K=1 delegates to :func:`chain_all_to_all`'s schedule."""
    L, orders = _ring_partition(axis_name, orders)
    prog = prg.plan_all_to_all(L, orders, wire_dtype=wire_dtype)
    return execute_program(x, axis_name, prog)


# ---------------------------------------------------------------------------
# XLA-native baselines (the "network-layer multicast" analogue)
# ---------------------------------------------------------------------------


def xla_broadcast(x: jax.Array, axis_name: Axis, root: int = 0) -> jax.Array:
    """Broadcast via the fabric's native reduction (baseline)."""
    idx = _axis_index(axis_name)
    return lax.psum(jnp.where(idx == root, x, jnp.zeros_like(x)), axis_name)


def _axis_size(axis_name: Axis) -> int:
    if isinstance(axis_name, (tuple, list)):
        return int(
            functools.reduce(lambda a, n: a * lax.axis_size(n), axis_name, 1)
        )
    return int(lax.axis_size(axis_name))
