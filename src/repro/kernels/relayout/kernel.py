"""Pallas TPU kernel: blocked-layout transform (the Torrent DSE).

The paper's Data Streaming Engine performs ND-affine reads so a matrix
can leave the source memory already in the destination layout (the P1/P2
workloads transform ``MNM16N8 -> MNM8N8`` on the fly). On TPU the same
job is an HBM->VMEM->HBM tiled relayout: each grid step stages one
*super-tile* — ``lcm`` of the two block heights × ``lcm`` of the two
block widths, padded up to MXU/VPU-friendly multiples — in VMEM,
re-tiles it with registers only (transpose/reshape), and writes it back
in the destination blocking.

VMEM budget: Pallas double-buffers one super-tile in and one out, and
each block's last two dims pad to the (sublane, 128-lane) tile. The
super-tile grows only while the padded in + out tile stays inside
``_VMEM_TILE_BYTES``. When both blockings share a block width (the
paged-KV relayout ``(1, F) -> (page, F)`` regroups rows and leaves
columns alone) a block's lanes are tiled too, in multiples of 128 that
divide the width, so a wide block never has to fit VMEM whole.
"""

from __future__ import annotations

import functools
import math

import jax
from jax.experimental import pallas as pl


# Padded bytes of one super-tile in + out; double-buffered, that stays
# well inside the 16 MiB of VMEM a TPU core gives a kernel by default.
_VMEM_TILE_BYTES = 2 << 20


def _padded_bytes(block: tuple[int, ...], itemsize: int) -> int:
    """VMEM bytes of a block whose last two dims pad to the dtype's
    (sublane, 128-lane) tile."""
    *lead, rows, cols = block
    sub = 8 * max(1, 4 // itemsize)
    return (math.prod(lead) * -(-rows // sub) * sub * -(-cols // 128) * 128
            * itemsize)


def _supertile(src_block: tuple[int, int], dst_block: tuple[int, int],
               shape: tuple[int, int], itemsize: int,
               target: int = 256) -> tuple[int, int, int]:
    """``(tm, tn, lane)``: rows and columns of one VMEM super-tile and
    the lane width of its blocks (the block width, unless lanes are
    tiled). The smallest tile compatible with both blockings is scaled
    up toward ``target`` while it divides ``shape`` and its padded
    in + out bytes fit ``_VMEM_TILE_BYTES``."""
    sbm, sbn = src_block
    dbm, dbn = dst_block
    M, N = shape

    def cost(tm, tn, lane):
        ws, wd = (lane, lane) if lane < sbn else (sbn, dbn)
        return (_padded_bytes((tm // sbm, tn // ws, sbm, ws), itemsize)
                + _padded_bytes((tm // dbm, tn // wd, dbm, wd), itemsize))

    tm, tn, lane = math.lcm(sbm, dbm), math.lcm(sbn, dbn), sbn
    if sbn == dbn and cost(tm, tn, lane) > _VMEM_TILE_BYTES:
        widths = [w for w in range(128, sbn, 128) if sbn % w == 0]
        if widths:
            fits = [w for w in widths if cost(tm, w, w) <= _VMEM_TILE_BYTES]
            lane = tn = max(fits) if fits else widths[0]
    while (tm * 2 <= min(target, M) and M % (tm * 2) == 0
           and cost(tm * 2, tn, lane) <= _VMEM_TILE_BYTES):
        tm *= 2
    while (lane == sbn and tn * 2 <= min(target, N) and N % (tn * 2) == 0
           and cost(tm, tn * 2, lane) <= _VMEM_TILE_BYTES):
        tn *= 2
    return tm, tn, lane


def _relayout_kernel(x_ref, o_ref, *, tm: int, tn: int,
                     src_block: tuple[int, int], dst_block: tuple[int, int]):
    sbm, sbn = src_block
    dbm, dbn = dst_block
    # x_ref: (tm//sbm, tn//sbn, sbm, sbn) — the super-tile in src blocking.
    x = x_ref[...]
    dense = x.transpose(0, 2, 1, 3).reshape(tm, tn)
    out = dense.reshape(tm // dbm, dbm, tn // dbn, dbn).transpose(0, 2, 1, 3)
    o_ref[...] = out


@functools.partial(
    jax.jit,
    static_argnames=("shape", "src_block", "dst_block", "interpret"),
)
def relayout_pallas(
    x: jax.Array,
    shape: tuple[int, int],
    src_block: tuple[int, int],
    dst_block: tuple[int, int],
    interpret: bool = False,
) -> jax.Array:
    """Blocked(src_block) -> blocked(dst_block) layout transform.

    ``x``: (M//sbm, N//sbn, sbm, sbn). Returns (M//dbm, N//dbn, dbm, dbn).
    """
    M, N = shape
    sbm, sbn = src_block
    dbm, dbn = dst_block
    if (M % sbm, N % sbn, M % dbm, N % dbn) != (0, 0, 0, 0):
        raise ValueError(f"blocks {src_block}/{dst_block} must divide {shape}")
    tm, tn, lane = _supertile(src_block, dst_block, shape, x.dtype.itemsize)
    # lane < sbn: each block's columns split into sbn // lane lane tiles
    ws, wd = (lane, lane) if lane < sbn else (sbn, dbn)
    grid = (M // tm, N // max(tn, sbn), sbn // ws)
    out_shape = jax.ShapeDtypeStruct((M // dbm, N // dbn, dbm, dbn), x.dtype)
    kernel = functools.partial(
        _relayout_kernel, tm=tm, tn=tn, src_block=(sbm, ws), dst_block=(dbm, wd)
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (tm // sbm, max(tn, sbn) // sbn, sbm, ws),
                lambda i, j, c: (i, j, 0, c),
            )
        ],
        out_specs=pl.BlockSpec(
            (tm // dbm, max(tn, dbn) // dbn, dbm, wd),
            lambda i, j, c: (i, j, 0, c),
        ),
        out_shape=out_shape,
        name="relayout",
        interpret=interpret,
    )(x)
