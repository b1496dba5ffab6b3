"""Segment-major stats kernels for packed super-dispatches.

The PR 3 segment axis rode the generic bucket product: a pack of S
member parts prepended a ``ByKey('seg')`` axis, so the one-hot
compare-and-reduce in kernels.stats_count_local widened from
(STATS_CHUNK, buckets) to (STATS_CHUNK, S*buckets) — every chunk's VMEM
tile and VPU compare count scaled with the pack size, and MAX_BUCKETS
gated the MULTIPLIED product, so wide group-bys taxed (or declined)
packing exactly on the shape packing exists for.

This module is the segment-major replacement: the segment axis is
reduced OUTSIDE the bucket one-hot —

- counts/sums: TWO small one-hots, (C, S) segment membership and
  (C, buckets) bucket membership, contracted on the row axis as an
  (S, C) x (C, B) matmul (MXU work; exact — per-chunk cell counts and
  uint8 plane sums stay < 2**24, the f32 mantissa);
- min/max: a static per-segment unroll of the classic (C, B) masked
  reduction (S <= VL_PACK_PARTS, so the unroll is a handful of steps
  and peak VMEM per step stays (C, B), not (C, S*B)).

The accumulator is the [S, buckets] layout the harvest already decodes
(the 'seg' axis was FIRST in the by order, so its stride equals the
base bucket product — the flattened seg-major result is bit-identical
to what the widened kernel produced), and the per-chunk working-set
width no longer scales with the pack size.  tpu/batch._assemble_axes
therefore stops counting the segment axis toward MAX_BUCKETS.

A Pallas variant of the count reduction (the dominant shape: plain
``count()`` group-bys) is gated behind VL_PALLAS=1 like every Pallas
kernel in this repo (never on by default; tests/pallas_check.py pins
parity in interpret mode on CPU and, run by chip_smoke.py, compiled on
the chip); the values variant stays jnp.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import kernels as K
from .kernels import STATS_CHUNK, _vary
from .kernels_pallas import pl, pltpu, vmem_spec

# Pallas tile geometry: segments pad to one f32 sublane tile, buckets
# to the 128-lane vector width (same discipline as kernels_pallas).
SEG_TILE = 8
LANE = 128


def _onehots(si, bi, mi, segs, buckets):
    """The two small one-hot operands of the seg-major contraction."""
    seg1h = (si[:, None] == segs[None, :]) & mi[:, None]      # (C, S)
    b1h = bi[:, None] == buckets[None, :]                     # (C, B)
    return seg1h, b1h


def stats_count_seg_local(seg_ids: jnp.ndarray, bucket_ids: jnp.ndarray,
                          mask: jnp.ndarray, nseg: int, nb: int,
                          vary_axes=(), use_pallas: bool = False,
                          interpret: bool = False) -> jnp.ndarray:
    """Masked per-(segment, bucket) row counts, flattened seg-major.

    seg_ids/bucket_ids: int-typed [R] (R a STATS_CHUNK multiple);
    mask: bool[R] (padding rows False).  Returns uint32[nseg*nb] in the
    exact order kernels.stats_count_local produced for the widened
    combined id (seg stride == nb) — the host decode is unchanged."""
    if use_pallas and nseg <= SEG_TILE:
        return stats_count_seg_pallas(seg_ids, bucket_ids, mask, nseg,
                                      nb, interpret=interpret)
    sg = seg_ids.astype(jnp.int32).reshape(-1, STATS_CHUNK)
    b = bucket_ids.astype(jnp.int32).reshape(-1, STATS_CHUNK)
    m = mask.reshape(-1, STATS_CHUNK)
    segs = jnp.arange(nseg, dtype=jnp.int32)
    buckets = jnp.arange(nb, dtype=jnp.int32)

    def body(acc, xs):
        si, bi, mi = xs
        seg1h, b1h = _onehots(si, bi, mi, segs, buckets)
        # (S, C) x (C, B) matmul: per-chunk cell counts <= STATS_CHUNK
        # < 2**24, exact in the f32 contraction
        acc = acc + jnp.einsum("cs,cb->sb", seg1h.astype(jnp.float32),
                               b1h.astype(jnp.float32)).astype(jnp.uint32)
        return acc, None

    acc, _ = jax.lax.scan(
        body, _vary(jnp.zeros((nseg, nb), jnp.uint32), vary_axes),
        (sg, b, m))
    return acc.reshape(-1)


def stats_values_seg_local(values: jnp.ndarray, seg_ids: jnp.ndarray,
                           bucket_ids: jnp.ndarray, mask: jnp.ndarray,
                           nseg: int, nb: int, vary_axes=()):
    """Seg-major count/sum/min/max partials for one uint32 value column.

    Returns (cnt, sums[4, .], lo, hi), each flattened over nseg*nb in
    seg-major order — drop-in for kernels.stats_values_local over the
    widened combined id, with the same exactness contract (uint8 byte
    planes contracted in f32, per-chunk plane sums < 2**24)."""
    v = values.reshape(-1, STATS_CHUNK)
    sg = seg_ids.astype(jnp.int32).reshape(-1, STATS_CHUNK)
    b = bucket_ids.astype(jnp.int32).reshape(-1, STATS_CHUNK)
    m = mask.reshape(-1, STATS_CHUNK)
    segs = jnp.arange(nseg, dtype=jnp.int32)
    buckets = jnp.arange(nb, dtype=jnp.int32)
    u32max = jnp.uint32(0xFFFFFFFF)

    def body(carry, xs):
        cnt, sums, lo, hi = carry
        vi, si, bi, mi = xs
        seg1h, b1h = _onehots(si, bi, mi, segs, buckets)
        seg_f = seg1h.astype(jnp.float32)
        b_f = b1h.astype(jnp.float32)
        cnt = cnt + jnp.einsum("cs,cb->sb", seg_f,
                               b_f).astype(jnp.uint32)
        # four byte planes, each its own (S, C) x (C, B) contraction of
        # the plane-weighted bucket one-hot — peak working set stays
        # (C, max(S, B)), never (C, S*B)
        ps = []
        for p in range(4):
            plane = ((vi >> (8 * p)) & 0xFF).astype(jnp.float32)
            ps.append(jnp.einsum("cs,cb->sb", seg_f,
                                 b_f * plane[:, None]))
        sums = sums + jnp.stack(ps, axis=0).astype(jnp.uint32)
        # min/max: static per-segment unroll of the classic masked
        # reduction (S <= VL_PACK_PARTS)
        los = []
        his = []
        for s in range(nseg):
            sel = b1h & seg1h[:, s][:, None]
            los.append(jnp.min(jnp.where(sel, vi[:, None], u32max),
                               axis=0))
            his.append(jnp.max(jnp.where(sel, vi[:, None],
                                         jnp.uint32(0)), axis=0))
        lo = jnp.minimum(lo, jnp.stack(los, axis=0))
        hi = jnp.maximum(hi, jnp.stack(his, axis=0))
        return (cnt, sums, lo, hi), None

    init = tuple(
        _vary(a, vary_axes)
        for a in (jnp.zeros((nseg, nb), jnp.uint32),
                  jnp.zeros((4, nseg, nb), jnp.uint32),
                  jnp.full((nseg, nb), u32max),
                  jnp.zeros((nseg, nb), jnp.uint32)))
    (cnt, sums, lo, hi), _ = jax.lax.scan(body, init, (v, sg, b, m))
    return (cnt.reshape(-1), sums.reshape(4, -1), lo.reshape(-1),
            hi.reshape(-1))


# ---------------- slot-map (segment-aligned) kernels ----------------
#
# The scan kernels above reduce every segment against every row chunk
# (the unroll/min-max term costs S passes per chunk), which is what
# shard_map's manual row stripes require — but a single-device dispatch
# can do better: gather the pack's rows into a [S, Lp] SEGMENT-ALIGNED
# grid (members are contiguous row ranges of the pack layout, so the
# map is a host-built static index table, cached per pack like any
# staging), then reduce each member against only ITS OWN padded slots.
# Total reduction work drops from S * R_padded to ~R (the members' own
# rows), the (S, SLOT_CHUNK, B) one-hot tile matches the classic
# (STATS_CHUNK, B) footprint, and results stay bit-identical.

SLOT_CHUNK = 1024      # slots per scan step; S*SLOT_CHUNK ~ STATS_CHUNK


def pad_slots(n: int, k: int = 0) -> int:
    """Slot-axis length: a SLOT_CHUNK multiple >= max(n, k, 1) (k: a
    topk dispatch needs at least k slots per member to select on)."""
    need = max(n, k, 1)
    return ((need + SLOT_CHUNK - 1) // SLOT_CHUNK) * SLOT_CHUNK


def build_seg_slot_map(part, layout, min_len: int = 0):
    """int32[S, Lp] row-index table of a packed part: row idx of member
    s's slot j, -1 on padding slots.  Members occupy contiguous row
    ranges of the pack layout (blocks concatenate in member order), so
    the table is pure host arithmetic over the block map."""
    import numpy as np
    nseg = part.num_segments
    starts = []
    lens = []
    for mi in range(nseg):
        first = part.block_offset(mi)
        nxt = part.block_offset(mi + 1) if mi + 1 < nseg else \
            part.num_blocks
        starts.append(layout.starts[first])
        lens.append(sum(part.block_rows(bi) for bi in range(first,
                                                            nxt)))
    lp = pad_slots(max(lens), min_len)
    idx = np.full((nseg, lp), -1, dtype=np.int32)
    for mi, (st, ln) in enumerate(zip(starts, lens)):
        idx[mi, :ln] = np.arange(st, st + ln, dtype=np.int32)
    return idx


def _slot_gather(seg_map, arr, fill=None):
    """arr[seg_map] with -1 slots masked (bool arrs -> False)."""
    valid = seg_map >= 0
    safe = jnp.maximum(seg_map, 0)
    got = arr[safe]
    if fill is None:
        return got, valid
    return jnp.where(valid, got, fill), valid


def stats_count_slots(seg_map, bucket_ids, mask, nb: int):
    """Seg-major masked counts via the slot grid; uint32[S*nb]."""
    s, _lp = seg_map.shape
    b2, valid = _slot_gather(seg_map, bucket_ids.astype(jnp.int32))
    m2 = mask[jnp.maximum(seg_map, 0)] & valid
    buckets = jnp.arange(nb, dtype=jnp.int32)
    bc = jnp.moveaxis(b2.reshape(s, -1, SLOT_CHUNK), 1, 0)
    mc = jnp.moveaxis(m2.reshape(s, -1, SLOT_CHUNK), 1, 0)

    def body(acc, xs):
        bi, mi = xs
        oh = (bi[:, :, None] == buckets[None, None, :]) \
            & mi[:, :, None]
        return acc + jnp.sum(oh.astype(jnp.uint32), axis=1), None

    acc, _ = jax.lax.scan(body, jnp.zeros((s, nb), jnp.uint32),
                          (bc, mc))
    return acc.reshape(-1)


def stats_values_slots(values, seg_map, bucket_ids, mask, nb: int):
    """Seg-major count/sum/min/max via the slot grid — each member
    reduces only its own slots; exactness contract as the scan form
    (per-cell plane sums <= 255 * SLOT_CHUNK < 2**24 in f32)."""
    s, _lp = seg_map.shape
    safe = jnp.maximum(seg_map, 0)
    valid = seg_map >= 0
    v2 = values[safe]
    b2 = bucket_ids.astype(jnp.int32)[safe]
    m2 = mask[safe] & valid
    buckets = jnp.arange(nb, dtype=jnp.int32)
    u32max = jnp.uint32(0xFFFFFFFF)
    vc = jnp.moveaxis(v2.reshape(s, -1, SLOT_CHUNK), 1, 0)
    bc = jnp.moveaxis(b2.reshape(s, -1, SLOT_CHUNK), 1, 0)
    mc = jnp.moveaxis(m2.reshape(s, -1, SLOT_CHUNK), 1, 0)

    def body(carry, xs):
        cnt, sums, lo, hi = carry
        vi, bi, mi = xs                              # (S, CL) each
        oh = (bi[:, :, None] == buckets[None, None, :]) \
            & mi[:, :, None]                         # (S, CL, B)
        cnt = cnt + jnp.sum(oh.astype(jnp.uint32), axis=1)
        ohf = oh.astype(jnp.float32)
        ps = []
        for p in range(4):
            plane = ((vi >> (8 * p)) & 0xFF).astype(jnp.float32)
            ps.append(jnp.einsum("sc,scb->sb", plane, ohf))
        sums = sums + jnp.stack(ps, axis=0).astype(jnp.uint32)
        lo = jnp.minimum(lo, jnp.min(
            jnp.where(oh, vi[:, :, None], u32max), axis=1))
        hi = jnp.maximum(hi, jnp.max(
            jnp.where(oh, vi[:, :, None], jnp.uint32(0)), axis=1))
        return (cnt, sums, lo, hi), None

    init = (jnp.zeros((s, nb), jnp.uint32),
            jnp.zeros((4, s, nb), jnp.uint32),
            jnp.full((s, nb), u32max),
            jnp.zeros((s, nb), jnp.uint32))
    (cnt, sums, lo, hi), _ = jax.lax.scan(body, init, (vc, bc, mc))
    return (cnt.reshape(-1), sums.reshape(4, -1), lo.reshape(-1),
            hi.reshape(-1))


# ---------------- Pallas count variant (VL_PALLAS gate) ----------------

# bucket-tile lanes per grid step, and the one-hot tile budget the row
# chunk is sized against: a (BUCKET_TILE, chunk) f32 tile plus its
# compare temporaries must sit well inside v5e's 16 MiB scoped VMEM
BUCKET_TILE = 512
_ONEHOT_TILE_ELEMS = 512 * 1024


def _count_seg_kernel(seg_ref, b_ref, out_ref, *, tbk: int):
    """One (bucket tile, row chunk) grid step.

    Ids arrive LANE-major as (1, chunk) rows — a (chunk, 1) column
    block would pad every id to a 128-lane vreg row (4 MiB per 8192-row
    column) — so both one-hots are built transposed by comparing the
    broadcast id row against a SUBLANE iota: seg1hT (SEG_TILE, chunk),
    b1hT (tbk, chunk), dense VPU compares, no gather.  They contract on
    the chunk axis as A @ B^T on the MXU; per-chunk cell counts are
    <= chunk < 2**24, exact in f32, and accumulate as int32 in the
    revisited output block (zeroed on the first row chunk).  Dead rows
    carry segment -1, which matches no sublane."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[:, :] = jnp.zeros_like(out_ref)

    sg = seg_ref[0]                            # int32[1, chunk]
    bi = b_ref[0]
    c = sg.shape[1]
    seg_iota = jax.lax.broadcasted_iota(jnp.int32, (SEG_TILE, c), 0)
    b_iota = jax.lax.broadcasted_iota(jnp.int32, (tbk, c), 0) \
        + pl.program_id(0) * tbk
    seg1h = jnp.where(sg == seg_iota, 1.0, 0.0).astype(jnp.float32)
    b1h = jnp.where(bi == b_iota, 1.0, 0.0).astype(jnp.float32)
    out_ref[:, :] += jax.lax.dot_general(
        seg1h, b1h, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(jnp.int32)


@partial(jax.jit, static_argnames=("nseg", "nb", "interpret"))
def stats_count_seg_pallas(seg_ids, bucket_ids, mask, nseg: int,
                           nb: int, interpret: bool = False):
    """Pallas seg-major count; bit-identical to the jnp path (padded
    segments/buckets reduce to zero and are sliced off)."""
    r = seg_ids.shape[0]
    nbp = ((nb + LANE - 1) // LANE) * LANE
    tbk = min(nbp, BUCKET_TILE)
    nbp = ((nbp + tbk - 1) // tbk) * tbk
    chunk = min(STATS_CHUNK, _ONEHOT_TILE_ELEMS // tbk)
    g = r // chunk
    sg = jnp.where(mask, seg_ids.astype(jnp.int32), -1).reshape(g, 1, chunk)
    b = bucket_ids.astype(jnp.int32).reshape(g, 1, chunk)

    spec = vmem_spec
    out = pl.pallas_call(
        partial(_count_seg_kernel, tbk=tbk),
        grid=(nbp // tbk, g),
        in_specs=[
            spec((1, 1, chunk), lambda j, i: (i, 0, 0)),
            spec((1, 1, chunk), lambda j, i: (i, 0, 0)),
        ],
        out_specs=spec((SEG_TILE, tbk), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((SEG_TILE, nbp), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(sg, b)
    return out[:nseg, :nb].astype(jnp.uint32).reshape(-1)


# ---------------- reference (differential-test oracle) ----------------

def stats_count_seg_reference(seg_ids, bucket_ids, mask, nseg: int,
                              nb: int) -> jnp.ndarray:
    """The widened-combined-id formulation this module replaces, kept
    as the parity oracle: seg stride == nb, one (C, S*B) one-hot."""
    combined = K.combine_ids(
        (seg_ids, bucket_ids), (nb, 1))
    return K.stats_count_local(combined, mask, nseg * nb)
