"""Batched bloom-plane probes: one dense bit-test over all blocks.

The host packs a part column's bloom filters into a zero-padded uint32
plane `[B, 2*Wmax]` and derives per-block probe coordinates from the
query tokens (storage/filterbank.py — positions come from
``bloom_probe_positions`` so host and device share one derivation).
This module evaluates the keep-mask three ways off those SAME
arguments:

- ``probe_np``: vectorized numpy — the host kill-path in
  tpu/batch.py's leaf evaluation and the prefetcher (a probe over 10k
  blocks is one gather + bit-test instead of 10k Python calls).
- ``plane_keep``: the jnp expression, traceable inside the fused
  single-dispatch jit (tpu/fused.py) — the per-block keep-mask gathers
  to rows through the staged block-id column and ANDs against the scan
  tree IN HBM, no host round-trip.
- ``plane_keep_pallas``: a VMEM-tiled Pallas variant (behind
  VL_PALLAS=1) replacing the gather with a lane-select so the probe
  stays a dense VPU op; tests/pallas_check.py pins parity in interpret
  mode on CPU and, run by chip_smoke.py, compiled on the chip.

Layout contract (split-block style, Lang et al. arXiv:2101.01719):
  plane  uint32[B, WP]  2 little-endian lanes per uint64 word, 0-padded
  idx    int32[B, P]    uint32-lane index of each probe bit (< 2*nwords)
  shift  int32[B, P]    bit position within the lane (0..31)
  nwords int32[B]       0 => block has no bloom => always keep
returns bool[B]: True where the block may contain ALL probed tokens.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .kernels_pallas import pl, pltpu, vmem_spec

PROBE_TILE_B = 128     # pallas block-axis tile (int32 sublane multiple)
PROBE_LANE = 128       # pallas lane width; also the max probe count
MAX_PALLAS_PROBES = PROBE_LANE


def probe_np(plane: np.ndarray, idx: np.ndarray, shift: np.ndarray,
             nwords: np.ndarray) -> np.ndarray:
    """Vectorized host probe; bit-identical to per-block
    bloom_contains_all (tests/test_filterbank.py differentials)."""
    if idx.shape[1] == 0:
        return np.ones(plane.shape[0], dtype=bool)
    words = np.take_along_axis(plane, idx, axis=1)
    bits = (words >> shift.astype(np.uint32)) & np.uint32(1)
    return (bits != 0).all(axis=1) | (nwords == 0)


def plane_keep(plane, idx, shift, nwords, use_pallas: bool = False,
               interpret: bool = False):
    """jnp keep-mask; traceable inside an outer jit (fused dispatch)."""
    if use_pallas and _pallas_ok(plane.shape, idx.shape):
        return plane_keep_pallas(plane, idx, shift, nwords,
                                 interpret=interpret)
    words = jnp.take_along_axis(plane, idx, axis=1)
    bits = (words >> shift.astype(jnp.uint32)) & jnp.uint32(1)
    return jnp.all(bits != 0, axis=1) | (nwords == 0)


@jax.jit
def plane_probe(plane, idx, shift, nwords):
    """Standalone jitted probe -> bool[B] (bench/parity entry point)."""
    return plane_keep(plane, idx, shift, nwords)


# ---------------- pallas variant ----------------

def _pallas_ok(plane_shape, idx_shape) -> bool:
    b, wp = plane_shape
    return (b % PROBE_TILE_B == 0 and wp % PROBE_LANE == 0
            and 0 < idx_shape[1] <= MAX_PALLAS_PROBES)


def _lane_tile(wp: int) -> int:
    """Widest lane tile (<= 2048) that divides the padded plane width:
    a big part's blooms run to thousands of lanes per block, and a
    whole-row (PROBE_TILE_B, WP) tile would not fit scoped VMEM."""
    return next(t for t in (2048, 1024, 512, 256, 128) if wp % t == 0)


def _probe_kernel(plane_ref, idx_ref, shift_ref, nw_ref, out_ref, acc_ref,
                  *, nprobes: int, tw: int):
    """One (PROBE_TILE_B, TW) plane tile: all probes tested from VMEM.

    No gather: each probe selects its lane by comparing a broadcast
    iota against the per-block lane index and sum-reducing the masked
    tile (exactly one lane of one tile matches; idx < 2*nwords <= WP
    always), so the probe lowers to dense VPU compare/select/reduce
    ops.  Per-probe columns are themselves lane-selected out of the
    (TB, 128) idx tile — no single-lane slices, which Mosaic only takes
    at aligned offsets.  Probe j's word accumulates in lane j of the
    (TB, 128) scratch across the lane-tile grid axis; the last lane
    tile tests the bits."""
    w = pl.program_id(1)

    @pl.when(w == 0)
    def _init():
        acc_ref[:, :] = jnp.zeros_like(acc_ref)

    plane = plane_ref[:, :]                    # int32[TB, TW] bit pattern
    tb = plane.shape[0]
    idx = idx_ref[:, :]                        # int32[TB, 128]
    lane = jax.lax.broadcasted_iota(jnp.int32, (tb, tw), 1) + w * tw
    slot = jax.lax.broadcasted_iota(jnp.int32, (tb, PROBE_LANE), 1)
    acc = acc_ref[:, :]
    for j in range(nprobes):
        idx_j = jnp.sum(jnp.where(slot == j, idx, 0), axis=1,
                        keepdims=True)
        word = jnp.sum(jnp.where(lane == idx_j, plane, 0), axis=1,
                       keepdims=True)
        acc = acc + jnp.where(slot == j, word, 0)
    acc_ref[:, :] = acc

    @pl.when(w == pl.num_programs(1) - 1)
    def _finish():
        # arithmetic >> then &1 extracts the bit regardless of sign
        bit = (acc >> shift_ref[:, :]) & 1
        miss = jnp.where((slot < nprobes) & (bit == 0), 1, 0)
        ok = jnp.max(miss, axis=1, keepdims=True) == 0
        keep = jnp.logical_or(ok, nw_ref[:, :] == 0)
        out_ref[:, :] = keep.astype(jnp.int32)


@partial(jax.jit, static_argnames=("interpret",))
def plane_keep_pallas(plane, idx, shift, nwords, interpret: bool = False):
    """Pallas drop-in for the jnp probe on aligned shapes -> bool[B]."""
    b, wp = plane.shape
    assert _pallas_ok(plane.shape, idx.shape), (plane.shape, idx.shape)
    nprobes = idx.shape[1]
    tw = _lane_tile(wp)
    # uint32 planes ride as int32 bit patterns (Mosaic int32 lanes)
    plane_i = jax.lax.bitcast_convert_type(plane, jnp.int32)
    pad = PROBE_LANE - nprobes
    if pad:
        idx = jnp.pad(idx, ((0, 0), (0, pad)))
        shift = jnp.pad(shift, ((0, 0), (0, pad)))
    nw_col = nwords.reshape(b, 1).astype(jnp.int32)

    spec = vmem_spec
    kernel = partial(_probe_kernel, nprobes=nprobes, tw=tw)
    out = pl.pallas_call(
        kernel,
        grid=(b // PROBE_TILE_B, wp // tw),
        in_specs=[
            spec((PROBE_TILE_B, tw), lambda i, w: (i, w)),
            spec((PROBE_TILE_B, PROBE_LANE), lambda i, w: (i, 0)),
            spec((PROBE_TILE_B, PROBE_LANE), lambda i, w: (i, 0)),
            spec((PROBE_TILE_B, 1), lambda i, w: (i, 0)),
        ],
        out_specs=spec((PROBE_TILE_B, 1), lambda i, w: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, 1), jnp.int32),
        scratch_shapes=[pltpu.VMEM((PROBE_TILE_B, PROBE_LANE), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(plane_i, idx.astype(jnp.int32), shift.astype(jnp.int32), nw_col)
    return out.reshape(b) != 0


# ---------------- device staging helpers ----------------

@dataclass
class StagedBloomPlane:
    """One part column's bloom plane resident in HBM (replicated on a
    mesh: every shard probes the full block axis)."""
    plane: object                  # jax uint32[Bp, WP]
    nwords: object                 # jax int32[Bp]; 0 = always keep
    bp: int                        # padded block count
    nbytes: int

    def device_bytes(self) -> int:
        return self.nbytes


@dataclass
class StagedBlockIds:
    """Layout-coordinate block id per row: the gather bridge from a
    bool[B] keep-mask to a row bitmap, staged once per part."""
    ids: object                    # jax int32[RLp], row-aligned
    nbytes: int

    def device_bytes(self) -> int:
        return self.nbytes


def stage_bloom_plane(part, field: str, put) -> StagedBloomPlane | None:
    """Upload the part column's packed plane (padded to device tiles);
    None when the column has no plane (no blooms / oversized)."""
    from ..storage.filterbank import filter_bank
    plb = filter_bank(part).plane(part, field)
    if plb is None:
        return None
    plane, nw = pad_plane(plb.plane, plb.nwords)
    return StagedBloomPlane(plane=put(plane), nwords=put(nw),
                            bp=plane.shape[0],
                            nbytes=plane.nbytes + nw.nbytes)


def stage_block_ids(part, layout, put) -> StagedBlockIds:
    bid = np.zeros(layout.nrows_padded, dtype=np.int32)
    for bi in range(part.num_blocks):
        s = layout.starts[bi]
        bid[s:s + part.block_rows(bi)] = bi
    return StagedBlockIds(ids=put(bid), nbytes=bid.nbytes)

def pad_plane(plane: np.ndarray, nwords: np.ndarray
              ) -> tuple[np.ndarray, np.ndarray]:
    """Pad a host plane to the device layout: block axis to a
    PROBE_TILE_B multiple, lanes to a PROBE_LANE multiple.  Pad blocks
    carry nwords=0 (always-keep) and are never gathered by a real row;
    padding also buckets jit signatures so part-shape churn doesn't
    recompile the fused program per part."""
    b, wp = plane.shape
    bp = ((b + PROBE_TILE_B - 1) // PROBE_TILE_B) * PROBE_TILE_B
    wpp = max(PROBE_LANE,
              ((wp + PROBE_LANE - 1) // PROBE_LANE) * PROBE_LANE)
    if bp == b and wpp == wp:
        return plane, nwords
    out = np.zeros((bp, wpp), dtype=np.uint32)
    out[:b, :wp] = plane
    nw = np.zeros(bp, dtype=np.int32)
    nw[:b] = nwords
    return out, nw


def pad_probe_args(idx: np.ndarray, shift: np.ndarray,
                   bp: int) -> tuple[np.ndarray, np.ndarray]:
    """Pad per-block (idx, shift) to the padded block count."""
    b = idx.shape[0]
    if bp == b:
        return idx, shift
    out_i = np.zeros((bp, idx.shape[1]), dtype=np.int32)
    out_s = np.zeros((bp, idx.shape[1]), dtype=np.int32)
    out_i[:b] = idx
    out_s[:b] = shift
    return out_i, out_s


# ---------------- split-block (v2) probe ----------------
# Sealed-part filter index (storage/filterindex): every token's 6
# probe bits live in ONE 256-bit block, so the probe is a single
# contiguous 8-lane gather per (block, token) + an AND-compare against
# a per-token mask — no scattered lane selects.  Layout contract:
#   plane  uint32[B, LP]   per-block sb filters, 0-padded
#   sbidx  int32[B, T]     lane base of each token's selected block
#                          (sb block index * 8; 0 when nsb==0)
#   mask   uint32[T, 8]    the token's 256-bit probe mask
#   nsb    int32[B]        0 => block has no filter => always keep
# returns bool[B]: True where the block may contain ALL probed tokens.

SB_PROBE_LANES = 8


def probe_np_sb(plane: np.ndarray, sbidx: np.ndarray, mask: np.ndarray,
                nsb: np.ndarray) -> np.ndarray:
    """Vectorized host probe of the split-block layout; bit-identical
    to sbbloom.sb_contains_all per block (tests/test_filterindex.py)."""
    b, t = sbidx.shape
    if t == 0:
        return np.ones(b, dtype=bool)
    lane = (sbidx[:, :, None]
            + np.arange(SB_PROBE_LANES, dtype=np.int32)) \
        .reshape(b, t * SB_PROBE_LANES)
    words = np.take_along_axis(plane, lane, axis=1) \
        .reshape(b, t, SB_PROBE_LANES)
    ok = ((words & mask[None, :, :]) == mask[None, :, :]).all(axis=2)
    return ok.all(axis=1) | (nsb == 0)


def plane_keep_sb(plane, sbidx, mask, nsb):
    """jnp split-block keep-mask; traceable inside the fused dispatch
    (the `bloom_sb` program node in tpu/fused.py)."""
    b, t = sbidx.shape
    lane = (sbidx[:, :, None]
            + jnp.arange(SB_PROBE_LANES, dtype=jnp.int32)) \
        .reshape(b, t * SB_PROBE_LANES)
    words = jnp.take_along_axis(plane, lane, axis=1) \
        .reshape(b, t, SB_PROBE_LANES)
    ok = jnp.all((words & mask[None, :, :]) == mask[None, :, :], axis=2)
    return jnp.all(ok, axis=1) | (nsb == 0)


@jax.jit
def sb_plane_probe(plane, sbidx, mask, nsb):
    """Standalone jitted sb probe -> bool[B] (bench/parity entry)."""
    return plane_keep_sb(plane, sbidx, mask, nsb)


@dataclass
class StagedSBPlane:
    """One part column's split-block plane resident in HBM."""
    plane: object                  # jax uint32[Bp, LPp]
    nsb: object                    # jax int32[Bp]; 0 = always keep
    bp: int                        # padded block count
    nbytes: int

    def device_bytes(self) -> int:
        return self.nbytes


def stage_sb_plane(part, field: str, put) -> StagedSBPlane | None:
    """Upload the sealed part's packed split-block plane; None when the
    part has no v2 sidecar (or the column no sb filters) — the caller
    falls back to the classic plane staging."""
    from ..storage.filterindex import sb_plane_for_staging
    got = sb_plane_for_staging(part, field)
    if got is None:
        return None
    plane, nsb = pad_sb_plane(*got)
    return StagedSBPlane(plane=put(plane), nsb=put(nsb),
                         bp=plane.shape[0],
                         nbytes=plane.nbytes + nsb.nbytes)


def pad_sb_plane(plane: np.ndarray, nsb: np.ndarray
                 ) -> tuple[np.ndarray, np.ndarray]:
    """Pad to device tiles exactly like pad_plane: block axis to a
    PROBE_TILE_B multiple, lanes to a PROBE_LANE multiple (bucketing
    jit signatures against part-shape churn).  Pad blocks carry nsb=0
    (always keep) and all-zero lanes (safe to gather)."""
    b, lp = plane.shape
    bp = ((b + PROBE_TILE_B - 1) // PROBE_TILE_B) * PROBE_TILE_B
    lpp = max(PROBE_LANE,
              ((lp + PROBE_LANE - 1) // PROBE_LANE) * PROBE_LANE)
    if bp == b and lpp == lp:
        return plane, np.ascontiguousarray(nsb, dtype=np.int32)
    out = np.zeros((bp, lpp), dtype=np.uint32)
    out[:b, :lp] = plane
    ns = np.zeros(bp, dtype=np.int32)
    ns[:b] = nsb
    return out, ns


def pad_sb_idx(sbidx: np.ndarray, bp: int) -> np.ndarray:
    """Pad per-block sb lane bases to the padded block count."""
    b = sbidx.shape[0]
    if bp == b:
        return sbidx
    out = np.zeros((bp, sbidx.shape[1]), dtype=np.int32)
    out[:b] = sbidx
    return out
