"""Pallas TPU scan kernels: VMEM-tiled string matching.

Why: the XLA `match_scan` kernel (kernels.py) expresses the windowed
compare as pat_len full-array slices, so XLA re-streams the (R, W) rows
matrix from HBM up to pat_len times.  This kernel tiles the matrix through
VMEM once — each (TILE_ROWS, W) tile is loaded a single time and ALL window
offsets are tested from on-chip memory — so HBM traffic drops from
pat_len×R×W to R×W and the scan becomes bandwidth-bound at one read of the
data (the VERDICT r1 #8 target).

Semantics are bit-identical to kernels.match_scan (same modes, same
word-boundary rules, 0xFF padding); tests/test_pallas.py diffs them in
interpret mode on CPU and chip_smoke.py diffs the Mosaic lowering on the
chip (tests/pallas_check.py --chip).  No query path selects this kernel:
production stages the u32 lane-major layout (kernels32.py).

Layout contract (caller pads; pallas_ok() checks):
  rows    uint8[R, W]   R % TILE_ROWS == 0, W % 128 == 0, 0xFF padded
  lengths int32[R]
returns bool[R].
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernels as K

TILE_ROWS = 512
LANE = 128


def vmem_spec(block, index_map):
    """A BlockSpec whose tile is DMA'd into VMEM (every kernel here)."""
    return pl.BlockSpec(block, index_map, memory_space=pltpu.VMEM)


def pallas_ok(r: int, w: int) -> bool:
    return r % TILE_ROWS == 0 and w % LANE == 0 and w >= LANE


def _scan_kernel(rows_ref, len_ref, pat_ref, out_ref, *, pat_len: int,
                 mode: int, starts_tok: bool, ends_tok: bool, w: int):
    """One (TILE_ROWS, W) tile: test every window offset from VMEM."""
    # Single VMEM read, then widen to int32: this Mosaic target supports
    # neither 8-bit vector compares nor 8-bit scalar extracts, so all
    # byte math runs as i32 lanes (the load itself stays uint8 in HBM —
    # traffic is still R×W bytes; widening happens on-chip).
    rows = rows_ref[:].astype(jnp.int32)    # int32[TR, W]
    tr = rows.shape[0]
    ff = jnp.int32(0xFF)
    # lengths/out ride as (TR, 1) column blocks: Mosaic requires the last
    # two block dims to be (8k, 128k) or equal to the array dims, so a
    # column vector is the only legal per-tile 1-value-per-row layout —
    # and it matches the sublane-resident layout of a lane-axis reduction.

    def shifted(j):
        # rows shifted left by j columns, tail-filled with 0xFF (never a
        # pattern byte, so windows running off the end can't match)
        if j == 0:
            return rows
        return jnp.concatenate(
            [rows[:, j:], jnp.full((tr, j), ff, dtype=jnp.int32)], axis=1)

    acc = jnp.ones((tr, w), dtype=jnp.bool_)
    for j in range(pat_len):
        # pattern rides as int32 (Mosaic only extracts 32-bit scalars);
        # cast the scalar back down for the byte compare
        acc = jnp.logical_and(acc, shifted(j) == pat_ref[0, j])

    lengths = len_ref[:, :]                 # int32[TR, 1] — stay 2-D:
    # Mosaic's layout inference crashes on rank-1 intermediates here

    if mode in (K.MODE_EXACT, K.MODE_EXACT_PREFIX):
        hit = acc[:, 0:1]
        if mode == K.MODE_EXACT:
            hit = jnp.logical_and(hit, lengths == pat_len)
        else:
            hit = jnp.logical_and(hit, lengths >= pat_len)
        out_ref[:, :] = hit.astype(jnp.int8)
        return

    def is_word(b):
        return ((b >= ord("a")) & (b <= ord("z"))) | \
               ((b >= ord("A")) & (b <= ord("Z"))) | \
               ((b >= ord("0")) & (b <= ord("9"))) | \
               (b == ord("_")) | ((b >= 0x80) & (b != 0xFF))

    if starts_tok and mode in (K.MODE_PHRASE, K.MODE_PREFIX):
        prev = jnp.concatenate(
            [jnp.full((tr, 1), ff, dtype=jnp.int32), rows[:, :w - 1]],
            axis=1)
        acc = jnp.logical_and(acc, jnp.logical_not(is_word(prev)))
    if ends_tok and mode == K.MODE_PHRASE:
        nxt = shifted(pat_len)
        acc = jnp.logical_and(acc, jnp.logical_not(is_word(nxt)))

    # reduce through int32 — Mosaic rejects the bool any() relayout
    anyhit = jnp.max(acc.astype(jnp.int32), axis=1, keepdims=True)
    hit = jnp.logical_and(anyhit > 0, lengths >= pat_len)
    out_ref[:, :] = hit.astype(jnp.int8)


@partial(jax.jit, static_argnames=("pat_len", "mode", "starts_tok",
                                   "ends_tok", "interpret"))
def match_scan_pallas(rows: jnp.ndarray, lengths: jnp.ndarray,
                      pattern: jnp.ndarray, pat_len: int, mode: int,
                      starts_tok: bool, ends_tok: bool,
                      interpret: bool = False) -> jnp.ndarray:
    """Pallas drop-in for kernels.match_scan on aligned shapes."""
    r, w = rows.shape
    assert pallas_ok(r, w), (r, w)
    g = r // TILE_ROWS
    lengths_col = lengths.reshape(r, 1).astype(jnp.int32)
    pat128 = jnp.zeros((1, LANE), dtype=jnp.int32)
    pat128 = pat128.at[0, :pat_len].set(pattern[:pat_len].astype(jnp.int32))

    kernel = partial(_scan_kernel, pat_len=pat_len, mode=mode,
                     starts_tok=starts_tok, ends_tok=ends_tok, w=w)

    spec = vmem_spec
    out = pl.pallas_call(
        kernel,
        grid=(g,),
        in_specs=[
            spec((TILE_ROWS, w), lambda i: (i, 0)),
            spec((TILE_ROWS, 1), lambda i: (i, 0)),
            spec((1, LANE), lambda i: (0, 0)),
        ],
        out_specs=spec((TILE_ROWS, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, 1), jnp.int8),
        interpret=interpret,
    )(rows, lengths_col, pat128)
    return out.reshape(r).astype(jnp.bool_)


def pad_for_pallas(mat: np.ndarray, lengths: np.ndarray
                   ) -> tuple[np.ndarray, np.ndarray]:
    """Pad a staged (R, W) matrix to the pallas layout contract."""
    r, w = mat.shape
    rp = ((r + TILE_ROWS - 1) // TILE_ROWS) * TILE_ROWS
    wp = max(LANE, ((w + LANE - 1) // LANE) * LANE)
    if rp == r and wp == w:
        return mat, lengths
    out = np.full((rp, wp), 0xFF, dtype=np.uint8)
    out[:r, :w] = mat
    lens = np.zeros(rp, dtype=np.int32)
    lens[:r] = lengths[:r]
    return out, lens
