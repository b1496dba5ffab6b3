"""Multi-chip query execution: blocks sharded over a device mesh, stats
partials reduced over ICI.

This maps the reference's two parallelism mechanisms (SURVEY.md §2.6) onto a
TPU mesh:

- intra-query data parallelism (N workers over a block channel —
  storage_search.go:1035-1067) -> a `blocks` mesh axis: each device scans its
  shard of the staged block batch;
- the stats remote/local pushdown split (pipe_stats.go:55-60, mergeState over
  exported states) -> `jax.lax.psum` over ICI: per-device partial aggregates
  are reduced in-network, the host only finalizes.

The step below is the distributed analogue of a training step: jit once over
the mesh, run per staged batch.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs import tracing
from ..tpu import kernels as K
from ..tpu.batch import BatchRunner

BLOCK_AXIS = "blocks"


def make_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    """Build the block-parallel mesh.

    devices: explicit device list (e.g. a virtual CPU world); defaults to
    jax.devices().  Raises when fewer than n_devices are attached instead of
    silently building a smaller mesh — callers that want a virtual mesh must
    provision one (see __graft_entry__.dryrun_multichip).
    """
    devs = list(devices) if devices is not None else jax.devices()
    if n_devices is not None:
        if len(devs) < n_devices:
            raise RuntimeError(
                f"need {n_devices} devices, have {len(devs)}; provision a "
                f"virtual CPU world with JAX_PLATFORMS=cpu "
                f"XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{n_devices}")
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (BLOCK_AXIS,))


@partial(jax.jit, static_argnames=("pat_len", "mode", "starts_tok",
                                   "ends_tok", "num_buckets", "mesh"))
def distributed_scan_count(mesh, rows, lengths,
                           bucket_ids, pattern, pat_len: int, mode: int,
                           starts_tok: bool, ends_tok: bool,
                           num_buckets: int):
    """One distributed query step.

    rows: uint8[B, R, W] — B fixed-width blocks sharded across the mesh's
    block axis; lengths: int32[B, R];
    bucket_ids: int32[B] — per-BLOCK stats group (e.g. the block's time
    bucket; blocks are the stats unit here since rows within a block share
    a stream and close timestamps);
    returns (match bitmaps bool[B, R], total count, per-bucket counts) with
    the two aggregates psum-reduced across devices.
    """

    @jax.named_scope("match_scan")
    def per_block(rw, lens):
        bm = K.match_scan(rw, lens, pattern, pat_len, mode, starts_tok,
                          ends_tok)
        return bm, jnp.sum(bm.astype(jnp.int32))

    def shard_fn(rows, lengths, bucket_ids):
        bms, cnts = jax.vmap(per_block)(rows, lengths)
        # stats partials merge over ICI — the psum analogue of mergeState
        total = jax.lax.psum(jnp.sum(cnts), BLOCK_AXIS)
        # per-bucket counts: one-hot matmul instead of segment ops (scatter
        # serializes on TPU; a (B, num_buckets) one-hot contraction rides
        # the MXU instead)
        onehot = jax.nn.one_hot(bucket_ids, num_buckets, dtype=jnp.float32)
        hist = jax.lax.psum(
            jnp.einsum("b,bk->k", cnts.astype(jnp.float32), onehot),
            BLOCK_AXIS)
        return bms, total, hist.astype(jnp.int32)

    spec = P(BLOCK_AXIS)
    return jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=(spec, P(), P()))(rows, lengths, bucket_ids)


def stage_block_batch(blocks: list[tuple[np.ndarray, np.ndarray, np.ndarray]],
                      n_devices: int):
    """Pad a list of (arena, offsets, lengths) into fixed-width batch
    tensors whose block count divides the mesh size.  Returns
    (rows uint8[B, R, W], lengths int32[B, R], rows_bucket)."""
    from ..tpu.kernels import pad_bucket
    from ..tpu.layout import to_fixed_width, row_width_bucket
    rb = pad_bucket(max(max((o.shape[0] for _a, o, _l in blocks),
                            default=1), 1), minimum=1024)
    w = max(row_width_bucket(int(l.max()) if l.size else 0)
            for _a, _o, l in blocks)
    b = len(blocks)
    bpad = ((b + n_devices - 1) // n_devices) * n_devices
    rows = np.full((bpad, rb, w), 0xFF, dtype=np.uint8)
    lengths = np.zeros((bpad, rb), dtype=np.int32)
    for i, (a, o, l) in enumerate(blocks):
        mat, _wi, _overflow = to_fixed_width(a, o, l, rb, width=w)
        rows[i] = mat
        lengths[i, :l.shape[0]] = np.minimum(l, w - 1).astype(np.int32)
    return rows, lengths, rb


def shard_batch(mesh: Mesh, *arrays):
    """Device-put batch tensors with the block axis sharded over the mesh."""
    sharding = NamedSharding(mesh, P(BLOCK_AXIS))
    return tuple(jax.device_put(a, sharding) for a in arrays)


# ---------------- the multi-chip product runner ----------------

class MeshBatchRunner(BatchRunner):
    """BatchRunner over a device mesh: the PRODUCT multi-chip query path.

    Staged arrays (string planes, numeric columns, bucket ids, masks)
    are device_put with their row axis sharded over the mesh, and the
    fused programs run SPMD: shard_mapped over the row axis, so a fused
    query is ONE collective dispatch across the whole mesh.  Each device
    scans its row stripe; stats partials ride psum/pmin/pmax over ICI
    and only the (7, buckets) reduced result reaches the host.

    Single-device behavior is identical to BatchRunner (the sharding
    degenerates); engine.searcher drives both through the same interface.
    """

    def __init__(self, mesh: Mesh | None = None, **kw):
        mesh = mesh if mesh is not None else make_mesh()
        super().__init__(devices=list(mesh.devices.flat), **kw)
        # the mesh runner exists to run SPMD — the whole point is ICI
        # reductions, so the per-part cost gate never routes it to host
        # (an explicit VL_COST_FORCE still wins)
        if not self.cost.force:
            self.cost.force = "device"
        self.mesh = mesh
        self.ndev = int(self.mesh.devices.size)
        self.stats_shards = self.ndev
        self._row_sharding = NamedSharding(self.mesh, P(BLOCK_AXIS))
        self._replicated = NamedSharding(self.mesh, P())

    def pallas_enabled(self) -> bool:
        # the Pallas variants are single-device programs, compiled and
        # diffed on one chip only; under shard_map the XLA twins serve
        return False

    def sweeps_bounded(self) -> bool:
        # a stripe under shard_map runs the plane kernel's body directly,
        # over the column's whole width
        return False

    def _put(self, arr, row_axis: int = 0):
        # shard the row axis when it divides evenly (stats layouts always
        # do; string-staging row buckets do for power-of-two mesh sizes),
        # else replicate — correctness never depends on the placement.
        # row_axis=1: the uint32[W/4, R/128, 128] planes of the string
        # staging, striped by whole rows of 128.
        striped = arr.shape[row_axis] % self.ndev == 0
        # a replicated array is handed to every device
        self._bump("h2d_bytes_total",
                   arr.nbytes * (1 if striped else self.ndev))
        if striped:
            if row_axis == 0:
                return jax.device_put(arr, self._row_sharding)
            return jax.device_put(
                arr, NamedSharding(self.mesh, P(None, BLOCK_AXIS)))
        # never on a power-of-two mesh of up to eight devices (row
        # buckets are 1024-multiples, eight rows of 128 a plane);
        # counted so a mesh that does replicate its rows says so on
        # /metrics instead of quietly holding ndev copies
        self._bump("replicated_row_puts")
        return jax.device_put(arr, self._replicated)

    def _put_replicated(self, arr):
        # block-axis arrays (bloom planes / keep-mask operands): every
        # shard probes the full block axis, so these never stripe —
        # matches the P() in_specs the fused mesh dispatch declares for
        # non-row args.  Every device is handed its own copy.
        self._bump("h2d_bytes_total", arr.nbytes * self.ndev)
        return jax.device_put(arr, self._replicated)

    def _trace_collective(self) -> None:
        """Mesh attribution on the active trace: fused dispatches here
        are ONE collective program over every device (psum/pmin/pmax
        over ICI), which a trace reader must be able to tell apart from
        the single-chip dispatch counts."""
        sp = tracing.current_span()
        if sp.enabled:
            sp.add("mesh_collective_dispatches")
            sp.set("mesh_devices", self.ndev)

    def _dispatch_fused(self, name, prog, strides, nb, n_values, blk,
                        cand_packed, seg_map, ids_tuple, values_tuple,
                        args):
        from ..tpu.fused import fused_mesh_program
        self._trace_collective()
        return fused_mesh_program(name)(
            self.mesh, BLOCK_AXIS, prog, strides, nb, n_values, blk,
            cand_packed, seg_map, ids_tuple, values_tuple, args)

    def _dispatch_filter(self, name, prog, blk, cand_packed, args):
        # row-query fused filter under shard_map: each device evaluates
        # its row stripe, packed (definite, maybe) bits concatenate over
        # the row axis.  Layouts are padded to STATS_CHUNK * ndev rows
        # (stats_shards), so stripes are whole and byte-aligned — this
        # holds for packed super-parts too (their layout rides the same
        # padding).  The async window (tpu/pipeline.py) drives this
        # exactly like the single-chip runner: submission issues the
        # collective dispatch, harvest materializes in order.
        from ..tpu.fused import filter_mesh_program
        self._trace_collective()
        return filter_mesh_program(name)(self.mesh, BLOCK_AXIS, prog,
                                         blk, cand_packed, args)
