"""The plane kernels compiled for the chip, without the chip.

The TPU's compiler is installed here and compiles for a v5e that is
described, not attached: what Mosaic refuses (a slice off the tiling, a
loop state it cannot carry, too much VMEM) it refuses in these tests,
where interpret mode (tests/test_kernels32.py) passes.  Nothing runs, so
nothing here is a result or a time.  The topology is described inside a
fixture of THIS file only: the process that describes it holds the TPU
library until it exits (on-chip-measurement guide, section 2).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from victorialogs_tpu.tpu import kernels as K
from victorialogs_tpu.tpu import kernels32 as K32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described device's programs cannot be read back from the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def as_on_the_chip(monkeypatch):
    """The launcher asks for the backend; the chip's process sees tpu."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _shapes(width, rows, sharding, lens_sharding=None):
    return (jax.ShapeDtypeStruct((width // 4, rows // 128, 128),
                                 jnp.uint32, sharding=sharding),
            jax.ShapeDtypeStruct((rows,), jnp.int32,
                                 sharding=lens_sharding or sharding))


SCANS = [(17, K.MODE_PHRASE, True, True, False),
         (4, K.MODE_PREFIX, True, False, False),
         (8, K.MODE_SUBSTRING, False, False, False),
         (17, K.MODE_PHRASE, True, True, True),
         (9, K.MODE_EXACT, False, False, False),
         (64, K.MODE_PHRASE, True, True, False)]


# the benchmark's `_msg` (W=128, parts of millions of rows), the
# narrowest and the widest column, the smallest row bucket, and the
# largest layout the device takes (stats_device.MAX_STAT_ROWS)
@pytest.mark.parametrize("width,rows", [(128, 1 << 21), (32, 1 << 21),
                                        (2048, 1 << 16), (64, 1024),
                                        (128, 16 << 20)])
def test_every_leaf_kind_compiles_through_mosaic(topo, as_on_the_chip,
                                                 width, rows):
    one = SingleDeviceSharding(topo.devices[0])
    lanes, lens = _shapes(width, rows, one)
    for pat_len, mode, st, et, fold in SCANS:
        if pat_len > width - 1:
            continue
        pat = jax.ShapeDtypeStruct((pat_len,), jnp.uint8, sharding=one)
        text = K32.match_scan_t.lower(
            lanes, lens, pat, pat_len, mode, st, et, fold
        ).compile().as_text()
        assert text.count("tpu_custom_call") == 1, (pat_len, mode)
    pa = jax.ShapeDtypeStruct((4,), jnp.uint8, sharding=one)
    pb = jax.ShapeDtypeStruct((8,), jnp.uint8, sharding=one)
    text = K32.match_ordered_pair_t.lower(lanes, lens, pa, 4, pb,
                                          8).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    for op in ("reverse(", " sort("):
        assert op not in text, op


def _pallas_calls(jaxpr):
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            yield e
        for v in e.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield from _pallas_calls(inner)


@pytest.mark.parametrize("rows", [1 << 21, 16 << 20])
def test_the_tile_table_is_an_smem_block_a_grid_step(as_on_the_chip, rows):
    """Each sweep tile's longest row reaches the kernel as ONE SMEM
    block a grid step, of ts // sub words whatever R is (the table of a
    16M-row layout is never prefetched whole); the pattern's chunks stay
    the one scalar-prefetched operand."""
    from victorialogs_tpu.tpu.stats_device import MAX_STAT_ROWS
    assert rows <= MAX_STAT_ROWS
    lanes, lens = _shapes(128, rows, None)
    pat = jax.ShapeDtypeStruct((17,), jnp.uint8)
    ts, sub = K32.sweep_blocks(32, rows // 128)
    jaxpr = jax.make_jaxpr(lambda l, n, p: K32.match_ordered_pair_t(
        l, n, p[:4], 4, p[4:], 13)[0] | K32.match_scan_t(
        l, n, p, 17, K.MODE_PHRASE, True, True))(lanes, lens, pat).jaxpr
    calls = list(_pallas_calls(jaxpr))
    assert calls
    for e in calls:
        gm = e.params["grid_mapping"]
        assert gm.num_index_operands == 1
        assert gm.grid == (rows // 128 // ts,)
        smem = [bm for bm in gm.block_mappings
                if str(bm.block_aval.memory_space) == "smem"]
        assert len(smem) == 1
        bm = smem[0]
        assert bm.array_aval.shape == (rows // 128 // ts, 1, ts // sub)
        assert bm.block_aval.shape == (1, ts // sub) == (1, 8)


def test_a_stripe_under_a_mesh_axis_runs_the_body_directly(
        topo, as_on_the_chip):
    """MeshBatchRunner's fused programs: under shard_map the launcher
    must leave Mosaic out (the body is plain XLA a device)."""
    mesh = Mesh(np.array(topo.devices).reshape(4), ("blocks",))
    lanes, lens = _shapes(128, 1 << 20,
                          NamedSharding(mesh, P(None, "blocks")),
                          NamedSharding(mesh, P("blocks")))
    pat = jax.ShapeDtypeStruct((17,), jnp.uint8,
                               sharding=NamedSharding(mesh, P()))

    def local(l, n, p):
        hit = K32.match_scan_t(l, n, p, 17, K.MODE_PHRASE, True, True)
        d, v = K32.match_ordered_pair_t(l, n, p[:4], 4, p[4:12], 8)
        return jax.lax.psum(jnp.sum(hit & ~d | v, dtype=jnp.int32),
                            "blocks")

    text = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P(None, "blocks"), P("blocks"), P()),
        out_specs=P())).lower(lanes, lens, pat).compile().as_text()
    assert "tpu_custom_call" not in text
    assert "all-reduce" in text


@pytest.mark.parametrize("query,kernels", [
    ('_time:[2025-07-28T00:02:00Z, 2025-07-28T00:17:00Z) "deadline '
     'exceeded" | stats by (_time:5m) count() c', 1),
    ('_msg:~"dead.*exceeded" | stats count() c', 1),
    ('lvl:in(error, warn) "GET" | stats count() c', 3)],
    ids=["time_phrase", "pair", "in_and_phrase"])
def test_a_fused_program_reads_its_operand_block_on_the_chip(
        topo, tmp_path, monkeypatch, query, kernels):
    """A whole fused program as the chip's process compiles it: the
    operand block (int32, the call's one host operand) feeds the time
    bounds and, through a static slice and a bitcast, the Pallas call's
    scalar-prefetched pattern chunks.  Operand shapes are those of a
    real dispatch on jax-CPU, placed on the described chip."""
    import operand_cases as OC
    from victorialogs_tpu.engine.searcher import run_query_collect
    from victorialogs_tpu.tpu import fused
    from victorialogs_tpu.tpu.batch import BatchRunner
    one = SingleDeviceSharding(topo.devices[0])
    s = OC.make_storage(str(tmp_path))
    try:
        seen = OC.record_launches(monkeypatch)
        run_query_collect(s, [OC.TEN], query, timestamp=OC.T0,
                          runner=BatchRunner())
    finally:
        s.close()
    # the launcher asks for the backend (as_on_the_chip, after the
    # jax-CPU run); that run traced the scan's inner jit with the direct
    # launcher, and a trace is cached by shapes, not by backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.clear_caches()
    _name, *args = seen[0]
    (blk,) = OC.host_operands(args)
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)
        if hasattr(a, "shape") else a, args)
    text = jax.jit(fused._fused_dispatch, static_argnums=(0, 1, 2, 3)) \
        .lower(*shapes).compile().as_text()
    assert blk.dtype == np.int32
    assert text.count("tpu_custom_call") == kernels
