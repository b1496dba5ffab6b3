"""Phase-level timing of the device query path on real hardware.

Breaks BASELINE config 3 (regex over every row) into its constituent
costs: staging upload, match kernel, bitmap download, stats dispatch,
and the full run_query e2e — so optimization effort goes where the
milliseconds are.  Run directly on the chip: python tools/profile_device.py
"""
from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("BENCH_ROWS", "4000000")


def t(label, fn, reps=3):
    fn()  # warmup
    times = []
    for _ in range(reps):
        t0 = time.time()
        fn()
        times.append(time.time() - t0)
    best = min(times)
    print(f"{label:42s} {best*1e3:8.1f} ms")
    return best


def main():
    import tempfile
    import jax
    import jax.numpy as jnp
    import bench
    from victorialogs_tpu.tpu.batch import BatchRunner
    from victorialogs_tpu.tpu import kernels as K
    from victorialogs_tpu.engine.searcher import run_query_collect

    print(f"backend={jax.default_backend()}")
    tmp = tempfile.mkdtemp(prefix="vlprof")
    t0 = time.time()
    storage, ten = bench.build_storage(tmp)
    print(f"gen: {time.time()-t0:.1f}s")
    float(jnp.sum(jnp.ones(8)))  # backend up before any timer starts

    runner = BatchRunner()
    pt = storage._get_partition(bench.T0 // bench.NS // 86400)
    parts = pt.ddb.small_parts + pt.ddb.big_parts
    part = max(parts, key=lambda p: p.num_rows)
    n = part.num_rows
    print(f"rows={n} blocks={part.num_blocks}")

    # 1. staging (host decode + upload) — warm-path cost (t() always
    # runs one warmup call first, so this is the repeat-staging number)
    from victorialogs_tpu.tpu.batch import stage_part_column
    t("stage_part_column _msg (warm, incl upload)",
      lambda: stage_part_column(part, "_msg"), reps=1)
    spc = runner.stage_part(part, "_msg")
    print(f"staged width={spc.width} nbytes={spc.nbytes/1e6:.0f}MB")

    # 2. raw kernel: dispatch+sync (no download)
    pat = jnp.asarray(np.frombuffer(b"deadline", dtype=np.uint8))
    t("match_scan dispatch+sync", lambda: K.match_scan(
        spc.rows, spc.lengths, pat, 8, K.MODE_PHRASE, True, True
    ).block_until_ready())

    # 3. kernel + full bool download
    t("match_scan + download bool[R]", lambda: np.array(K.match_scan(
        spc.rows, spc.lengths, pat, 8, K.MODE_PHRASE, True, True)))

    # 3b. packed download (bits)
    def packed():
        r = K.match_scan(spc.rows, spc.lengths, pat, 8, K.MODE_PHRASE,
                         True, True)
        rp = jnp.packbits(r.astype(jnp.uint8))
        return np.array(rp)
    t("match_scan + packbits download", packed)

    # 4. ordered pair (the regex config's kernel)
    a = jnp.asarray(np.frombuffer(b"dead", dtype=np.uint8))
    b = jnp.asarray(np.frombuffer(b"exceeded", dtype=np.uint8))
    t("match_ordered_pair + download", lambda: [np.array(x) for x in
      K.match_ordered_pair(spc.rows, spc.lengths, a, 4, b, 8)])

    # 5. mask upload cost (stats path re-upload)
    from victorialogs_tpu.tpu.kernels import STATS_CHUNK
    mask = np.zeros(((n + STATS_CHUNK - 1)//STATS_CHUNK)*STATS_CHUNK, dtype=bool)
    mask[::7] = True
    t("mask upload bool[R]", lambda: jnp.asarray(mask).block_until_ready())

    # 6. count-only stats dispatch (ids all-zero)
    ids = jnp.zeros(mask.shape[0], dtype=jnp.int32)
    mj = jnp.asarray(mask)
    t("stats_bucket_count dispatch", lambda: np.array(
        K.stats_bucket_count((ids,), (1,), mj, 1)))

    # 7. e2e configs
    for q, label in [
        ('_msg:~"dead.*exceeded" | stats count() c', "e2e regex_full dev"),
        ('"deadline exceeded" | stats count() c', "e2e phrase dev"),
    ]:
        t(label, lambda q=q: run_query_collect(
            storage, [ten], q, timestamp=bench.T0, runner=runner))
        t(label.replace("dev", "cpu"), lambda q=q: run_query_collect(
            storage, [ten], q, timestamp=bench.T0, runner=None))

    storage.close()


if __name__ == "__main__":
    main()
