"""Microbench: round-3 byte kernel vs round-4 u32-lane kernel.

Run with JAX_PLATFORMS=cpu for the host backend, or on the chip through
the chip tool.  Reports p50 of N reps after a warmup compile."""
import os, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np
import jax
import jax.numpy as jnp
from victorialogs_tpu.tpu import kernels as K
from victorialogs_tpu.tpu import kernels32 as K32
from victorialogs_tpu.tpu.layout import to_lanes32

R = int(os.environ.get("BK_ROWS", 1 << 20))
W = int(os.environ.get("BK_W", 128))
REPS = int(os.environ.get("BK_REPS", 5))

rng = np.random.default_rng(7)
mat = rng.integers(32, 127, size=(R, W), dtype=np.uint8)
lens = np.full(R, W - 1, dtype=np.int32)
lanes = to_lanes32(mat)
matj, lensj, lanesj = jnp.asarray(mat), jnp.asarray(lens), jnp.asarray(lanes)

def timeit(fn):
    fn().block_until_ready()
    ts = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        fn().block_until_ready()
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2]

for pat_len in (4, 8, 16, 32):
    pat = jnp.asarray(rng.integers(32, 127, size=pat_len, dtype=np.uint8))
    for mode, st, et, name in [
            (K.MODE_SUBSTRING, False, False, "substr"),
            (K.MODE_PHRASE, True, True, "phrase"),
            (K.MODE_EXACT, False, False, "exact")]:
        t_old = timeit(lambda: K.match_scan(matj, lensj, pat, pat_len,
                                            mode, st, et))
        t_new = timeit(lambda: K32.match_scan_t(lanesj, lensj, pat,
                                                pat_len, mode, st, et))
        gbps = R * W / t_new / 1e9
        print(f"L={pat_len:3d} {name:7s} old={t_old*1e3:8.2f}ms "
              f"new={t_new*1e3:8.2f}ms speedup={t_old/t_new:6.2f}x "
              f"eff={gbps:6.1f} GB/s")
