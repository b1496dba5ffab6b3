"""Pallas-vs-XLA scan kernel micro-benchmark: one JSON line.

Runs on the chip only (the Mosaic lowering is what is being timed; an
interpreted kernel's rows/s would be a CPU number under a device
metric's name).  bench.py calls run() in-process after its five configs
— one process holds the chip."""

from __future__ import annotations

import json
import sys
import time

import numpy as np


def run(n_rows: int = 2_097_152) -> dict:
    n_rows = max(512, (n_rows // 512) * 512)   # pallas tile alignment
    width = 128
    import jax
    import jax.numpy as jnp

    from victorialogs_tpu.tpu import kernels as K
    from victorialogs_tpu.tpu.kernels_pallas import (match_scan_pallas,
                                                     pallas_ok)
    if jax.default_backend() != "tpu":
        raise SystemExit(f"bench_pallas.py: jax backend is "
                         f"{jax.default_backend()!r}, not tpu")

    rng = np.random.default_rng(7)
    mat = np.full((n_rows, width), 0xFF, dtype=np.uint8)
    base = np.frombuffer(
        (b"GET /api/items status=200 deadline exceeded retry ok " * 3),
        dtype=np.uint8)
    lens = rng.integers(20, width - 1, n_rows).astype(np.int32)
    take = min(base.shape[0], width - 1)
    mat[:, :take] = base[:take]
    assert pallas_ok(n_rows, width)

    rows_d = jax.device_put(jnp.asarray(mat))
    lens_d = jax.device_put(jnp.asarray(lens))
    pat = jnp.asarray(np.frombuffer(b"deadline", dtype=np.uint8))

    def timed(fn, reps=5):
        out = fn()          # warmup/compile
        np.asarray(out)
        t0 = time.time()
        for _ in range(reps):
            np.asarray(fn())
        return (time.time() - t0) / reps

    def xla():
        return K.match_scan(rows_d, lens_d, pat, 8, K.MODE_PHRASE, True,
                            True)

    def pallas():
        return match_scan_pallas(rows_d, lens_d, pat, 8, K.MODE_PHRASE,
                                 True, True)

    xla_s = timed(xla)
    pl_s = timed(pallas)
    same = bool(np.array_equal(np.asarray(xla()), np.asarray(pallas())))
    return {
        "backend": jax.default_backend(),
        "n_rows": n_rows,
        "xla_rows_per_sec": round(n_rows / xla_s),
        "pallas_rows_per_sec": round(n_rows / pl_s),
        "pallas_speedup_vs_xla": round(xla_s / pl_s, 2),
        "identical": same,
    }


def main() -> int:
    out = run(int(sys.argv[1]) if len(sys.argv) > 1 else 2_097_152)
    print(json.dumps(out))
    return 0 if out["identical"] else 1


if __name__ == "__main__":
    sys.exit(main())
