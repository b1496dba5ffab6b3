"""The plane scan's own throughput on the chip: ns a row and the share
of the chip's memory bandwidth, for the leaf kinds the served path runs.

    chiprun -- python tools/bench_kernels32.py [--rows N] [--width W]

One staged column of R rows (2M by default) of W bytes (128), rows like
chip_smoke.py's access line with the phrase planted in one row of 16.
Each kind is timed as CALLS jitted calls queued back to back and waited
for once (the device runs them in order, so the host's dispatch latency
is not in the figure), median of REPS.  A row is W + 4 staged bytes: the
column and its length; the peak is benchmark/peaks.json's.  Off the chip the tool refuses to print a time;
`--rehearsal` runs it on jax-CPU at a small size to prove the paths and
prints counts only.
"""

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CALLS = 8
REPS = 5


def column(rows: int, width: int, seed: int) -> tuple:
    """(uint8[rows, width] 0xFF-padded, int32[rows] lengths)."""
    rng = np.random.default_rng(seed)
    words = [b"GET", b"/api/v1/items", b"status=200", b"upstream", b"took",
             b"Deadline Exceeded", b"deadline exceeded", b"dead", b"ms",
             b"retry", b"ok", b"exceeded\nquota"]
    lines = []
    for i in range(256):
        n = int(rng.integers(5, 9))
        pick = [words[int(k)] for k in rng.integers(0, 5, size=n)]
        if i % 16 == 0:
            pick.insert(int(rng.integers(0, n)), words[5 + (i // 16) % 7])
        lines.append(b" ".join(pick)[:width - 1])
    mat = np.full((256, width), 0xFF, dtype=np.uint8)
    lens = np.zeros(256, dtype=np.int32)
    for i, b in enumerate(lines):
        mat[i, :len(b)] = np.frombuffer(b, dtype=np.uint8)
        lens[i] = len(b)
    order = rng.integers(0, 256, size=rows)
    return mat[order], lens[order]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1 << 21)
    ap.add_argument("--width", type=int, default=128)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        args.rows = min(args.rows, 1 << 14)

    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    from victorialogs_tpu.tpu import kernels as K
    from victorialogs_tpu.tpu import kernels32 as K32
    from victorialogs_tpu.tpu.layout import to_lanes32

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearsal:
        print(f"bench_kernels32: no chip (platform={dev.platform}); "
              "a time from here would not be a device time",
              file=sys.stderr)
        return 3
    if not args.rehearsal:
        # the benchmark's table of peaks; a device it lacks is an error
        with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
            hbm_bytes_per_s = json.load(f)[dev.device_kind]["values"][
                "hbm_bytes_per_s"]

    mat, lens = column(args.rows, args.width, args.seed)
    lanes = jnp.asarray(to_lanes32(mat))
    lensj = jnp.asarray(lens)

    def pat(b: bytes):
        return jnp.asarray(np.frombuffer(b, dtype=np.uint8))

    def scan(p: bytes, mode: int, st: bool, et: bool, fold: bool = False):
        pj = pat(p)
        fn = jax.jit(lambda l, n: jnp.sum(K32.match_scan_t(
            l, n, pj, len(p), mode, st, et, fold), dtype=jnp.int32))
        return lambda: fn(lanes, lensj)

    def pair(a: bytes, b: bytes):
        aj, bj = pat(a), pat(b)

        def both(l, n):
            d, v = K32.match_ordered_pair_t(l, n, aj, len(a), bj, len(b))
            # rows decided on the device + rows a newline sends to the host
            return jnp.sum(d, dtype=jnp.int32) + jnp.sum(v, dtype=jnp.int32)
        fn = jax.jit(both)
        return lambda: fn(lanes, lensj)

    kinds = [
        ("phrase", scan(b"deadline exceeded", K.MODE_PHRASE, True, True)),
        ("prefix", scan(b"dead", K.MODE_PREFIX, True, False)),
        ("substring", scan(b"line exc", K.MODE_SUBSTRING, False, False)),
        ("phrase_fold", scan(b"deadline exceeded", K.MODE_PHRASE, True,
                             True, True)),
        ("pair", pair(b"dead", b"exceeded")),
    ]
    row_bytes = args.width + 4
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "rows": args.rows, "width": args.width, "kinds": {}}
    for name, call in kinds:
        hits = int(call())                     # compiles
        line = {"hits": hits}
        if not args.rehearsal:
            times = []
            for _ in range(REPS):
                t0 = time.perf_counter()
                for _ in range(CALLS - 1):
                    call()
                call().block_until_ready()
                times.append((time.perf_counter() - t0) / CALLS)
            t = statistics.median(times)
            line["ms_a_call"] = t * 1e3
            line["ns_a_row"] = t * 1e9 / args.rows
            line["hbm_share_pct"] = \
                100.0 * args.rows * row_bytes / t / hbm_bytes_per_s
        out["kinds"][name] = line
        print(name, json.dumps(line), flush=True)
    if not args.rehearsal:
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/bench_kernels32.json", "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
