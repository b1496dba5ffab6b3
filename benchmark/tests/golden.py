#!/usr/bin/env python3
"""The fingerprints of what a cell's run is made of: rows, streams, the
blocks the build hands to storage, requests, reference answers, required
bytes.  One recipe, two ways to reach the harness:

    python3 benchmark/tests/golden.py --parent <checkout>/benchmark

prints them for a tree from before the schema seam (PR 27 and earlier:
`gen.row_fields`, `gen.Text`, a build job a stream), and
test_schema_seam.py computes them through `gen.load_schema` and holds
them to the constants that the first printed.  A digest that differs
means the same --seed no longer gives the same bytes.
"""

import hashlib
import json
import os
import pickle
import sys

SEEDS = (11, 2147483777, 333)
CONFIG = "baseline-1chip"
TRAFFIC = ("adhoc_scan", "needle")
EDGE = 4096                 # rows hashed at each end of every part
BLOCK_PARTS = (0, 13)       # rehearsal-size parts whose built blocks are hashed


def _sha(chunks) -> str:
    h = hashlib.sha256()
    for c in chunks:
        h.update(c if isinstance(c, bytes) else str(c).encode())
        h.update(b"\0")
    return h.hexdigest()


def rows_digest(api, config, seed: int) -> str:
    """`_time` and every stored column of the first and last EDGE rows of
    every part at the cells' full layout; the stream ids and tags."""
    import numpy as np
    layout = api.layout(config, 1.0)

    def chunks():
        for p in layout.parts:
            for lo in (p["lo"], max(p["lo"], p["hi"] - EDGE)):
                idx = np.arange(lo, min(lo + EDGE, p["hi"]), dtype=np.int64)
                yield layout.times(idx).tobytes()
                for name, col in api.columns(config, layout, idx, seed).items():
                    yield name
                    yield col.dtype.str
                    yield col.tobytes()
        yield repr(api.stream_ids(layout))
    return _sha(chunks())


def blocks_digest(api, config, seed: int) -> str:
    """The blocks of two rehearsal-size parts as the build gives them to
    storage, in the order it gives them."""
    layout = api.layout(config, api.scale)

    def chunks():
        for i in BLOCK_PARTS:
            for b in api.part_blocks(config, layout, seed, i):
                yield repr(b.stream_id)
                yield b.stream_tags_str
                yield b.timestamps.tobytes()
                yield repr(b.const_columns)
                yield pickle.dumps(b.columns, protocol=4)
    return _sha(chunks())


def requests_digest(api, config, traffic: dict, seed: int) -> tuple:
    """Every request of the 30 s schedule, window and warm-up aliases:
    (count, digest of due instant, class, endpoint, text, time range)."""
    layout = api.layout(config, 1.0)
    sched = api.schedule(traffic, 30.0)

    def chunks():
        for prefix in ("q", "w"):
            for k, (due, cls) in enumerate(sched):
                r = api.make_request(traffic, layout, seed, k, cls, prefix)
                yield json.dumps([repr(due), cls, r["endpoint"], r["query"],
                                  r["t_range"], r["answer"]])
    return len(sched), _sha(chunks())


def answers_digest(api, config, traffic: dict, seed: int) -> str:
    """The reference's normal-form answers to the requests a run of
    `seed` would sample, at rehearsal scale."""
    layout = api.layout(config, api.scale)
    recs = [{"req": api.make_request(traffic, layout, seed, k, cls, "q"),
             "due": 0.0, "done": 0.0, "status": 200, "body": b""}
            for k, (_due, cls) in enumerate(api.schedule(traffic, 30.0))]
    ref = api.reference(layout, seed)
    picked = api.pick_sample(recs, seed, int(traffic["check_sample"]))

    def chunks():
        for k, r in sorted(picked.items()):
            spec = traffic["classes"][r["req"]["cls"]]["reference"]
            yield repr((k, ref.answer(r["req"], spec)))
    return _sha(chunks())


def required_sums(api, config, traffic: dict, seed: int) -> list:
    """[rows, bytes] that bytes.required sums to over the schedule."""
    layout = api.layout(config, 1.0)
    rows = nbytes = 0
    for k, (_due, cls) in enumerate(api.schedule(traffic, 30.0)):
        r = api.make_request(traffic, layout, seed, k, cls, "q")
        w = api.required(config, layout, traffic["classes"][cls],
                         r["t_range"])
        rows, nbytes = rows + w["rows"], nbytes + w["bytes"]
    return [rows, nbytes]


def fingerprints(api, bench_dir: str) -> dict:
    config = api.load_config(os.path.join(bench_dir, "configs",
                                          CONFIG + ".json"))
    out = {}
    for seed in SEEDS:
        fp = {"rows": rows_digest(api, config, seed),
              "blocks": blocks_digest(api, config, seed)}
        for name in TRAFFIC:
            traffic = api.load_json(os.path.join(bench_dir, "traffic",
                                                 name + ".json"))
            n, digest = requests_digest(api, config, traffic, seed)
            fp[name] = {"requests": n, "request_texts": digest,
                        "answers": answers_digest(api, config, traffic, seed),
                        "required": required_sums(api, config, traffic, seed)}
        out[str(seed)] = fp
    return out


class _Api:
    """What the recipe asks of the harness, from the modules on sys.path."""

    def __init__(self):
        import bytes as yardstick_bytes
        import gen
        import partbuild
        import reference
        import run
        import traffic
        self.gen, self.partbuild, self._texts = gen, partbuild, {}
        self.scale = gen.REHEARSAL_SCALE
        self.load_config = self.load_json = gen.load_json
        self.layout = gen.Layout
        self.stream_ids = partbuild.stream_ids
        self.schedule, self.make_request = traffic.schedule, traffic.make_request
        self.reference, self.pick_sample = reference.Reference, run.pick_sample
        self.required = yardstick_bytes.required


class ParentApi(_Api):
    """The harness before the seam: the six fields live in gen.py."""

    def columns(self, config, layout, idx, seed):
        if not self._texts:
            self._texts[None] = self.gen.Text()
        return self._texts[None].columns(
            idx, self.gen.row_fields(idx, seed), layout.streams)

    def part_blocks(self, config, layout, seed, i):
        sids, _tags = self.partbuild.stream_ids(layout)
        by_sid = sorted(range(layout.streams), key=lambda k: sids[k])
        return [b for k in by_sid for b in self.partbuild.part_blocks(
            (config, self.scale, seed, i, k))]


class SeamApi(_Api):
    """The harness since the seam: the rows are the schema module's."""

    def __init__(self):
        super().__init__()
        self.load_config = self.gen.load_config

    def columns(self, config, layout, idx, seed):
        schema = layout.schema
        if schema not in self._texts:
            self._texts[schema] = schema.Text(config)
        return self._texts[schema].columns(
            idx, schema.row_fields(idx, seed, config))

    def part_blocks(self, config, layout, seed, i):
        job = json.dumps(config), self.scale, seed, i
        return self.partbuild.in_build_order(
            [b for j in range(self.partbuild.jobs(layout))
             for b in self.partbuild.part_blocks(job + (j,))])


def main() -> int:
    if len(sys.argv) != 3 or sys.argv[1] != "--parent":
        print(__doc__, file=sys.stderr)
        return 2
    bench_dir = os.path.abspath(sys.argv[2])
    sys.path.insert(0, bench_dir)
    print(json.dumps(fingerprints(ParentApi(), bench_dir), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
