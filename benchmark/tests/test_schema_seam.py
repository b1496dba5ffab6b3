"""The row-schema seam (PR 28): the move of the access-line rows behind
`schemas/access_line.py` changed no byte, and a loader that says what it
looked for.  The constants in golden_parent.json were printed from the
parent tree (commit 18ece72, PR 27) by

    python3 benchmark/tests/golden.py --parent <parent checkout>/benchmark

before anything moved; this file computes the same fingerprints through
`gen.load_schema` with the same recipe (golden.py).  numpy, and for the
blocks and stream ids the program's storage build (no jax)."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(1, HERE)

import gen  # noqa: E402
import golden  # noqa: E402
import partbuild  # noqa: E402
import run  # noqa: E402

GOLDEN = gen.load_json(os.path.join(HERE, "golden_parent.json"))


@pytest.fixture(scope="module")
def api():
    return golden.SeamApi()


@pytest.fixture(scope="module")
def config():
    return gen.load_config(os.path.join(BENCH, "configs",
                                        golden.CONFIG + ".json"))


@pytest.mark.parametrize("seed", golden.SEEDS)
def test_rows_and_streams_are_the_parents(api, config, seed):
    assert golden.rows_digest(api, config, seed) == GOLDEN[str(seed)]["rows"]


@pytest.mark.parametrize("seed", golden.SEEDS)
def test_built_blocks_are_the_parents(api, config, seed):
    assert golden.blocks_digest(api, config, seed) \
        == GOLDEN[str(seed)]["blocks"]


@pytest.mark.parametrize("name", golden.TRAFFIC)
@pytest.mark.parametrize("seed", golden.SEEDS)
def test_requests_answers_and_bytes_are_the_parents(api, config, seed, name):
    want = GOLDEN[str(seed)][name]
    traffic = gen.load_json(os.path.join(BENCH, "traffic", name + ".json"))
    n, digest = golden.requests_digest(api, config, traffic, seed)
    assert (n, digest) == (want["requests"], want["request_texts"])
    assert golden.answers_digest(api, config, traffic, seed) \
        == want["answers"]
    assert golden.required_sums(api, config, traffic, seed) \
        == want["required"]


def test_a_job_is_the_rows_of_its_streams(config):
    """Every row of a part falls to exactly one job, and with as many
    jobs as streams a job is "every `streams`-th row", as it was."""
    import numpy as np
    layout = gen.Layout(config, gen.REHEARSAL_SCALE)
    part = layout.parts[3]
    idx = np.arange(part["lo"], part["hi"], dtype=np.int64)
    stream = layout.schema.stream_of(idx, config)
    assert partbuild.jobs(layout) == layout.streams == 8
    for j in range(8):
        first = part["lo"] + (j - part["lo"]) % 8
        assert np.array_equal(idx[stream % 8 == j],
                              np.arange(first, part["hi"], 8))


# ---- the loader says what it looked for ----

def test_a_configuration_without_a_schema_is_refused(config):
    bare = {k: v for k, v in config.items() if k != "schema"}
    with pytest.raises(gen.SchemaError, match="baseline-1chip.*\"schema\""):
        gen.Layout(bare)


def test_a_configuration_that_does_not_say_where_it_lies_is_refused(config):
    """No default place either: a configuration read with load_json, or
    made as a dict, does not silently take benchmark/schemas/."""
    loose = {k: v for k, v in config.items() if k != "_dir"}
    with pytest.raises(gen.SchemaError, match="baseline-1chip.*load_config"):
        gen.Layout(loose)


def test_a_missing_or_short_schema_module_is_named_with_its_path(tmp_path,
                                                                 config):
    away = dict(config, schema="not_there")
    path = os.path.join(BENCH, "schemas", "not_there.py")
    with pytest.raises(gen.SchemaError) as e:
        gen.load_schema(away)
    assert "not_there" in str(e.value) and path in str(e.value)
    (tmp_path / "schemas").mkdir()
    (tmp_path / "schemas" / "short.py").write_text("STREAM_FIELDS = ()\n")
    with pytest.raises(gen.SchemaError) as e:
        gen.load_schema(dict(config, schema="short", _dir=str(tmp_path)))
    assert "row_fields" in str(e.value) and "short.py" in str(e.value)


def test_load_cell_fails_before_any_child_starts(tmp_path, config, capsys):
    """A BENCHMARK.json whose configuration names a schema that is not
    there: load_cell exits with the name and the path."""
    for d in ("configs", "traffic"):
        (tmp_path / "b" / d).mkdir(parents=True)
    bad = {k: v for k, v in config.items() if k != "_dir"}
    bad["schema"] = "gone"
    (tmp_path / "b" / "configs" / "baseline-1chip.json").write_text(
        json.dumps(bad))
    (tmp_path / "b" / "traffic" / "needle.json").write_text(
        open(os.path.join(BENCH, "traffic", "needle.json")).read())
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "baseline-1chip",
                     "file": "b/configs/baseline-1chip.json"}],
        "workloads": [{"name": "w", "config": "baseline-1chip",
                       "traffic": "needle", "chips": 1}]}))
    with pytest.raises(SystemExit):
        run.load_cell("w", root=str(tmp_path))
    err = capsys.readouterr().err
    assert "'gone'" in err and os.path.join("b", "schemas", "gone.py") in err


def test_more_than_100_access_line_streams_are_refused(config):
    with pytest.raises(ValueError, match="101 streams"):
        gen.Layout(dict(config, streams=101))
