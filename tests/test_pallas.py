"""Pallas kernel parity vs their XLA twins, in interpret mode on CPU
(tests/pallas_check.py holds the checks; chip_smoke.py runs the same
checks compiled through Mosaic on the chip)."""

import numpy as np

import pallas_check


def test_scan_parity():
    assert pallas_check.check_scan(interpret=True)["patterns"] == 9


def test_bloom_probe_parity():
    pallas_check.check_bloom(interpret=True)
    # blooms wide enough that the plane spans several lane tiles
    got = pallas_check.check_bloom(interpret=True, nblocks=140,
                                   max_tokens=9000)
    assert got["plane_lanes"] > 2048


def test_stats_seg_parity():
    pallas_check.check_seg(interpret=True)
    # more buckets than one BUCKET_TILE: the bucket grid axis tiles
    pallas_check.check_seg(interpret=True, shapes=((3, 1300),), chunks=1)


def test_pad_for_pallas():
    from victorialogs_tpu.tpu.kernels_pallas import (TILE_ROWS,
                                                     pad_for_pallas,
                                                     pallas_ok)
    mat = np.full((100, 32), 0xFF, dtype=np.uint8)
    lens = np.arange(100, dtype=np.int32)
    m2, l2 = pad_for_pallas(mat, lens)
    assert pallas_ok(*m2.shape)
    assert m2.shape == (TILE_ROWS, 128)
    assert np.all(m2[100:] == 0xFF) and np.all(l2[100:] == 0)
