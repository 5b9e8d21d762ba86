"""The least-bytes function and the peaks table."""
import json

import numpy as np
import pytest

from bench import roofline
from bench.reference import GET, NOP, UPDATE

V5E = "TPU v5 lite"


def test_v5e_peaks():
    p = roofline.peaks(V5E)
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    assert p["ici_bytes_per_s"] * 8 == 1600e9


def test_unknown_device_kind_is_an_error(tmp_path):
    with pytest.raises(KeyError, match="cpu"):
        roofline.peaks("cpu")
    path = tmp_path / "peaks.json"
    path.write_text(json.dumps({"source": "-", "devices": {}}))
    with pytest.raises(KeyError):
        roofline.peaks(V5E, str(path))


def test_four_lane_window_by_hand():
    # lane: 0 GET local, 1 UPDATE of a key on chip 1 from chip 0,
    #       2 NOP, 3 GET local on chip 1
    ops = np.array([GET, UPDATE, NOP, GET])
    key_chip = np.array([0, 1, 0, 1])
    lane_chip = np.array([0, 0, 1, 1])
    hbm, wire = roofline.window_bytes(ops, key_chip, lane_chip)
    # GET: 20 B index entry + 1,024 B record read + 1,024 B result write;
    # UPDATE: 20 B index entry + 1,024 B record write; NOP: nothing
    assert hbm == 2 * (20 + 1024 + 1024) + (20 + 1024) == 5180
    assert wire == 1024
    p = roofline.peaks(V5E)
    assert roofline.least_seconds(hbm, wire, p, 1) == pytest.approx(
        5180 / 819e9)
    assert roofline.least_seconds(hbm, wire, p, 4) == pytest.approx(
        5180 / (4 * 819e9))
    assert roofline.least_seconds(0, 4096, p, 1) == pytest.approx(
        4096 / 200e9)
