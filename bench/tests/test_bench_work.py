"""Work counted from shapes, against hand counts."""
from __future__ import annotations

import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import work  # noqa: E402

def test_collection_kernel_floor_and_roofline():
    flops, nbytes = work.collection_kernel(8, 1024, 16)
    assert nbytes == 8 * 8 * 1024 * 16 and flops == 8 * 1024 * 16
    peak = work.peaks_for("TPU v5 lite")
    t, bound = work.roofline_seconds(flops, nbytes, peak)
    assert bound == "memory" and t == pytest.approx(nbytes / 819e9)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        work.peaks_for("TPU v99")
    table = json.loads((BENCH / "peaks.json").read_text())
    assert "source" in table
