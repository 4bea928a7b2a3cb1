"""Each per-layer metric reader on a synthetic reduced trace: the number
it reads, and nothing where the trace holds nothing to read."""
from __future__ import annotations

import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import work  # noqa: E402

T = harness.load_module(BENCH / "trace.py")
SPEC = harness.load_spec()
READERS = {m["name"]: harness.load_module(BENCH / "metrics" / f"{m['name']}.py")
           for m in SPEC["per_layer"]}
CONFIG = json.loads((BENCH / "configs" / "cocktail-paper.json").read_text())
DEV = "/device:TPU:0"
# device ops are named by their HLO text; the kernels by the program's HLO
COLLECT = "%branch_0_fun.4 = f32[8,20,5]{2,1,0:T(8,128)} custom-call(f32[8,20,5] %p)"
PAIRING = "%branch_0_fun.9 = f32[8,5,5]{2,1,0:T(8,128)} custom-call(f32[8,5,5] %q)"
OTHER = "%custom-call.3 = f32[8,20,5]{2,1,0} custom-call(f32[8,20,5] %r)"
KERNELS = {"branch_0_fun.4": "_collection_kernel",
           "branch_0_fun.9": "_pairing_kernel", "custom-call.3": "_other_kernel"}


def ctx(events, window_s, counts, kernels=KERNELS):
    return {"trace": T.reduce_events(events, window_s), "counts": counts,
            "config": CONFIG, "work": work, "kernels": kernels,
            "peak": work.peaks_for("TPU v5 lite")}


def fleet_ctx(slots=20):
    evs = []
    for s in range(slots):
        t0 = s * 10_000.0  # us; 8 ms of device work per 10 ms slot
        evs.append(T.Event(DEV, "XLA Modules", "jit__fleet_scan(7)", t0 * 1e3, 8e6))
        evs.append(T.Event(DEV, "XLA Ops", COLLECT, t0 * 1e3, 4e6))
        evs.append(T.Event(DEV, "XLA Ops", PAIRING, (t0 + 4000) * 1e3, 1e6))
        evs.append(T.Event(DEV, "XLA Ops", OTHER, (t0 + 5000) * 1e3, 1e6))
        evs.append(T.Event(DEV, "XLA Ops", "fusion", (t0 + 6000) * 1e3, 2e6))
    return ctx(evs, slots * 0.01, {"slots": slots, "slices": 8})


def test_fleet_readers():
    """Kernels are counted by name: another Pallas kernel in the slot
    program counts as program time, not as a matcher."""
    c = fleet_ctx()
    assert READERS["idle_share.fleet"].read(c) == pytest.approx(20.0)
    assert READERS["matcher_kernel_ms.fleet"].read(c) == pytest.approx(5.0)
    assert READERS["slot_xla_ms.fleet"].read(c) == pytest.approx(3.0)
    assert READERS["slot_ms_p95.fleet"].read(c) == pytest.approx(8.0)
    least = 8 * 8 * 20 * 5 / 819e9  # bytes over HBM bandwidth, per execution
    assert READERS["collect_kernel_roofline"].read(c) == pytest.approx(
        100 * least / 4e-3)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_reads_nothing_from_an_empty_trace(name):
    c = ctx([T.Event(DEV, "XLA Ops", "unrelated", 0, 1e6)], 1.0,
            {"slots": 5, "slices": 8}, kernels={})
    value = READERS[name].read(c)
    if name.startswith("idle_share"):
        assert value == pytest.approx(99.9)  # one 1 ms op in 1 s
    else:
        assert value is None


def test_matchers_unnamed_read_nothing():
    """A trace whose kernels the program's HLO does not name gives no
    matcher time (not all custom calls)."""
    c = fleet_ctx()
    c["kernels"] = {}
    assert READERS["matcher_kernel_ms.fleet"].read(c) is None
    assert READERS["collect_kernel_roofline"].read(c) is None
