"""The trace reduction on synthetic events and on a trace the profiler
records here (on the CPU, where no device plane exists)."""
from __future__ import annotations

import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

T = harness.load_module(BENCH / "trace.py")
DEV, HOST = "/device:TPU:0", "/host:CPU"
# a device op is named by its HLO instruction, or by its HLO text
COLLECT = "%branch_0_fun.4 = f32[8,20,5]{2,1,0} custom-call(f32[8,20,5] %p)"
KERNELS = {"branch_0_fun.4": "_collection_kernel", "custom-call.7": "_pairing_kernel"}


def ev(plane, line, name, start_us, dur_us):
    return T.Event(plane, line, name, start_us * 1e3, dur_us * 1e3)


def synthetic():
    return [
        ev(DEV, "XLA Modules", "jit__fleet_scan(1)", 0, 400),
        ev(DEV, "XLA Ops", "fusion.1", 0, 100),
        ev(DEV, "XLA Ops", COLLECT, 50, 250),  # overlaps fusion.1
        ev(DEV, "XLA Ops", "custom-call.7", 300, 100),
        ev(DEV, "XLA Modules", "jit__fleet_scan(1)", 1000, 200),
        ev(DEV, "XLA Ops", COLLECT, 1000, 200),
        ev(DEV, "XLA Ops", "tiny", 1250, 10),  # 50 us gap: below the cut
        ev("/device:TPU:0 SparseCore", "XLA Ops", "ignored", 0, 5000),
        ev(HOST, "python", "host loop", 0, 2000),
        ev(HOST, "python", "PjitFunction(_fleet_scan)", 450, 500),
    ]


def test_busy_union_ops_and_modules():
    red = T.reduce_events(synthetic(), window_s=2e-3)
    assert red.n_devices == 1
    assert red.busy_s == pytest.approx((400 + 200 + 10) * 1e-6)
    assert red.idle_share == pytest.approx(1 - 610 / 2000)
    assert red.ops[COLLECT] == pytest.approx(450e-6)
    assert red.op_counts[COLLECT] == 2
    assert red.kernel_seconds(KERNELS, ("_collection_kernel",)) == pytest.approx(450e-6)
    assert red.kernel_executions(KERNELS, ("_collection_kernel",)) == 2
    assert red.kernel_seconds(KERNELS, ("_pairing_kernel",)) == pytest.approx(100e-6)
    assert red.kernel_seconds(KERNELS, ("_other_kernel",)) == 0
    assert sorted(red.module_runs(r"_fleet_scan")) == pytest.approx([200e-6, 400e-6])


def test_gaps_named_by_the_shortest_covering_host_span():
    red = T.reduce_events(synthetic(), window_s=2e-3)
    gaps = dict(red.gaps)
    assert gaps["PjitFunction(_fleet_scan)"] == pytest.approx(600e-6)
    assert set(gaps) == {"PjitFunction(_fleet_scan)"}  # the 50 us gap is cut
    b = red.breakdown()
    assert b["device_ops"][0][0] == COLLECT
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_no_device_reads_nothing():
    red = T.reduce_events([ev(HOST, "python", "x", 0, 10)], window_s=1.0)
    assert red.n_devices == 0 and red.idle_share is None


def test_pallas_kernels_named_from_the_mosaic_module():
    """Each Mosaic custom call of a program's HLO text maps to its kernel's
    name; other instructions map to nothing."""
    import jax
    import jax.numpy as jnp
    from jax import export
    from jax._src.lib import _jax
    harness.use_src()
    from repro.kernels.matching import kernel as K
    f = jax.jit(lambda w, v: (K.greedy_collection_pallas(w) + 1.0,
                              K.greedy_pairing_pallas(v)))
    exp = export.export(f, platforms=("tpu",))(
        jax.ShapeDtypeStruct((20, 5), jnp.float32),
        jax.ShapeDtypeStruct((5, 5), jnp.float32))
    hlo = _jax.mlir.mlir_module_to_xla_computation(
        exp.mlir_module(), use_tuple_args=False, return_tuple=False).as_hlo_text()
    names = T.pallas_kernels(hlo)
    assert sorted(names.values()) == ["_collection_kernel", "_pairing_kernel"]
    assert all(T.instruction(f"%{k} = f32[5,5] custom-call()") == k for k in names)


def test_capture_reads_a_recorded_trace():
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    out, red = T.capture(lambda: f(x).block_until_ready())
    assert out.shape == (64, 64)
    assert red.window_s > 0
    assert red.n_devices == 0  # a CPU run has no TPU plane
