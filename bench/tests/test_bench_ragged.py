"""The ragged fleet driver's correctness check at the configuration's own
slice sizes, on the CPU, with the program as shipped: a sound run passes;
the control (the reference in the precision below the configuration's) and
each fault planted in the timed path fail. Then the readers of the ragged
cell's two metrics, and the program's pair solver against the reference's.

The harness's look for a chip is skipped; everything after it runs as in a
benchmark run: set-up, window, freeing the program's state, check."""
from __future__ import annotations

import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import work  # noqa: E402

harness.use_src()

from repro import obs  # noqa: E402

SEED = 3000000019
CONFIG = json.loads((BENCH / "configs" / "cocktail-paper-sweep.json").read_text())
PAPER = json.loads((BENCH / "configs" / "cocktail-paper.json").read_text())
TRAFFIC = {"slices": 8, "warm_slots": 2, "check_slots": 4}
DRIVER = harness.load_module(BENCH / "drivers" / "ragged_fleet.py")
T = harness.load_module(BENCH / "trace.py")
READERS = {n: harness.load_module(BENCH / "metrics" / f"{n}.py")
           for n in ("pad_pair_share.ragged", "collect_kernel_roofline.ragged",
                     "collect_kernel_roofline")}


class _Broken:
    """The fleet engine with its slot call broken underneath: the next
    state is altered before the driver sees it (and carried on)."""

    def __init__(self, eng, fault):
        self.eng, self.fault = eng, fault

    def run(self, n, state):
        import jax
        import jax.numpy as jnp
        nxt, rec = self.eng.run(n, state)
        old = state.tree() if hasattr(state, "tree") else state
        new = nxt.tree()
        if self.fault == "unchanged":  # slice 1 keeps its input state
            pick = lambda a, b: a.at[1].set(b[1])
            return jax.tree.map(pick, new, old), rec
        if self.fault == "altered":  # slice 3 (20 x 3): its true queues
            q = new.queues.q.at[3, :20].multiply(1.1)
            return new._replace(queues=new.queues._replace(q=q)), rec
        if self.fault == "padding":  # slice 0 (10 x 5): CU 10 is padding
            q = new.queues.q.at[0, 10].set(1.0)
            return new._replace(queues=new.queues._replace(q=q)), rec
        if self.fault == "swapped":  # slices 0 (10 x 5) and 1 (20 x 5)
            perm = jnp.array([1, 0, 2, 3, 4, 5, 6, 7])
            return jax.tree.map(lambda a: a[perm], new), rec
        raise ValueError(self.fault)


def _run(fault=None):
    d = DRIVER.Driver(CONFIG, TRAFFIC, SEED, 1)
    d.setup()
    if fault is not None:
        d.eng = _Broken(d.eng, fault)
    d.window(0.5)
    d.free()
    return d


@pytest.fixture(scope="module")
def sound():
    return _run()


def test_sound_run_is_correct(sound):
    ok, checks, info = sound.check()
    assert ok, checks
    assert dict((n, v) for n, v, _ in checks)["pad_nonzero"] == 0
    assert info["checked"] == 1 + TRAFFIC["check_slots"]


def test_control_fails(sound):
    ok, checks, _ = sound.check("control")
    assert not ok, checks


@pytest.mark.parametrize("fault", ["unchanged", "altered", "padding", "swapped"])
def test_fault_fails(fault):
    ok, checks, _ = _run(fault).check()
    assert not ok, checks
    failed = {n for n, v, lim in checks if v > lim}
    if fault == "padding":  # caught by the padding count
        assert "pad_nonzero" in failed, checks


def test_slices_must_match_the_configuration():
    with pytest.raises(ValueError):
        DRIVER.Driver(CONFIG, dict(TRAFFIC, slices=4), SEED, 1)


# ------------------------------------------------------------ readers

def _window_ctx(monkeypatch, slots=4):
    """A window of ``slots`` calls in the program's ``fleet.run`` spans."""
    monkeypatch.setattr(obs, "spans", lambda name: [(0, 1000)] * slots
                        if name == "fleet.run" else [])
    return {"counts": {"slots": slots, "latency_s": [0.001] * slots,
                       "slices": 8}}


def test_pad_pair_share_reads_the_configurations_padding(monkeypatch):
    from repro.core import FleetEngine
    obs.reset()
    FleetEngine.from_jobs(DRIVER.Driver(CONFIG, TRAFFIC, SEED, 1).jobs())
    assert obs.gauges() == {"fleet.links_real": 920, "fleet.links_compiled": 2560,
                            "fleet.pairs_real": 91, "fleet.pairs_compiled": 224}
    c = _window_ctx(monkeypatch)
    assert READERS["pad_pair_share.ragged"].read(c) == pytest.approx(
        100 * (1 - 91 / 224))  # 59.4
    obs.reset()
    assert READERS["pad_pair_share.ragged"].read(c) is None


def test_pad_pair_share_reads_nothing_without_gauges(monkeypatch):
    """An older program (spans, no gauges) gives nothing and raises
    nothing."""
    c = _window_ctx(monkeypatch)
    monkeypatch.delattr(obs, "gauges")
    assert READERS["pad_pair_share.ragged"].read(c) is None


DEV = "/device:TPU:0"
COLLECT = "%branch_0_fun.4 = f32[8,40,8]{2,1,0:T(8,128)} custom-call(f32[8,40,8] %p)"
KERNELS = {"branch_0_fun.4": "_collection_kernel"}


def _trace_ctx(config, slots=20):
    """Each slot: 8 ms of slot program holding 4 ms of collection kernel."""
    evs = []
    for s in range(slots):
        t0 = s * 10_000_000.0  # ns
        evs.append(T.Event(DEV, "XLA Modules", "jit__fleet_scan(7)", t0, 8e6))
        evs.append(T.Event(DEV, "XLA Ops", COLLECT, t0, 4e6))
    return {"trace": T.reduce_events(evs, slots * 0.01),
            "counts": {"slots": slots, "slices": 8}, "config": config,
            "work": work, "kernels": KERNELS,
            "peak": work.peaks_for("TPU v5 lite")}


def test_ragged_roofline_counts_the_true_shapes():
    """The work is the slices' true links (920), not the compiled 8 x 40 x
    8: bytes over HBM bandwidth, per execution."""
    least = 8 * 920 / 819e9
    got = READERS["collect_kernel_roofline.ragged"].read(_trace_ctx(CONFIG))
    assert got == pytest.approx(100 * least / 4e-3)


def test_ragged_roofline_of_a_uniform_fleet_is_the_fleet_roofline():
    uniform = dict(PAPER, slices=[{"n_cu": 20, "n_ec": 5}] * 8)
    assert READERS["collect_kernel_roofline.ragged"].read(
        _trace_ctx(uniform)) == pytest.approx(
        READERS["collect_kernel_roofline"].read(_trace_ctx(PAPER)))
    assert READERS["collect_kernel_roofline.ragged"].read(_trace_ctx(PAPER)) is None


def test_pair_allocation_follows_the_reference_bit_for_bit():
    """The program's pair solver (problem (21)) and the plain reference's
    agree to the bit on the same inputs. The dual step runs the link price
    down towards 0, where the primal 1/price amplifies one rounding step
    into a different allocation, so the two must round alike: a dual step
    in another order made a ragged8 run read ``correct`` false."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import training_alloc
    ref = harness.load_module(BENCH / "reference" / "cocktail_slot.py")
    rng = np.random.default_rng(12)
    p, n = 64, 40
    f = lambda *shape, lo=0.0, hi=1.0: jnp.asarray(rng.uniform(lo, hi, shape), jnp.float32)
    args = (f(p, n, lo=-0.5, hi=2.0), f(p, n, lo=-0.5, hi=2.0),
            f(p, n, lo=-0.5, hi=2.0), f(p, n, lo=-0.5, hi=2.0),
            f(p, n, hi=500.0), f(p, n, hi=500.0), f(p, lo=5e3, hi=4e4),
            f(p, lo=5e3, hi=4e4), f(p, lo=5e2, hi=6e3))
    got = jax.jit(jax.vmap(lambda *a: training_alloc.pair_allocate(*a, iters=30)))(*args)
    want = jax.jit(jax.vmap(lambda *a: ref.pair_alloc(*a, 30)))(*args)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
