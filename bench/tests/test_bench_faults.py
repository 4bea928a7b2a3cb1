"""The fleet driver's correctness check at the configuration's own slice
size, on the CPU: a sound program passes; the control (the reference in
the precision below the configuration's) and each fault planted in the
timed path fail.

The program's own solo water-filling (``training_alloc.solo_waterfill``)
trains nothing at an EC whose budget covers all its data in about a third
of such cases (an absolute tolerance of 1e-6 on levels of thousands of
samples), so the program as shipped is not a sound program. Here it runs
with that one function replaced by the same algorithm without the
tolerance: these tests check the benchmark's check, and the shipped
program's fault is the benchmark's to report.

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

harness.use_src()
SEED = 3000000019


def _config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


# ------------------------------------------------------------------ fleet

FLEET = _config("cocktail-paper")
FLEET_TRAFFIC = {"slices": 4, "warm_slots": 2, "check_slots": 4}


class _Broken:
    """The fleet engine with its slot call broken underneath."""

    def __init__(self, eng, fault):
        self.eng, self.fault = eng, fault

    def run(self, n, state):
        import jax
        import jax.numpy as jnp
        nxt, rec = self.eng.run(n, state)
        if self.fault == "unchanged":
            return state, rec
        if self.fault == "half":  # the second half of the slices is left out
            k = jax.tree.leaves(state)[0].shape[0]
            keep = jnp.arange(k) < k // 2
            pick = lambda a, b: jnp.where(
                keep.reshape((k,) + (1,) * (a.ndim - 1)), a, b)
            return jax.tree.map(pick, nxt, state), rec
        if self.fault == "altered":  # one slice's answers altered
            return nxt, rec._replace(cost=rec.cost.at[:, 0].multiply(1.1))
        raise ValueError(self.fault)


def _solo_waterfill(beta, r, budget):
    """``training_alloc.solo_waterfill`` with its level chosen without a
    tolerance: the first sorted segment whose level does not pass its cap,
    else every active CU trains all it holds."""
    import jax.numpy as jnp
    n = beta.shape[0]
    active = (beta > 0) & (r > 1e-9)
    n_act = jnp.sum(active)
    fill = jnp.minimum(jnp.maximum(budget, 0.0), jnp.sum(jnp.where(active, r, 0.0)))
    s = jnp.sort(jnp.where(active, r, jnp.inf))
    cs = jnp.concatenate([jnp.zeros((1,), s.dtype),
                          jnp.cumsum(jnp.where(jnp.isfinite(s), s, 0.0))])[:-1]
    k = jnp.arange(n)
    w_k = (fill - cs) / jnp.maximum((n_act - k).astype(r.dtype), 1.0)
    stops = (k < n_act) & (w_k <= s)
    level = jnp.where(jnp.any(stops), w_k[jnp.argmax(stops)], jnp.inf)
    x = jnp.where(active, jnp.minimum(r, jnp.maximum(level, 0.0)), 0.0)
    value = jnp.sum(jnp.where(x > 1e-9, jnp.log(jnp.maximum(beta * x, 1e-9)), 0.0))
    return x, value


@pytest.fixture(scope="module", autouse=True)
def sound_solver():
    """The program with its water-filling fault mended (module docstring);
    compiled programs of the shipped solver are dropped on both sides."""
    import jax
    from repro.core import training_alloc
    shipped = training_alloc.solo_waterfill
    jax.clear_caches()
    training_alloc.solo_waterfill = _solo_waterfill
    yield
    training_alloc.solo_waterfill = shipped
    jax.clear_caches()


def _fleet_run(fault=None, slices=4):
    d = harness.load_module(BENCH / "drivers" / "fleet.py").Driver(
        FLEET, dict(FLEET_TRAFFIC, slices=slices), SEED, 1)
    d.setup()
    if fault is not None:
        d.eng = _Broken(d.eng, fault)
    d.window(0.5)
    d.free()
    return d


@pytest.fixture(scope="module")
def fleet_sound():
    return _fleet_run()


def test_fleet_sound_run_is_correct(fleet_sound):
    ok, checks, _ = fleet_sound.check()
    assert ok, checks


def test_fleet_control_fails(fleet_sound):
    ok, checks, _ = fleet_sound.check("control")
    assert not ok, checks


@pytest.mark.parametrize("fault,slices", [("unchanged", 4), ("half", 4),
                                          ("altered", 4), ("unchanged", 1),
                                          ("altered", 1)])
def test_fleet_fault_fails(fault, slices):
    ok, checks, _ = _fleet_run(fault, slices).check()
    assert not ok, checks
