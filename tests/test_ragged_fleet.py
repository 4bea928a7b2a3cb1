"""Masked-equivalence harness for ragged (mixed-shape) fleets.

The padding contract: a slice whose true shape is (N, M), zero-padded to a
larger compiled ``ShapeConfig`` with ``cu_mask``/``ec_mask`` set, must
reproduce its standalone unpadded ``run()`` trace — per-slot records, final
queues/multipliers and accumulated objective — because

  * network sampling is entity-keyed (value at (i, j) never depends on the
    array shape) and masked entities get zero capacity/arrivals,
  * masked entities carry -inf solver weights, so collection, pairing and
    training allocate exactly zero to them,
  * record scalars are sums to which padded entries contribute exact zeros.

The single-slice padded path is asserted BIT-exact on CPU for everything
per entity (queues, multipliers, uploads). The scalar sums over the padded
axes (the per-slot records and the accumulated cost/trained totals) are held
to SUM_RTOL instead: XLA:CPU vectorises a reduction according to the array's
length, so the same values plus trailing exact zeros can round differently
in the last ulp, depending on the host's vector width. The vmapped fleet
path reuses the tolerances of tests/test_fleet.py (vmap may re-associate
reductions).
"""
import dataclasses

import numpy as np
import pytest

from repro.core import (CU_FULL, DS, EC_FULL, EC_SELF, LDS, NO_LSA, NO_SDC,
                        NO_SLT, CocktailConfig, FleetEngine, ShapeConfig,
                        SliceParams, init_state, ragged_pad_shape, run,
                        trim_state)
from repro.core import metrics
from repro.core.fleet import slice_records, unstack

BASE = CocktailConfig(n_cu=8, n_ec=3, eps=0.1, pair_iters=15, seed=7,
                      f_base=(8000.0, 20000.0, 12000.0))
SLOTS = 10
# A few float32 ulps (eps = 2**-23): the reassociation bound of a sum whose
# array length changed; measured at most 2.0e-7 over every policy here.
SUM_RTOL = 4 * 2.0 ** -23


def _padded_run(cfg: CocktailConfig, pad: ShapeConfig, spec, n_slots: int):
    params = SliceParams.from_config(cfg, pad_shape=pad)
    state = init_state(pad, params, seed=cfg.seed)
    return run(pad, spec, n_slots, state=state, params=params)


def _assert_records_equal(recs_pad, recs_ref, exact=True):
    for field in recs_ref._fields:
        a = np.asarray(getattr(recs_ref, field))
        b = np.asarray(getattr(recs_pad, field))
        if exact:
            np.testing.assert_allclose(b, a, rtol=SUM_RTOL, atol=0,
                                       err_msg=field)
        else:
            np.testing.assert_allclose(b, a, rtol=1e-4, atol=1e-4,
                                       err_msg=field)


def _assert_trimmed_state_equal(st_pad, st_ref, shape, exact=True):
    tr = trim_state(st_pad, shape)
    assert_eq = (np.testing.assert_array_equal if exact else
                 lambda b, a, err_msg: np.testing.assert_allclose(
                     b, a, rtol=1e-4, atol=1e-2, err_msg=err_msg))
    for name in ("q", "r", "omega"):
        assert_eq(np.asarray(getattr(tr.queues, name)),
                  np.asarray(getattr(st_ref.queues, name)), err_msg=name)
    for name in ("mu", "eta", "phi", "lam"):
        assert_eq(np.asarray(getattr(tr.mults, name)),
                  np.asarray(getattr(st_ref.mults, name)), err_msg=name)
    assert_eq(np.asarray(tr.uploaded), np.asarray(st_ref.uploaded),
              err_msg="uploaded")
    if exact:
        for name in ("total_cost", "total_trained"):
            np.testing.assert_allclose(float(getattr(tr, name)),
                                       float(getattr(st_ref, name)),
                                       rtol=SUM_RTOL, atol=0, err_msg=name)
    else:
        np.testing.assert_allclose(float(tr.total_cost),
                                   float(st_ref.total_cost), rtol=1e-4)


def _assert_padding_zero(st_pad, shape):
    n, m = shape.n_cu, shape.n_ec
    assert (np.asarray(st_pad.queues.q)[n:] == 0).all()
    assert (np.asarray(st_pad.queues.r)[n:, :] == 0).all()
    assert (np.asarray(st_pad.queues.r)[:, m:] == 0).all()
    assert (np.asarray(st_pad.queues.omega)[n:, :] == 0).all()
    assert (np.asarray(st_pad.queues.omega)[:, m:] == 0).all()
    assert (np.asarray(st_pad.mults.mu)[n:] == 0).all()
    for name in ("eta", "phi", "lam"):
        v = np.asarray(getattr(st_pad.mults, name))
        assert (v[n:, :] == 0).all() and (v[:, m:] == 0).all()
    assert (np.asarray(st_pad.uploaded)[n:] == 0).all()


@pytest.mark.parametrize("pad", [(8, 4), (12, 3), (12, 5), (16, 8)],
                         ids=lambda p: f"pad{p[0]}x{p[1]}")
def test_padded_matches_unpadded_bitexact(pad):
    """DS at several pad shapes: padded run == unpadded run, bit for bit."""
    pad_shape = ShapeConfig(n_cu=pad[0], n_ec=pad[1], pair_iters=BASE.pair_iters)
    st_ref, recs_ref = run(BASE, DS, SLOTS)
    st_pad, recs_pad = _padded_run(BASE, pad_shape, DS, SLOTS)
    _assert_records_equal(recs_pad, recs_ref, exact=True)
    _assert_trimmed_state_equal(st_pad, st_ref, BASE.shape, exact=True)
    _assert_padding_zero(st_pad, BASE.shape)


@pytest.mark.parametrize("spec", [LDS, NO_SDC, NO_SLT, NO_LSA, EC_FULL,
                                  EC_SELF, CU_FULL], ids=lambda s: s.name)
def test_padded_matches_unpadded_all_policies(spec):
    """Every jittable policy variant honours the masks (collection, linear
    and log-utility training, full-allocation, learning-aid virtual path)."""
    pad_shape = ShapeConfig(n_cu=12, n_ec=5, pair_iters=BASE.pair_iters)
    st_ref, recs_ref = run(BASE, spec, SLOTS)
    st_pad, recs_pad = _padded_run(BASE, pad_shape, spec, SLOTS)
    _assert_records_equal(recs_pad, recs_ref, exact=True)
    _assert_trimmed_state_equal(st_pad, st_ref, BASE.shape, exact=True)
    _assert_padding_zero(st_pad, BASE.shape)


def test_masked_decision_entries_zero():
    """One slot at pad shape: the Decision itself allocates exactly nothing
    to padded entities (alpha/theta/x rows+cols, y and z slabs)."""
    from repro.core import step

    pad_shape = ShapeConfig(n_cu=12, n_ec=5, pair_iters=BASE.pair_iters)
    params = SliceParams.from_config(BASE, pad_shape=pad_shape)
    state = init_state(pad_shape, params, seed=BASE.seed)
    n, m = BASE.n_cu, BASE.n_ec
    for _ in range(3):
        state, _, dec = step(pad_shape, DS, state, params=params)
        for name in ("alpha", "theta", "x"):
            v = np.asarray(getattr(dec, name))
            assert (v[n:, :] == 0).all() and (v[:, m:] == 0).all(), name
        y = np.asarray(dec.y)
        assert (y[n:] == 0).all() and (y[:, m:, :] == 0).all() and (y[:, :, m:] == 0).all()
        z = np.asarray(dec.z)
        assert (z[m:, :] == 0).all() and (z[:, m:] == 0).all()


def test_ragged_fleet_matches_standalone_runs():
    """Acceptance: distinct-(N, M) slices in ONE jitted program, each slice's
    per-slot records matching its standalone unpadded run()."""
    cfgs = [
        CocktailConfig(n_cu=6, n_ec=3, pair_iters=15, seed=0,
                       f_base=(8000.0, 20000.0, 12000.0)),
        CocktailConfig(n_cu=12, n_ec=4, pair_iters=15, seed=1, zeta=800.0),
        CocktailConfig(n_cu=9, n_ec=2, pair_iters=15, seed=2, eps=0.2),
        dataclasses.replace(BASE, seed=3),
    ]
    eng = FleetEngine.from_ragged_configs(cfgs, DS)
    assert eng.shape == ShapeConfig(n_cu=12, n_ec=4, pair_iters=15)
    assert eng.n_slices == 4
    st, recs = eng.run(SLOTS)
    assert recs.cost.shape == (SLOTS, 4)
    for k, cfg in enumerate(cfgs):
        st_ref, recs_ref = run(cfg, DS, SLOTS)
        # vmap may re-associate reductions: same tolerance as test_fleet.py
        _assert_records_equal(slice_records(recs, k), recs_ref, exact=False)
        _assert_trimmed_state_equal(unstack(st, k), st_ref, cfg.shape,
                                    exact=False)
        _assert_padding_zero(unstack(st, k), cfg.shape)
        # slice_state trims, so shape-aware metrics work off the original cfg
        s = metrics.summary(cfg, eng.slice_state(st, k))
        np.testing.assert_allclose(s["total_trained"],
                                   float(st_ref.total_trained), rtol=1e-4)


def test_ragged_fleet_lds():
    """Learning-aid DS (virtual plain-P1/P2 decisions) also masks cleanly in
    a ragged fleet."""
    cfgs = [CocktailConfig(n_cu=5, n_ec=2, pair_iters=12, seed=4),
            CocktailConfig(n_cu=10, n_ec=3, pair_iters=12, seed=5)]
    eng = FleetEngine.from_ragged_configs(cfgs, LDS)
    st, recs = eng.run(8)
    for k, cfg in enumerate(cfgs):
        st_ref, recs_ref = run(cfg, LDS, 8)
        _assert_records_equal(slice_records(recs, k), recs_ref, exact=False)
        _assert_padding_zero(unstack(st, k), cfg.shape)


def test_ragged_rejects_mismatched_pair_iters():
    a = CocktailConfig(n_cu=4, n_ec=2, pair_iters=10)
    b = CocktailConfig(n_cu=6, n_ec=3, pair_iters=20)
    with pytest.raises(ValueError):
        FleetEngine.from_ragged_configs([a, b], DS)
    with pytest.raises(ValueError):
        FleetEngine.from_ragged_configs([], DS)


def test_from_jobs_gauges_the_padding():
    """The padding of a small mixed-shape fleet, as ``from_jobs`` counts it:
    (8, 3), (12, 5) and (6, 2) padded to (12, 5)."""
    from repro import obs
    from repro.core import SliceJob
    obs.reset()
    f_base = (8000.0, 20000.0, 12000.0, 9000.0, 9000.0)
    cfgs = [dataclasses.replace(BASE, n_cu=n, n_ec=m, f_base=f_base[:m])
            for n, m in ((8, 3), (12, 5), (6, 2))]
    eng = FleetEngine.from_jobs([SliceJob(c, spec=DS) for c in cfgs])
    assert eng.shape == ShapeConfig(12, 5, BASE.pair_iters)
    assert obs.gauges() == {
        "fleet.links_real": 8 * 3 + 12 * 5 + 6 * 2,  # 96
        "fleet.links_compiled": 3 * 12 * 5,  # 180
        "fleet.pairs_real": 3 + 10 + 1,
        "fleet.pairs_compiled": 3 * 10}
    obs.reset()


def test_ragged_pad_shape_and_mask_layout():
    shapes = [ShapeConfig(4, 2, 10), ShapeConfig(6, 3, 10), ShapeConfig(5, 5, 10)]
    assert ragged_pad_shape(shapes) == ShapeConfig(6, 5, 10)
    cfg = CocktailConfig(n_cu=4, n_ec=2, pair_iters=10)
    p = SliceParams.from_config(cfg, pad_shape=ShapeConfig(6, 5, 10))
    np.testing.assert_array_equal(np.asarray(p.cu_mask), [1, 1, 1, 1, 0, 0])
    np.testing.assert_array_equal(np.asarray(p.ec_mask), [1, 1, 0, 0, 0])
    assert (np.asarray(p.zeta)[4:] == 0).all()
    assert (np.asarray(p.f_base)[2:] == 0).all()
    np.testing.assert_allclose(np.asarray(p.proportions).sum(), 1.0, rtol=1e-6)
    with pytest.raises(ValueError):
        SliceParams.from_config(cfg, pad_shape=ShapeConfig(3, 2, 10))


@pytest.mark.tier2
def test_padded_equivalence_property():
    """Hypothesis sweep over random true shapes, pad shapes and seeds: the
    padded DS trace matches the unpadded one (per entity bit-exact, sums
    within SUM_RTOL)."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as hst

    @given(hst.integers(2, 10), hst.integers(2, 4), hst.integers(0, 6),
           hst.integers(0, 3), hst.integers(0, 10_000))
    @settings(max_examples=10, deadline=None)
    def check(n_cu, n_ec, pad_n, pad_m, seed):
        cfg = CocktailConfig(n_cu=n_cu, n_ec=n_ec, eps=0.12, pair_iters=10,
                             seed=seed % 89)
        pad_shape = ShapeConfig(n_cu=n_cu + pad_n, n_ec=n_ec + pad_m,
                               pair_iters=10)
        st_ref, recs_ref = run(cfg, DS, 6)
        st_pad, recs_pad = _padded_run(cfg, pad_shape, DS, 6)
        _assert_records_equal(recs_pad, recs_ref, exact=True)
        _assert_trimmed_state_equal(st_pad, st_ref, cfg.shape, exact=True)
        _assert_padding_zero(st_pad, cfg.shape)

    check()
