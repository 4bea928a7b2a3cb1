"""The slot program's packed boundary (``core/fleet``): one buffer per side.

Contracts:
  * ``FleetEngine.run`` on packed buffers is bit-identical to the tree-level
    slot program (``scan_slots``), state and records, the PRNG keys
    included: DS at K = 1 and K = 8, a ragged fleet and a mixed-policy
    (``SWITCHED``) fleet; chained ``run(1)`` calls and one ``run(T)``;
  * ``PackedState`` / ``PackedRecord`` fields read as the trees' fields, on
    device arrays and after ``jax.device_get``;
  * ``unstack``, ``slice_records``, ``trim_state`` and ``slice_state`` give
    the same trees from either form;
  * the compiled slot program takes 2 buffers and returns 2, keeps its
    ``_fleet_scan`` module name and the five stage scopes, and a tree state
    is packed (span ``fleet.pack``) once, never from a packed one.
"""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import (DS, LDS, NO_SDC, CocktailConfig, FleetEngine,
                        SchedulerState, SliceJob, SlotRecord)
from repro.core.fleet import (PackedRecord, PackedState, scan_slots,
                              slice_records, trim_state, unstack)

BASE = CocktailConfig(n_cu=8, n_ec=3, eps=0.1, pair_iters=15, seed=7,
                      f_base=(8000.0, 20000.0, 12000.0))
CHAIN = 4
STAGES = ("network", "collection", "allocation", "pairing", "update")

# the tree-level slot program, jitted on its own (not named _fleet_scan)
_tree_scan = jax.jit(scan_slots, static_argnums=(0, 1, 2))


def _ds_k1():
    return FleetEngine.from_jobs([SliceJob(BASE, DS)])


def _ds_k8():
    return FleetEngine.from_jobs([
        SliceJob(dataclasses.replace(BASE, seed=s, zeta=300.0 + 60.0 * s,
                                     eps=0.08 + 0.02 * (s % 3)), DS)
        for s in range(8)])


def _ragged():
    return FleetEngine.from_jobs([
        SliceJob(BASE, DS),
        SliceJob(CocktailConfig(n_cu=5, n_ec=2, pair_iters=15, seed=3), DS),
        SliceJob(CocktailConfig(n_cu=10, n_ec=4, pair_iters=15, seed=4,
                                zeta=800.0), DS)])


def _switched():
    return FleetEngine.from_jobs([
        SliceJob(BASE, DS),
        SliceJob(dataclasses.replace(BASE, seed=1), NO_SDC),
        SliceJob(dataclasses.replace(BASE, eps=0.2, seed=2), LDS)])


FLEETS = {"ds_k1": _ds_k1, "ds_k8": _ds_k8, "ragged": _ragged,
          "switched": _switched}


@functools.lru_cache(maxsize=None)
def _fleet(name):
    return FLEETS[name]()


def _assert_trees_equal(got, want):
    want_leaves, want_def = jax.tree.flatten(want)
    assert jax.tree.structure(got) == want_def
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            want_leaves):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype, jax.tree_util.keystr(path)
        np.testing.assert_array_equal(a, b,
                                      err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("name", list(FLEETS))
def test_chained_run1_is_bit_identical_to_the_tree_program(name):
    eng = _fleet(name)
    state = eng.init()
    ref_state = state
    for _ in range(CHAIN):
        state, rec = eng.run(1, state)
        assert isinstance(state, PackedState) and isinstance(rec, PackedRecord)
        ref_state, ref_rec = _tree_scan(eng.shape, eng.spec, 1, eng.params,
                                        ref_state)
        _assert_trees_equal(state.tree(), ref_state)
        _assert_trees_equal(rec.tree(), ref_rec)


@pytest.mark.parametrize("name", list(FLEETS))
def test_run_t_is_bit_identical_to_the_tree_program(name):
    eng = _fleet(name)
    state, rec = eng.run(CHAIN)
    ref_state, ref_rec = _tree_scan(eng.shape, eng.spec, CHAIN, eng.params,
                                    eng.init())
    assert rec.buf.shape == (CHAIN, eng.n_slices, len(SlotRecord._fields))
    assert rec.cost.shape == (CHAIN, eng.n_slices)
    _assert_trees_equal(state.tree(), ref_state)
    _assert_trees_equal(rec.tree(), ref_rec)


@pytest.mark.parametrize("where", ["device", "host"])
def test_packed_fields_read_as_the_tree(where):
    eng = _fleet("switched")
    state, rec = eng.run(2)
    tree_state, tree_rec = state.tree(), rec.tree()
    if where == "host":
        state, rec = jax.device_get((state, rec))
        assert isinstance(state.buf, np.ndarray)
        assert isinstance(state.queues.q, np.ndarray)
    for field in SchedulerState._fields:
        _assert_trees_equal(getattr(state, field), getattr(tree_state, field))
    for field in SlotRecord._fields:
        _assert_trees_equal(getattr(rec, field), getattr(tree_rec, field))
    _assert_trees_equal(state.tree(), tree_state)
    _assert_trees_equal(rec.tree(), tree_rec)
    assert PackedRecord._fields == SlotRecord._fields


def test_packed_record_replace_acts_as_the_tree_replace():
    eng = _fleet("ds_k8")
    _, rec = eng.run(2)
    cost = rec.cost.at[:, 0].multiply(1.1)
    got = rec._replace(cost=cost)
    assert isinstance(got, PackedRecord)
    _assert_trees_equal(got.tree(), rec.tree()._replace(cost=cost))


def test_helpers_give_the_same_trees_from_either_form():
    eng = _fleet("ragged")
    state, rec = eng.run(3)
    tree_state, tree_rec = state.tree(), rec.tree()
    for k, shape in enumerate(eng.slice_shapes):
        _assert_trees_equal(unstack(state, k), unstack(tree_state, k))
        _assert_trees_equal(slice_records(rec, k), slice_records(tree_rec, k))
        _assert_trees_equal(eng.slice_state(state, k),
                            eng.slice_state(tree_state, k))
        _assert_trees_equal(trim_state(unstack(state, k), shape),
                            trim_state(unstack(tree_state, k), shape))
    _assert_trees_equal(unstack(eng.packed_params, 1), unstack(eng.params, 1))


def test_trim_state_keeps_the_slice_axis_of_a_stacked_state():
    eng = _fleet("ragged")
    state, _ = eng.run(2)
    small = eng.slice_shapes[1]
    want = [trim_state(unstack(state, k), small) for k in range(eng.n_slices)]
    want = jax.tree.map(lambda *ls: np.stack(ls), *want)
    _assert_trees_equal(trim_state(state, small), want)
    _assert_trees_equal(trim_state(state.tree(), small), want)


def test_params_are_packed_once_as_bits():
    eng = _ds_k1()
    packed = eng.packed_params
    assert packed is eng.packed_params
    assert packed.buf.dtype == jnp.uint32 and packed.buf.shape[0] == 1
    assert packed.buf.shape[1] == sum(int(np.prod(np.shape(l)[1:]))
                                      for l in jax.tree.leaves(eng.params))
    # int32 policy leaves come back as the same int32 values, not converted
    _assert_trees_equal(packed.tree(), eng.params)
    assert packed.tree().collect_id.dtype == jnp.int32


def test_slot_program_boundary_is_two_buffers_each_way():
    eng = _ds_k1()
    state, _ = eng.run(1)
    compiled = eng.lower(1, state).compile()
    text = compiled.as_text()
    head = text.splitlines()[0]
    assert re.match(r"HloModule \S*_fleet_scan", head), head
    sig = re.search(r"entry_computation_layout=\{\((.*)\)->\((.*)\)\}", head)
    shape = r"[a-z0-9]+\[[0-9,]*\](?:\{[0-9,]*\})?"
    args = re.findall(shape, sig.group(1))
    results = re.findall(shape, sig.group(2))
    assert len(args) == 2 and len(results) == 2, head
    assert len(jax.tree.leaves(compiled.input_shardings)) == 2
    assert len(jax.tree.leaves(compiled.output_shardings)) == 2
    scoped = eng.lower(1, state).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]*)"', scoped))
    for stage in STAGES:
        rx = re.compile(rf"(^|/|\(){stage}(\)|/)")
        assert any(rx.search(n) for n in names), stage


def test_a_tree_state_is_packed_once():
    eng = _ds_k1()
    state = eng.init()
    before = len(obs.spans("fleet.pack"))
    state, rec = eng.run(1, state)
    assert len(obs.spans("fleet.pack")) == before + 1
    for _ in range(3):
        state, rec = eng.run(1, state)
        jax.block_until_ready((state, rec))
    eng.lower(1, state)
    assert len(obs.spans("fleet.pack")) == before + 1
