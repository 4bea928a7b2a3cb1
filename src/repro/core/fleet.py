"""Fleet engine: one compiled program scheduling K network slices at once.

A real 5G operator runs many concurrent incremental-learning jobs — one
traffic-prediction slice per region, one per tenant — not the single slice of
the paper's testbed. The batch-first core makes this a pure data-parallel
problem: all per-slice numbers live in a ``SliceParams`` pytree, so a fleet
is just that pytree with a leading K axis, and one slot of the whole fleet is
``jax.vmap(step)`` over (params, state). The slot loop is a single
``lax.scan``; the result is ONE jitted program for K heterogeneous slices.

Axis conventions (documented in ROADMAP.md):
  * stacked ``SliceParams`` / ``SchedulerState``: leading axis = slice (K)
  * records of a run: time-major (T, K) — axis 0 is the slot, matching
    single-slice ``run``'s (T,)
  * optional device sharding splits the K axis over a mesh axis via
    ``launch.mesh.shard_leading_axis`` (NamedSharding, trailing axes
    replicated)

The slot program's boundary is packed: one buffer in per side and one out.
:meth:`FleetEngine.run` passes the parameters as one ``(K, Wp)`` ``uint32``
buffer (``PackedTree``, packed once per engine) and the carried state as one
``(K, Ws)`` ``uint32`` buffer (``PackedState``), and gets back the next
``PackedState`` and the records as one ``(T, K, F)`` ``float32`` buffer
(``PackedRecord``, F = the ``SlotRecord`` fields in order). Each leaf of a
packed tree is bitcast to ``uint32`` and flattened per slice, in the tree's
leaf order; the layout (treedef, per-slice shape and dtype of each leaf) is
static pytree data, read from the tree itself. K stays axis 0 (axis 1 of the
records), so sharding and ``shard_map`` see the same leading axis as on the
tree. ``PackedState`` and ``PackedRecord`` read like the trees they hold
(``state.queues.q``, ``recs.cost``), on device arrays and on the numpy
arrays ``jax.device_get`` leaves; ``.tree()`` gives the tree itself. A tree
state given to ``run`` is packed once (span ``fleet.pack``); the state
``run`` returns is packed already, so a chain of calls never packs again.

Constraints: all slices of a fleet run at one *compiled* ``ShapeConfig`` (N,
M and solver iteration counts are compile-time); ``exact`` specs are
host-side and cannot be vmapped. Everything else is transparent through the
:meth:`FleetEngine.from_jobs` frontend (a list of ``SliceJob``):

  * slices with different *true* (N, M) are zero-padded to the
    elementwise-max shape, with the ``SliceParams`` entity masks
    (``cu_mask``/``ec_mask``) making every policy ignore the padding, so the
    padded slice reproduces its standalone run on the real block
    (tests/test_ragged_fleet.py);
  * slices with different ``AlgoSpec`` run under branch-free (``SWITCHED``)
    dispatch: the policy choice is ``lax.switch`` over the indexed policy
    tables, driven by the per-slice policy leaves ``with_policy`` fills —
    still ONE compiled program (tests/test_policy_switch.py).

``from_configs`` / ``from_ragged_configs`` are kept as thin shims over
``from_jobs`` for older call sites.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from .. import obs
from .datasche import AlgoSpec, DS, SWITCHED, SWITCHED_NOAID, SlotRecord, step
from .job import JobLike, SliceJob, as_jobs
from .types import (CocktailConfig, Decision, Multipliers, QueueState,
                    SchedulerState, ShapeConfig, SliceParams, init_state,
                    split_config, stack_slice_params)


# -- the packed boundary ------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _Layout:
    """Where each leaf of a pytree lies in its packed ``(K, W)`` buffer:
    the treedef, and each leaf's per-slice shape, dtype and first column."""

    treedef: Any
    shapes: tuple[tuple[int, ...], ...]
    dtypes: tuple[np.dtype, ...]
    offsets: tuple[int, ...]  # len(shapes) + 1 column bounds

    @classmethod
    def of(cls, tree) -> "_Layout":
        leaves, treedef = jax.tree.flatten(tree)
        dtypes = tuple(np.dtype(l.dtype) for l in leaves)
        shapes = tuple(tuple(l.shape[1:]) for l in leaves)
        offsets = np.cumsum([0] + [int(np.prod(s)) for s in shapes])
        return cls(treedef, shapes, dtypes, tuple(int(o) for o in offsets))


@jax.tree_util.register_pytree_node_class
class PackedTree:
    """A pytree of 32-bit leaves with a leading slice axis K, held as one
    ``(K, W)`` ``uint32`` buffer: each leaf bitcast to ``uint32`` and
    flattened per slice, in leaf order. The buffer is the only pytree leaf;
    the layout is static. ``tree()`` gives the tree back, bit for bit."""

    __slots__ = ("buf", "layout")

    def __init__(self, buf, layout: _Layout):
        self.buf, self.layout = buf, layout

    def tree_flatten(self):
        return (self.buf,), self.layout

    @classmethod
    def tree_unflatten(cls, layout, children):
        return cls(children[0], layout)

    @classmethod
    def pack(cls, tree):
        """Pack ``tree`` (traceable; run under jit, see ``_pack``)."""
        words = [x if x.dtype == jnp.uint32
                 else jax.lax.bitcast_convert_type(x, jnp.uint32)
                 for x in map(jnp.asarray, jax.tree.leaves(tree))]
        k = words[0].shape[0]
        return cls(jnp.concatenate([w.reshape(k, -1) for w in words], axis=1),
                   _Layout.of(tree))

    def _leaf(self, i: int):
        lo, hi = self.layout.offsets[i], self.layout.offsets[i + 1]
        words, dtype = self.buf[:, lo:hi], self.layout.dtypes[i]
        if isinstance(words, np.ndarray):
            x = words.view(dtype)
        elif dtype == np.uint32:
            x = words
        else:
            x = jax.lax.bitcast_convert_type(words, dtype)
        return x.reshape(x.shape[:1] + self.layout.shapes[i])

    def tree(self):
        n = len(self.layout.shapes)
        return jax.tree.unflatten(self.layout.treedef,
                                  [self._leaf(i) for i in range(n)])


def _field_property(name: str) -> property:
    return property(lambda self: getattr(self.tree(), name),
                    doc=f"``SchedulerState.{name}``, unpacked")


@jax.tree_util.register_pytree_node_class
class PackedState(PackedTree):
    """A stacked ``SchedulerState`` packed as one ``(K, Ws)`` ``uint32``
    buffer: what :meth:`FleetEngine.run` returns and takes back. Its fields
    read as the tree's (``state.queues.q``, ``state.rng``)."""

    __slots__ = ()

    queues = _field_property("queues")
    mults = _field_property("mults")
    emp_mults = _field_property("emp_mults")
    t = _field_property("t")
    total_cost = _field_property("total_cost")
    total_trained = _field_property("total_trained")
    uploaded = _field_property("uploaded")
    rng = _field_property("rng")
    het_key = _field_property("het_key")


def _column_property(name: str) -> property:
    i = SlotRecord._fields.index(name)
    return property(lambda self: self.buf[..., i],
                    doc=f"``SlotRecord.{name}``, (T, K)")


@jax.tree_util.register_pytree_node_class
class PackedRecord:
    """Time-major ``SlotRecord``s as one ``(T, K, F)`` ``float32`` buffer,
    F = the record fields in order; each field reads as its ``(T, K)``
    array (``recs.cost``)."""

    __slots__ = ("buf",)
    _fields = SlotRecord._fields

    def __init__(self, buf):
        self.buf = buf

    def tree_flatten(self):
        return (self.buf,), None

    @classmethod
    def tree_unflatten(cls, _, children):
        return cls(children[0])

    @classmethod
    def pack(cls, rec: SlotRecord) -> "PackedRecord":
        return cls(jnp.stack(rec, axis=-1))

    def tree(self) -> SlotRecord:
        return SlotRecord(*(self.buf[..., i] for i in range(len(self._fields))))

    def _replace(self, **fields) -> "PackedRecord":
        """As ``SlotRecord._replace``: the record with some fields replaced."""
        return PackedRecord.pack(self.tree()._replace(**fields))

    cost = _column_property("cost")
    trained = _column_property("trained")
    q_backlog = _column_property("q_backlog")
    r_backlog = _column_property("r_backlog")
    skew = _column_property("skew")


@functools.partial(jax.jit, static_argnums=0)
def _pack(cls, tree):
    """``cls.pack(tree)`` as one small program (one compile per layout)."""
    return cls.pack(tree)


def _as_tree(x):
    """The tree of a packed value; a tree as it is."""
    return x.tree() if isinstance(x, (PackedTree, PackedRecord)) else x


# -- tree helpers -------------------------------------------------------------

def unstack(tree, k: int):
    """Extract slice k from a stacked (K, ...) pytree (state, params), packed
    or not; the result is a tree."""
    return jax.tree.map(lambda l: l[k], _as_tree(tree))


def slice_records(recs, k: int) -> SlotRecord:
    """Slice k's (T,) per-slot trace out of time-major (T, K) fleet records
    (a ``SlotRecord`` or a ``PackedRecord``)."""
    return jax.tree.map(lambda l: l[:, k], _as_tree(recs))


def ragged_pad_shape(shapes: Sequence[ShapeConfig]) -> ShapeConfig:
    """The common compiled shape of a ragged fleet: elementwise max over the
    entity axes. Solver iteration counts are control flow, not padding, so
    they must agree across slices."""
    iters = {s.pair_iters for s in shapes}
    if len(iters) != 1:
        raise ValueError(f"ragged fleet slices must share pair_iters, got {iters}")
    return ShapeConfig(n_cu=max(s.n_cu for s in shapes),
                      n_ec=max(s.n_ec for s in shapes),
                      pair_iters=iters.pop())


def _pairs(m: int) -> int:
    return m * (m - 1) // 2


def _gauge_padding(shapes: Sequence[ShapeConfig], pad: ShapeConfig) -> None:
    """How much of the compiled slot is ragged padding: the CU-EC links and
    EC-pair problems of the true shapes against those the program computes
    at ``pad`` (gauges ``fleet.links_*``, ``fleet.pairs_*``)."""
    k = len(shapes)
    obs.gauge("fleet.links_real", sum(s.n_cu * s.n_ec for s in shapes))
    obs.gauge("fleet.links_compiled", k * pad.n_cu * pad.n_ec)
    obs.gauge("fleet.pairs_real", sum(_pairs(s.n_ec) for s in shapes))
    obs.gauge("fleet.pairs_compiled", k * _pairs(pad.n_ec))


def trim_state(state, shape: ShapeConfig) -> SchedulerState:
    """Drop the ragged padding of a state: slice every entity axis down to
    the true (N, M). The state is one slice's tree, or a stacked tree or
    ``PackedState`` whose leading slice axis is kept (every slice trimmed
    to ``shape``). Padded entries are exactly zero by the mask invariants,
    so this is lossless."""
    state = _as_tree(state)
    lead = (slice(None),) * (jnp.ndim(state.queues.q) - 1)
    cu = lead + (slice(shape.n_cu),)
    pair = cu + (slice(shape.n_ec),)

    def trim_mults(mu: Multipliers) -> Multipliers:
        return Multipliers(mu=mu.mu[cu], eta=mu.eta[pair],
                           phi=mu.phi[pair], lam=mu.lam[pair])

    return state._replace(
        queues=QueueState(q=state.queues.q[cu], r=state.queues.r[pair],
                          omega=state.queues.omega[pair]),
        mults=trim_mults(state.mults),
        emp_mults=trim_mults(state.emp_mults),
        uploaded=state.uploaded[cu],
    )


def scan_slots(shape: ShapeConfig, spec: AlgoSpec, n_slots: int,
               params: SliceParams, state: SchedulerState
               ) -> tuple[SchedulerState, SlotRecord]:
    """The slot program on trees: ``vmap(step)`` over the K slices inside
    one scan. Records come back time-major (T, K)."""
    def one_slot(p, s):
        s2, rec, _ = step(shape, spec, s, params=p)
        return s2, rec

    vstep = jax.vmap(one_slot)

    def body(s, _):
        return vstep(params, s)

    return jax.lax.scan(body, state, None, length=n_slots)


@functools.partial(jax.jit, static_argnums=(0, 1, 2),
                   static_argnames=("mesh", "axis_name"))
def _fleet_scan(shape: ShapeConfig, spec: AlgoSpec, n_slots: int,
                params: PackedTree, state: PackedState, mesh=None,
                axis_name: str = "data"
                ) -> tuple[PackedState, PackedRecord]:
    """The slot program on the packed boundary: unpack, :func:`scan_slots`,
    pack. Two buffers in, two out.

    With ``mesh``, the program runs per shard of the K axis
    (``jax.shard_map``): slices are independent, so each device scans its
    own K / n slices with no communication. This is also what lets the
    Pallas matchers run sharded: Mosaic kernels are not partitioned
    automatically."""
    def scan(params, state):
        s2, rec = scan_slots(shape, spec, n_slots, params.tree(), state.tree())
        return PackedState.pack(s2), PackedRecord.pack(rec)

    if mesh is None:
        return scan(params, state)
    k = PartitionSpec(axis_name)
    # check_vma off: pallas_call out_shapes carry no varying-axes annotation
    return jax.shard_map(scan, mesh=mesh, in_specs=(k, k),
                         out_specs=(k, PartitionSpec(None, axis_name)),
                         check_vma=False)(params, state)


def _stacked_slice_count(params: SliceParams) -> int:
    """K of a stacked (K, ...) params pytree, validating that every non-None
    leaf agrees on the leading (slice) axis. Raises naming the offending leaf
    instead of silently mis-reading an unstacked pytree."""
    k: Optional[int] = None
    first = None
    for name, leaf in zip(SliceParams._fields, params):
        if leaf is None:
            continue
        if jnp.ndim(leaf) == 0:
            raise ValueError(
                f"SliceParams leaf {name!r} is rank-0: params look unstacked "
                "(no leading slice axis); stack K slices with "
                "stack_slice_params first")
        n = jnp.shape(leaf)[0]
        if k is None:
            k, first = int(n), name
        elif n != k:
            raise ValueError(
                f"inconsistent leading (slice) axis across SliceParams leaves: "
                f"{first!r} has K={k} but {name!r} has K={n}")
    if k is None:
        raise ValueError("SliceParams has no array leaves (every field is "
                         "None); build it with SliceParams.from_config / "
                         "stack_slice_params")
    return k


@dataclasses.dataclass(frozen=True)
class FleetEngine:
    """K-slice batch scheduler: vmapped ``step`` inside one jitted scan.

    Build with :meth:`from_jobs` (a list of ``SliceJob`` — handles
    homogeneous, ragged-shape and mixed-policy fleets uniformly), or adopt a
    pre-stacked ``SliceParams`` pytree via :meth:`from_params`.
    """

    shape: ShapeConfig
    spec: AlgoSpec
    params: SliceParams  # stacked, leading axis K
    n_slices: int
    seeds: tuple[int, ...]
    # Per-slice *true* shapes (== (shape,) * K for non-ragged fleets). Only
    # metadata: used by slice_state to trim the padding back off.
    slice_shapes: Optional[tuple[ShapeConfig, ...]] = None
    # Per-slice AlgoSpec (metadata; the compiled program runs self.spec,
    # which is SWITCHED for mixed-policy fleets).
    slice_specs: Optional[tuple[AlgoSpec, ...]] = None

    def __post_init__(self):
        if self.spec.exact:
            raise ValueError("exact (host-side oracle) specs cannot be vmapped; "
                             "use datasche.run per slice instead")

    @classmethod
    def from_jobs(cls, jobs: Sequence[JobLike],
                  spec: AlgoSpec = DS) -> "FleetEngine":
        """THE fleet constructor: one ``SliceJob`` per slice.

        Transparently composes every supported axis of heterogeneity:
        numeric params always differ freely; mixed true (N, M) are padded to
        the elementwise-max shape with entity masks; mixed ``AlgoSpec`` run
        under branch-free ``SWITCHED`` dispatch (policy leaves +
        ``lax.switch``), so the whole fleet is still ONE compiled program.
        Bare ``CocktailConfig`` entries are accepted and get ``spec``.
        Sets the padding gauges ``fleet.links_*``/``fleet.pairs_*``
        (``repro.obs``) for the fleet it builds.
        """
        with obs.span("fleet.from_jobs"):
            jobs = as_jobs(jobs, spec)
            if not jobs:
                raise ValueError("need at least one SliceJob")
            pad = ragged_pad_shape([j.shape for j in jobs])
            policies = {(j.spec.collection, j.spec.training, j.spec.use_lsa,
                         j.spec.learning_aid) for j in jobs}
            # Distinct specs with identical policy tuples (e.g. DS vs
            # GREEDY) still compile one static program — switch only when
            # policies differ. The policy leaves are filled either way, so
            # the params always state what each slice runs (static dispatch
            # just ignores them). Mixed fleets without an L-DS slice get the
            # virtual path compiled out.
            mixed = len(policies) > 1
            any_aid = any(j.spec.learning_aid for j in jobs)
            switch_spec = SWITCHED if any_aid else SWITCHED_NOAID
            _gauge_padding([j.shape for j in jobs], pad)
            return cls(
                shape=pad,
                spec=switch_spec if mixed else jobs[0].spec,
                params=stack_slice_params(
                    [j.params(pad_shape=pad, policy_leaves=True) for j in jobs]),
                n_slices=len(jobs),
                seeds=tuple(j.resolved_seed for j in jobs),
                slice_shapes=tuple(j.shape for j in jobs),
                slice_specs=tuple(j.spec for j in jobs),
            )

    @classmethod
    def from_configs(cls, configs: Sequence[CocktailConfig],
                     spec: AlgoSpec = DS) -> "FleetEngine":
        """Deprecated shim over :meth:`from_jobs` (kept for older call sites;
        it still *rejects* mixed shapes, which from_jobs would pad)."""
        if not configs:
            raise ValueError("need at least one slice config")
        shapes = {c.shape for c in configs}
        if len(shapes) != 1:
            raise ValueError(f"fleet slices must share one ShapeConfig, got {shapes}; "
                             "pad mixed shapes with from_jobs/from_ragged_configs")
        return cls.from_jobs([SliceJob(config=c, spec=spec) for c in configs])

    @classmethod
    def from_ragged_configs(cls, configs: Sequence[CocktailConfig],
                            spec: AlgoSpec = DS) -> "FleetEngine":
        """Deprecated shim over :meth:`from_jobs`: batch slices of different
        true (N, M) into one compiled program via padding + entity masks."""
        return cls.from_jobs([SliceJob(config=c, spec=spec) for c in configs])

    @classmethod
    def from_params(cls, shape: ShapeConfig, params: SliceParams,
                    spec: AlgoSpec = DS,
                    seeds: Optional[Sequence[int]] = None) -> "FleetEngine":
        """Adopt an already-stacked (K, ...) SliceParams pytree."""
        k = _stacked_slice_count(params)
        seeds = tuple(seeds) if seeds is not None else tuple(range(k))
        if len(seeds) != k:
            raise ValueError(f"{k} slices but {len(seeds)} seeds")
        return cls(shape=shape, spec=spec, params=params, n_slices=k, seeds=seeds)

    # -- state ------------------------------------------------------------

    def init(self) -> SchedulerState:
        """Stacked initial state: slice k gets params[k] and PRNGKey(seeds[k])."""
        with obs.span("fleet.init"):
            states = [init_state(self.shape, unstack(self.params, k),
                                 seed=self.seeds[k])
                      for k in range(self.n_slices)]
            return jax.tree.map(lambda *ls: jnp.stack(ls), *states)

    def slice_state(self, state, k: int) -> SchedulerState:
        """Slice k's SchedulerState (for per-slice metrics.summary etc.),
        from a stacked tree or a ``PackedState``.

        Ragged fleets: the padding is trimmed back off, so the result has the
        slice's true (N, M) and drops straight into shape-aware consumers
        (metrics.summary against the original CocktailConfig)."""
        sk = unstack(state, k)
        if self.slice_shapes is not None and self.slice_shapes[k] != self.shape:
            sk = trim_state(sk, self.slice_shapes[k])
        return sk

    # -- execution --------------------------------------------------------

    def step(self, state) -> tuple[SchedulerState, SlotRecord, Decision]:
        """One fleet slot (eager vmap; prefer :meth:`run` for loops)."""
        new_state, rec, dec = jax.vmap(
            lambda p, s: step(self.shape, self.spec, s, params=p)
        )(self.params, _as_tree(state))
        return new_state, rec, dec

    def run(self, n_slots: int,
            state: "PackedState | SchedulerState | None" = None, mesh=None,
            axis_name: str = "data") -> tuple[PackedState, PackedRecord]:
        """Run the whole fleet for n_slots inside one jitted scan.

        ``state`` is a ``PackedState`` (what ``run`` returns), a stacked
        ``SchedulerState`` (packed first, span ``fleet.pack``) or None (the
        initial state). Returns (the final state as a ``PackedState`` (K,
        ...), the records as a ``PackedRecord`` (T, K)); both read like
        the trees, and ``.tree()`` gives the trees. With ``mesh``, the K
        axis of params/state is sharded over ``mesh[axis_name]`` before the
        scan (K % axis size must be 0) and each device runs the slot
        program on its own slices. The call returns once the program is
        dispatched (span ``fleet.run``).
        """
        with obs.span("fleet.run"):
            return _fleet_scan(self.shape, self.spec, n_slots,
                               *self._placed(state, mesh, axis_name),
                               mesh=mesh, axis_name=axis_name)

    def lower(self, n_slots: int,
              state: "PackedState | SchedulerState | None" = None, mesh=None,
              axis_name: str = "data"):
        """The slot program :meth:`run` executes, lowered but not run:
        ``.compile()`` gives the executable (a cache hit after a ``run`` of
        the same shapes) and ``.compile().as_text()`` its HLO."""
        return _fleet_scan.lower(self.shape, self.spec, n_slots,
                                 *self._placed(state, mesh, axis_name),
                                 mesh=mesh, axis_name=axis_name)

    @functools.cached_property
    def packed_params(self) -> PackedTree:
        """``params`` as the slot program takes them, packed once."""
        return _pack(PackedTree, self.params)

    def _placed(self, state, mesh, axis_name):
        """(params, state) packed as the slot program takes them: the
        initial state by default, the K axis sharded over
        ``mesh[axis_name]`` with a mesh."""
        if state is None:
            state = self.init()
        if not isinstance(state, PackedState):
            with obs.span("fleet.pack"):
                state = _pack(PackedState, state)
        params = self.packed_params
        if mesh is not None:
            from ..launch.mesh import shard_leading_axis
            params = shard_leading_axis(params, mesh, axis_name)
            state = shard_leading_axis(state, mesh, axis_name)
        return params, state
