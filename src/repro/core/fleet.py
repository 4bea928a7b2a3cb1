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
  * stacked ``SlotRecord`` returned by :meth:`FleetEngine.run`: time-major
    (T, K) — axis 0 is the slot, matching single-slice ``run``'s (T,)
  * optional device sharding splits the K axis over a mesh axis via
    ``launch.mesh.shard_leading_axis`` (NamedSharding, trailing axes
    replicated)

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
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from .datasche import AlgoSpec, DS, SWITCHED, SWITCHED_NOAID, SlotRecord, step
from .job import JobLike, SliceJob, as_jobs
from .types import (CocktailConfig, Decision, Multipliers, QueueState,
                    SchedulerState, ShapeConfig, SliceParams, init_state,
                    split_config, stack_slice_params)


def unstack(tree, k: int):
    """Extract slice k from a stacked (K, ...) pytree (state, params)."""
    return jax.tree.map(lambda l: l[k], tree)


def slice_records(recs: SlotRecord, k: int) -> SlotRecord:
    """Slice k's (T,) per-slot trace out of time-major (T, K) fleet records."""
    return jax.tree.map(lambda l: l[:, k], recs)


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


def trim_state(state: SchedulerState, shape: ShapeConfig) -> SchedulerState:
    """Drop the ragged padding of one slice's state: slice every entity axis
    down to the true (N, M). Padded entries are exactly zero by the mask
    invariants, so this is lossless."""
    n, m = shape.n_cu, shape.n_ec

    def trim_mults(mu: Multipliers) -> Multipliers:
        return Multipliers(mu=mu.mu[:n], eta=mu.eta[:n, :m],
                           phi=mu.phi[:n, :m], lam=mu.lam[:n, :m])

    return state._replace(
        queues=QueueState(q=state.queues.q[:n], r=state.queues.r[:n, :m],
                          omega=state.queues.omega[:n, :m]),
        mults=trim_mults(state.mults),
        emp_mults=trim_mults(state.emp_mults),
        uploaded=state.uploaded[:n],
    )


@functools.partial(jax.jit, static_argnums=(0, 1, 2),
                   static_argnames=("mesh", "axis_name"))
def _fleet_scan(shape: ShapeConfig, spec: AlgoSpec, n_slots: int,
                params: SliceParams, state: SchedulerState, mesh=None,
                axis_name: str = "data"
                ) -> tuple[SchedulerState, SlotRecord]:
    """The slot program: ``vmap(step)`` over the K slices inside one scan.

    With ``mesh``, the program runs per shard of the K axis
    (``jax.shard_map``): slices are independent, so each device scans its
    own K / n slices with no communication. This is also what lets the
    Pallas matchers run sharded: Mosaic kernels are not partitioned
    automatically."""
    def one_slot(p, s):
        s2, rec, _ = step(shape, spec, s, params=p)
        return s2, rec

    def scan(params, state):
        vstep = jax.vmap(one_slot)

        def body(s, _):
            s2, rec = vstep(params, s)
            return s2, rec

        return jax.lax.scan(body, state, None, length=n_slots)

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
        """
        jobs = as_jobs(jobs, spec)
        if not jobs:
            raise ValueError("need at least one SliceJob")
        pad = ragged_pad_shape([j.shape for j in jobs])
        policies = {(j.spec.collection, j.spec.training, j.spec.use_lsa,
                     j.spec.learning_aid) for j in jobs}
        # Distinct specs with identical policy tuples (e.g. DS vs GREEDY)
        # still compile one static program — switch only when policies differ.
        # The policy leaves are filled either way, so the params always state
        # what each slice runs (static dispatch just ignores them). Mixed
        # fleets without an L-DS slice get the virtual path compiled out.
        mixed = len(policies) > 1
        any_aid = any(j.spec.learning_aid for j in jobs)
        switch_spec = SWITCHED if any_aid else SWITCHED_NOAID
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
        states = [init_state(self.shape, unstack(self.params, k), seed=self.seeds[k])
                  for k in range(self.n_slices)]
        return jax.tree.map(lambda *ls: jnp.stack(ls), *states)

    def slice_state(self, state: SchedulerState, k: int) -> SchedulerState:
        """Slice k's SchedulerState (for per-slice metrics.summary etc.).

        Ragged fleets: the padding is trimmed back off, so the result has the
        slice's true (N, M) and drops straight into shape-aware consumers
        (metrics.summary against the original CocktailConfig)."""
        sk = unstack(state, k)
        if self.slice_shapes is not None and self.slice_shapes[k] != self.shape:
            sk = trim_state(sk, self.slice_shapes[k])
        return sk

    # -- execution --------------------------------------------------------

    def step(self, state: SchedulerState
             ) -> tuple[SchedulerState, SlotRecord, Decision]:
        """One fleet slot (eager vmap; prefer :meth:`run` for loops)."""
        new_state, rec, dec = jax.vmap(
            lambda p, s: step(self.shape, self.spec, s, params=p)
        )(self.params, state)
        return new_state, rec, dec

    def run(self, n_slots: int, state: Optional[SchedulerState] = None,
            mesh=None, axis_name: str = "data"
            ) -> tuple[SchedulerState, SlotRecord]:
        """Run the whole fleet for n_slots inside one jitted scan.

        Returns (stacked final state (K, ...), stacked records (T, K)).
        With ``mesh``, the K axis of params/state is sharded over
        ``mesh[axis_name]`` before the scan (K % axis size must be 0) and
        each device runs the slot program on its own slices.
        """
        return _fleet_scan(self.shape, self.spec, n_slots,
                           *self._placed(state, mesh, axis_name), mesh=mesh,
                           axis_name=axis_name)

    def lower(self, n_slots: int, state: Optional[SchedulerState] = None,
              mesh=None, axis_name: str = "data"):
        """The slot program :meth:`run` executes, lowered but not run:
        ``.compile()`` gives the executable (a cache hit after a ``run`` of
        the same shapes) and ``.compile().as_text()`` its HLO."""
        return _fleet_scan.lower(self.shape, self.spec, n_slots,
                                 *self._placed(state, mesh, axis_name),
                                 mesh=mesh, axis_name=axis_name)

    def _placed(self, state, mesh, axis_name):
        """(params, state) as the slot program takes them: the initial state
        by default, the K axis sharded over ``mesh[axis_name]`` with a mesh."""
        if state is None:
            state = self.init()
        params = self.params
        if mesh is not None:
            from ..launch.mesh import shard_leading_axis
            params = shard_leading_axis(params, mesh, axis_name)
            state = shard_leading_axis(state, mesh, axis_name)
        return params, state
