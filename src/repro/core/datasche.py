"""DataSche and Learning-aid DataSche online scheduling algorithms (Sec. III).

The per-slot pipeline is

  1. observe network state S(t) (or sample the stochastic generator),
  2. solve the collection subproblem  -> alpha, theta      (P1' / P1 / full)
  3. solve the training subproblem    -> x, y, z           (P2' / linear / ...)
  4. execute: update queues Q, R, cumulative Omega, framework cost,
  5. SGD-update the Lagrange multipliers (step eps); L-DS additionally keeps
     empirical multipliers Theta' updated from *virtual* plain-P1/P2 decisions
     with a diminishing step and schedules with Theta~ = Theta + Theta' - pi.

Policies are selected by an ``AlgoSpec`` so every paper baseline (NO-SDC,
NO-SLT, NO-LSA, Greedy, ECFull, ECSelf, CUFull) is a one-line variant.
``exact=False`` (production) is fully jittable and driven by ``lax.scan``;
``exact=True`` swaps the greedy matchers for the networkx Thm.-1/Thm.-2
oracles and runs a host loop.

Policy dispatch runs off two indexed registries, ``COLLECTION_POLICIES`` and
``TRAINING_POLICIES`` (see ``PolicyTable``), in one of two modes: Python-static
(table lookup by ``spec.collection``/``spec.training`` at trace time) or
branch-free (``SWITCHED`` spec: ``jax.lax.switch`` over the table indexed by
the ``SliceParams`` policy leaves, filled by ``with_policy``). The branch-free
mode is what lets a fleet mix *different* algorithms per slice inside one
compiled program (``fleet.FleetEngine.from_jobs``).

Batch-first convention: everything numeric that can differ between network
slices lives in a ``SliceParams`` pytree (traced), while shapes and control
flow live in the hashable ``ShapeConfig`` (static). ``step``/``run`` accept
either the frontend ``CocktailConfig`` or an explicit split; a fleet of K
slices is ``jax.vmap`` of ``step`` over stacked params/state (see
``repro.core.fleet``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import training_alloc
# Production matching goes through the kernels dispatch layer: Pallas on TPU,
# identical jnp refs elsewhere, batch-compatible and mask-aware. No cycle:
# kernels/matching depends only on core.types.
from ..kernels.matching import ops as matching_ops
from .network import framework_cost, sample_network_state
from .types import (MASKED_WEIGHT, CocktailConfig, Decision, Multipliers,
                    NetworkState, QueueState, SchedulerState, ShapeConfig,
                    SliceParams, entity_masks, init_state, mask_pairs,
                    split_config)

_TINY = 1e-9
_NEG = MASKED_WEIGHT  # masked-entity weight (see types.mask_pairs)


class PolicyTable:
    """Ordered, registry-backed policy table.

    Every entry shares one call signature, so the same table serves both
    dispatch paths: Python-static (``table[spec.collection]``, one compiled
    program per spec) and branch-free (``jax.lax.switch`` over ``table.fns``
    indexed by a traced ``SliceParams`` policy leaf, one compiled program for
    a whole mixed-policy fleet). Registration order fixes the integer ids, so
    ids are stable across processes as long as registration is module-level.
    """

    def __init__(self, kind: str):
        self.kind = kind
        self._entries: dict[str, int] = {}  # name -> index (insertion order)
        self._fns: list = []

    def register(self, name: str):
        """Decorator: append ``fn`` under ``name`` with the next free id."""
        def deco(fn):
            if name in self._entries:
                raise ValueError(f"{self.kind} policy {name!r} already registered")
            self._entries[name] = len(self._fns)
            self._fns.append(fn)
            return fn
        return deco

    @property
    def names(self) -> tuple[str, ...]:
        return tuple(self._entries)

    @property
    def fns(self) -> tuple:
        """Implementations in id order — the ``lax.switch`` branch list."""
        return tuple(self._fns)

    def index(self, name: str) -> int:
        try:
            return self._entries[name]
        except KeyError:
            raise KeyError(f"unknown {self.kind} policy {name!r}; "
                           f"registered: {list(self._entries)}") from None

    def __getitem__(self, name: str):
        return self._fns[self.index(name)]

    def __contains__(self, name: str) -> bool:
        return name in self._entries

    def __len__(self) -> int:
        return len(self._fns)

    def __iter__(self):
        return iter(self._entries)


COLLECTION_POLICIES = PolicyTable("collection")
TRAINING_POLICIES = PolicyTable("training")

# Sentinel policy name selecting branch-free dispatch (see SWITCHED below).
_SWITCH = "switch"


@dataclasses.dataclass(frozen=True)
class AlgoSpec:
    """Which variant of the scheduler to run (paper Sec. IV benchmarks).

    ``collection``/``training`` name entries of ``COLLECTION_POLICIES`` /
    ``TRAINING_POLICIES``; the special value ``"switch"`` defers the choice to
    the ``SliceParams`` policy leaves at runtime (branch-free dispatch, see
    ``SWITCHED``/``with_policy``).
    """

    name: str = "ds"
    collection: str = "skew"  # skew | plain | cufull | switch
    training: str = "skew"  # skew | linear | solo | ecfull | switch
    use_lsa: bool = True  # long-term skew amendment (phi/lam multipliers)
    learning_aid: bool = False
    exact: bool = False  # exact Thm.1/Thm.2 matching oracles (host-side)

    @property
    def switched(self) -> bool:
        """True if this spec defers policy choice to the params leaves."""
        return self.collection == _SWITCH or self.training == _SWITCH


DS = AlgoSpec(name="ds")
DS_EXACT = AlgoSpec(name="ds-exact", exact=True)
LDS = AlgoSpec(name="l-ds", learning_aid=True)
NO_SDC = AlgoSpec(name="no-sdc", collection="plain")
NO_SLT = AlgoSpec(name="no-slt", training="linear")
NO_LSA = AlgoSpec(name="no-lsa", use_lsa=False)
GREEDY = AlgoSpec(name="greedy")  # greedy matchers == production path
EC_FULL = AlgoSpec(name="ecfull", training="ecfull")
EC_SELF = AlgoSpec(name="ecself", training="solo")
CU_FULL = AlgoSpec(name="cufull", collection="cufull")

# Branch-free dispatch: policy choice is jax.lax.switch over the tables,
# indexed by the SliceParams policy leaves (with_policy). use_lsa on the spec
# is ignored — the leaves carry it as a {0,1} float32 gate (selects, never a
# Python `if`) — so K slices running DIFFERENT paper variants vmap into ONE
# compiled program (fleet.from_jobs). spec.learning_aid keeps ONE static
# role: it decides whether the L-DS virtual-update path is compiled into the
# program at all (it runs every slot, gated per slice by the learning_aid
# leaf). SWITCHED_NOAID compiles it out — use it when no slice of the fleet
# runs L-DS (from_jobs picks automatically); under it the learning_aid leaf
# is ignored entirely.
SWITCHED = AlgoSpec(name="switched", collection=_SWITCH, training=_SWITCH,
                    learning_aid=True)
SWITCHED_NOAID = AlgoSpec(name="switched-noaid", collection=_SWITCH,
                          training=_SWITCH)

ALL_SPECS = {s.name: s for s in
             [DS, DS_EXACT, LDS, NO_SDC, NO_SLT, NO_LSA, GREEDY, EC_FULL, EC_SELF, CU_FULL]}


def _pin_default_policy_ids() -> None:
    # SliceParams.from_config (types.py) defaults the policy leaves to DS
    # without importing this module; fail fast at import if table order ever
    # drifts (a real raise, not assert: must survive python -O).
    if (COLLECTION_POLICIES.index(DS.collection) != 0
            or TRAINING_POLICIES.index(DS.training) != 0
            or not DS.use_lsa or DS.learning_aid):
        raise RuntimeError(
            "policy table order drifted: SliceParams.from_config hardcodes "
            "the DS policy leaves as collect_id=0/train_id=0/use_lsa=1/"
            "learning_aid=0 (types.py); keep DS's policies registered first "
            "or update those defaults")


def with_policy(params: SliceParams, spec: AlgoSpec) -> SliceParams:
    """Fill the policy leaves of ``params`` from a static ``spec`` so the
    slice can run under branch-free (``SWITCHED``) dispatch."""
    if spec.exact:
        raise ValueError(f"spec {spec.name!r} is exact (host-side oracles); "
                         "it has no branch-free dispatch path")
    if spec.switched:
        raise ValueError("with_policy needs a concrete spec, not SWITCHED")
    return params._replace(
        collect_id=jnp.asarray(COLLECTION_POLICIES.index(spec.collection), jnp.int32),
        train_id=jnp.asarray(TRAINING_POLICIES.index(spec.training), jnp.int32),
        use_lsa=jnp.asarray(1.0 if spec.use_lsa else 0.0, jnp.float32),
        learning_aid=jnp.asarray(1.0 if spec.learning_aid else 0.0, jnp.float32),
    )


# --------------------------------------------------------------------------
# Weights (the per-slot dual prices entering P1'/P2')
# --------------------------------------------------------------------------

def collection_weights(net: NetworkState, mults: Multipliers,
                       cu_mask: Optional[jax.Array] = None,
                       ec_mask: Optional[jax.Array] = None) -> jax.Array:
    """w_ij = d_ij (mu_i - eta_ij - c_ij); the P1' utility rate.

    Ragged padding: entries whose CU or EC is masked are forced to 0 (the
    sampler already zeroes d there, but a caller-supplied net need not), so
    no collection policy can ever select them (they all require w > 0)."""
    w = net.d * (mults.mu[:, None] - mults.eta - net.c)
    if cu_mask is not None or ec_mask is not None:
        cu = cu_mask if cu_mask is not None else jnp.ones_like(w[:, 0])
        ec = ec_mask if ec_mask is not None else jnp.ones_like(w[0, :])
        w = mask_pairs(w, cu, ec, fill=0.0)
    return w


def training_weights(cfg: CocktailConfig | ShapeConfig, net: NetworkState,
                     mults: Multipliers, use_lsa: bool | jax.Array,
                     params: Optional[SliceParams] = None) -> tuple[jax.Array, jax.Array]:
    """Returns (beta (N,M), gamma (N,M,M)).

    beta[i,j]    weight of x[i,j]   (eq. 18 x-coefficient)
    gamma[i,j,k] weight of y[i,j,k] (from queue R[i,j], trained at EC k)
                 = beta[i,k] + eta[i,j] - eta[i,k] - e[j,k]

    ``use_lsa`` is a Python bool on the static dispatch path and a traced
    {0,1} float32 gate under SWITCHED dispatch; the gate multiplies phi/lam,
    which is bit-exact against both static branches (x*1 == x, finite x*0 == 0).

    Ragged padding: any entry touching a masked CU/EC is forced to the large
    negative ``_NEG`` so every training solver (waterfill/coordinate-ascent/
    knapsack) treats it as inactive and allocates exactly zero there.
    """
    _, params = split_config(cfg, params)
    if isinstance(use_lsa, bool):
        phi = mults.phi if use_lsa else jnp.zeros_like(mults.phi)
        lam = mults.lam if use_lsa else jnp.zeros_like(mults.lam)
    else:
        gate = jnp.asarray(use_lsa, jnp.float32)
        phi = mults.phi * gate
        lam = mults.lam * gate
    d_hi, d_lo = params.delta_hi, params.delta_lo
    common = jnp.sum(lam * d_hi[:, None] - phi * d_lo[:, None], axis=0)  # (M,)
    beta = -net.p[None, :] + mults.eta - lam + phi + common[None, :]
    gamma = (beta[:, None, :] + mults.eta[:, :, None]
             - mults.eta[:, None, :] - net.e[None, :, :])
    cu, ec = entity_masks(params)
    beta = mask_pairs(beta, cu, ec)
    gamma = jnp.where(
        (cu[:, None, None] * ec[None, :, None] * ec[None, None, :]) > 0,
        gamma, _NEG)
    return beta, gamma


# --------------------------------------------------------------------------
# Collection policies — shared signature (shape, params, net, mults, queues,
# exact) -> (alpha, theta); registration order fixes the lax.switch branch id.
# --------------------------------------------------------------------------

@COLLECTION_POLICIES.register("skew")
def _collect_skew(shape, params, net, mults, queues, exact):
    cu, ec = entity_masks(params)
    w = collection_weights(net, mults, cu, ec)
    logw = jnp.where(w > 0, jnp.log(jnp.maximum(w, _TINY)), -jnp.inf)
    if exact:
        from . import oracle
        alpha, theta = oracle.exact_collection(np.asarray(logw))
        return jnp.asarray(alpha), jnp.asarray(theta)
    # Kernel-dispatched (Pallas on TPU): the masks are redundant with the
    # masked weights above but pin the padded-pair invariant at the boundary.
    return matching_ops.greedy_collection(logw, cu_mask=cu, ec_mask=ec)


@COLLECTION_POLICIES.register("plain")
def _collect_plain(shape, params, net, mults, queues, exact):
    cu, ec = entity_masks(params)
    w = collection_weights(net, mults)
    # Production path dispatches through the kernels layer: Pallas on TPU,
    # the (identical) jnp greedy elsewhere; both vmap over a slice axis and
    # take the entity masks (masked pairs can never be assigned).
    alpha = matching_ops.greedy_assignment(w, cu_mask=cu, ec_mask=ec)
    return alpha, alpha  # theta = 1 on the selected connection


@COLLECTION_POLICIES.register("cufull")
def _collect_cufull(shape, params, net, mults, queues, exact):
    # Full connection over the *real* entities only: every real EC slot is
    # shared evenly by the n_real connected CUs (theta = 1/n_real each).
    cu, ec = entity_masks(params)
    n_real = jnp.maximum(jnp.sum(cu), 1.0)
    alpha = cu[:, None] * ec[None, :]
    theta = alpha / n_real
    return alpha, theta


# --------------------------------------------------------------------------
# Training policies — shared signature (shape, params, net, mults, queues,
# exact, use_lsa) -> (x, y, z); registered in the same indexed-table scheme.
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _pair_index(m: int) -> tuple[np.ndarray, np.ndarray]:
    # Cached per M: this is hit on every trace of every policy variant and
    # np.triu_indices is pure host-side work.
    pj, pk = np.triu_indices(m, k=1)
    return pj.astype(np.int32), pk.astype(np.int32)


def _compose_from_match(match, x_solo, pairs, pa, m):
    """Assemble (x, y, z) from the matching and the pre-solved allocations."""
    pj, pk = pairs
    onehot_j = jax.nn.one_hot(pj, m, dtype=x_solo.dtype)  # (P, M)
    onehot_k = jax.nn.one_hot(pk, m, dtype=x_solo.dtype)
    sel = match[pj, pk]  # (P,) 1 if pair matched
    diag = jnp.diagonal(match)  # (M,)

    # float32 throughout: at the default precision the TPU rounds the
    # operands of these contractions to bfloat16.
    ein = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)
    x = x_solo * diag[None, :]
    x = x + ein("pn,pm->nm", pa.x_j * sel[:, None], onehot_j)
    x = x + ein("pn,pm->nm", pa.x_k * sel[:, None], onehot_k)
    y = ein("pn,pm,pl->nml", pa.y_jk * sel[:, None], onehot_j, onehot_k)
    y = y + ein("pn,pm,pl->nml", pa.y_kj * sel[:, None], onehot_k, onehot_j)
    z = match * (1.0 - jnp.eye(m, dtype=match.dtype))
    return x, y, z


def _train_generic(shape, params, net, mults, queues, exact, use_lsa, solo_fn, pair_fn):
    m = shape.n_ec
    pj, pk = _pair_index(m)
    pj_a, pk_a = jnp.asarray(pj), jnp.asarray(pk)
    with jax.named_scope("allocation"):
        beta, gamma = training_weights(shape, net, mults, use_lsa, params)
        budgets = net.f / params.rho
        x_solo, val_solo = jax.vmap(solo_fn, in_axes=(1, 1, 0), out_axes=(1, 0))(
            beta, queues.r, budgets)

        def one_pair(j, k):
            return pair_fn(
                beta[:, j], gamma[:, k, j], beta[:, k], gamma[:, j, k],
                queues.r[:, j], queues.r[:, k], budgets[j], budgets[k],
                net.cap_d[j, k])

        pa = jax.vmap(one_pair)(pj_a, pk_a)
        pair_vals = jnp.zeros((m, m), jnp.float32).at[pj_a, pk_a].set(pa.value)
        pair_vals = pair_vals + pair_vals.T

    # Ragged padding: a masked EC must never be solo-selected nor paired (a
    # (real, padded) pair would otherwise shadow the real EC's solo option —
    # its value approximates the solo objective by a different solver). The
    # greedy path delegates the identical masking to the ops dispatch layer.
    with jax.named_scope("pairing"):
        _, ec = entity_masks(params)
        if exact:
            from . import oracle
            val_solo = jnp.where(ec > 0, val_solo, _NEG)
            pair_vals = mask_pairs(pair_vals, ec, ec)
            match = jnp.asarray(oracle.exact_pairing(np.asarray(val_solo),
                                                     np.asarray(pair_vals)))
        else:
            match = matching_ops.greedy_pairing(val_solo, pair_vals, ec_mask=ec)
        return _compose_from_match(match, x_solo, (pj_a, pk_a), pa, m)


@TRAINING_POLICIES.register("skew")
def _train_skew(shape, params, net, mults, queues, exact, use_lsa):
    pair_fn = functools.partial(training_alloc.pair_allocate, iters=shape.pair_iters)
    return _train_generic(shape, params, net, mults, queues, exact, use_lsa,
                          training_alloc.solo_waterfill, pair_fn)


@TRAINING_POLICIES.register("linear")
def _train_linear(shape, params, net, mults, queues, exact, use_lsa):
    return _train_generic(shape, params, net, mults, queues, exact, use_lsa,
                          training_alloc.linear_solo, training_alloc.linear_pair)


@TRAINING_POLICIES.register("solo")
def _train_solo(shape, params, net, mults, queues, exact, use_lsa):
    with jax.named_scope("allocation"):
        beta, _ = training_weights(shape, net, mults, use_lsa, params)
        budgets = net.f / params.rho
        x, _ = jax.vmap(training_alloc.solo_waterfill, in_axes=(1, 1, 0),
                        out_axes=(1, 0))(beta, queues.r, budgets)
    m = shape.n_ec
    return x, jnp.zeros((shape.n_cu, m, m), jnp.float32), jnp.zeros((m, m), jnp.float32)


@TRAINING_POLICIES.register("ecfull")
def _train_ecfull(shape, params, net, mults, queues, exact, use_lsa):
    with jax.named_scope("allocation"):
        beta, gamma = training_weights(shape, net, mults, use_lsa, params)
        budgets = net.f / params.rho
        x, y, _ = training_alloc.full_allocate(beta, gamma, queues.r, budgets,
                                               net.cap_d)
    m = shape.n_ec
    _, ec = entity_masks(params)
    z = (jnp.ones((m, m), jnp.float32) - jnp.eye(m, dtype=jnp.float32))
    return x, y, z * (ec[:, None] * ec[None, :])


# --------------------------------------------------------------------------
# Dynamics (queues + multiplier SGD)
# --------------------------------------------------------------------------

def _served(dec_alpha, dec_theta, net, queues):
    """Samples actually moved CU->EC: alpha*theta*d, capped by Q backlog."""
    req = dec_alpha * dec_theta * net.d
    tot = jnp.sum(req, axis=1)
    scale = jnp.minimum(1.0, queues.q / jnp.maximum(tot, _TINY))
    return req * scale[:, None]


def update_multipliers(cfg: CocktailConfig | ShapeConfig, mults: Multipliers,
                       net: NetworkState, served: jax.Array, x: jax.Array,
                       y: jax.Array, use_lsa: bool | jax.Array,
                       step: jax.Array | float,
                       params: Optional[SliceParams] = None) -> Multipliers:
    _, params = split_config(cfg, params)
    dep_r = x + jnp.sum(y, axis=2)  # leaves queue R[i,j]
    trained_at = x + jnp.sum(y, axis=1)  # trained at EC k
    tot_j = jnp.sum(trained_at, axis=0)
    d_hi, d_lo = params.delta_hi, params.delta_lo

    # Ragged padding: masked entities see zero flows, so their gradients are
    # already zero; the explicit mask products pin the invariant (padded
    # multipliers stay exactly 0) independent of upstream guarantees.
    cu, ec = entity_masks(params)
    link = cu[:, None] * ec[None, :]
    mu = jnp.maximum(mults.mu + step * (net.arrivals - jnp.sum(served, axis=1)), 0.0) * cu
    eta = jnp.maximum(mults.eta + step * (served - dep_r), 0.0) * link
    if isinstance(use_lsa, bool) and not use_lsa:
        phi, lam = mults.phi, mults.lam
    else:
        phi = jnp.maximum(mults.phi + step * (d_lo[:, None] * tot_j[None, :] - trained_at), 0.0) * link
        lam = jnp.maximum(mults.lam + step * (trained_at - d_hi[:, None] * tot_j[None, :]), 0.0) * link
        if not isinstance(use_lsa, bool):
            # Traced {0,1} gate (SWITCHED dispatch): select, never a Python if.
            gate = jnp.asarray(use_lsa, jnp.float32) > 0
            phi = jnp.where(gate, phi, mults.phi)
            lam = jnp.where(gate, lam, mults.lam)
    return Multipliers(mu=mu, eta=eta, phi=phi, lam=lam)


def apply_decision(cfg: CocktailConfig | ShapeConfig, queues: QueueState,
                   net: NetworkState, served: jax.Array, x: jax.Array,
                   y: jax.Array) -> QueueState:
    dep_r = x + jnp.sum(y, axis=2)
    trained_at = x + jnp.sum(y, axis=1)
    q = jnp.maximum(queues.q - jnp.sum(served, axis=1), 0.0) + net.arrivals
    r = jnp.maximum(queues.r - dep_r, 0.0) + served
    return QueueState(q=q, r=r, omega=queues.omega + trained_at)


# --------------------------------------------------------------------------
# One slot
# --------------------------------------------------------------------------

class SlotRecord(NamedTuple):
    cost: jax.Array
    trained: jax.Array
    q_backlog: jax.Array
    r_backlog: jax.Array
    skew: jax.Array


def stack_slot_records(recs: Sequence[SlotRecord]) -> SlotRecord:
    """Stack per-slot records time-major, mirroring what ``lax.scan`` produces
    on the jitted path (leading axis = slot index)."""
    return SlotRecord(*[jnp.stack([getattr(r, f) for r in recs])
                        for f in SlotRecord._fields])


def skew_degree(cfg: CocktailConfig | ShapeConfig | SliceParams, omega: jax.Array,
                params: Optional[SliceParams] = None) -> jax.Array:
    """max_{i,j} | Omega_ij / sum_l Omega_lj - zeta_i / sum zeta | (eq. 9 LHS)."""
    if params is None and isinstance(cfg, SliceParams):
        params = cfg
    else:
        _, params = split_config(cfg, params)
    props = params.proportions
    tot = jnp.sum(omega, axis=0, keepdims=True)
    frac = omega / jnp.maximum(tot, _TINY)
    dev = jnp.abs(frac - props[:, None])
    return jnp.max(jnp.where(tot > _TINY, dev, 0.0))


def _pi(params: SliceParams) -> jax.Array:
    """L-DS distance parameter pi = sqrt(eps) * log^2(eps) ([24],[25])."""
    return jnp.sqrt(params.eps) * jnp.log(params.eps) ** 2


def _tree_affine(a: Multipliers, b: Multipliers, shift: jax.Array) -> Multipliers:
    return jax.tree.map(lambda x, y: x + y - shift, a, b)


def _require_policy_leaves(params: SliceParams) -> None:
    missing = [f for f in ("collect_id", "train_id", "use_lsa", "learning_aid")
               if getattr(params, f) is None]
    if missing:
        raise TypeError(
            f"SWITCHED dispatch needs the SliceParams policy leaves, but "
            f"{missing} are unset; fill them with datasche.with_policy(params, "
            f"spec) or build the fleet via FleetEngine.from_jobs")


def step(cfg: CocktailConfig | ShapeConfig, spec: AlgoSpec, state: SchedulerState,
         net: Optional[NetworkState] = None,
         params: Optional[SliceParams] = None) -> tuple[SchedulerState, SlotRecord, Decision]:
    """Run one slot. Jittable when spec.exact is False (cfg/spec static,
    params traced); vmappable over a leading slice axis of (params, state).

    Two dispatch modes:
      * Python-static (any named spec): policy functions are resolved from
        the tables at trace time — one compiled program per (shape, spec).
      * Branch-free (``spec.switched``, i.e. ``SWITCHED``/``SWITCHED_NOAID``):
        the policy choice is ``jax.lax.switch`` over the tables indexed by
        the traced ``SliceParams`` policy leaves, and the learning-aid
        virtual update is gated by a select instead of a Python ``if`` — so
        K slices running different algorithms vmap into ONE compiled
        program. Under ``SWITCHED`` the virtual plain-P1/P2 path runs every
        slot (its result is masked out for slices with learning_aid=0) — the
        price of branch-freedom; ``SWITCHED_NOAID`` compiles it out for
        fleets with no L-DS slice and ignores the learning_aid leaf.
    """
    shape, params = split_config(cfg, params)
    rng, k_net = jax.random.split(state.rng)
    if net is None:
        # Per-slot noise from k_net; persistent heterogeneity from the
        # slot-invariant het_key the state carries unchanged.
        with jax.named_scope("network"):
            net = sample_network_state(k_net, shape, state.t, params,
                                       het_key=state.het_key)

    switched = spec.switched
    if switched:
        _require_policy_leaves(params)
        use_lsa: bool | jax.Array = jnp.asarray(params.use_lsa, jnp.float32)
        aid = jnp.asarray(params.learning_aid, jnp.float32) > 0
        if spec.learning_aid:
            # Same affine as _tree_affine (x + y - shift), selected per slice
            # so the aid=1 branch stays bit-exact against the static L-DS path.
            pi = _pi(params)
            eff = jax.tree.map(lambda m, e: jnp.where(aid, m + e - pi, m),
                               state.mults, state.emp_mults)
        else:
            eff = state.mults  # SWITCHED_NOAID: aid leaf ignored wholesale
    else:
        use_lsa = spec.use_lsa
        if spec.learning_aid:
            eff = _tree_affine(state.mults, state.emp_mults, _pi(params))
        else:
            eff = state.mults

    # Training policies scope their own stages (allocation, pairing).
    if switched:
        with jax.named_scope("collection"):
            alpha, theta = jax.lax.switch(
                params.collect_id,
                [(lambda p, n, m, q, fn=fn: fn(shape, p, n, m, q, False))
                 for fn in COLLECTION_POLICIES.fns],
                params, net, eff, state.queues)
        x, y, z = jax.lax.switch(
            params.train_id,
            [(lambda p, n, m, q, fn=fn: fn(shape, p, n, m, q, False, use_lsa))
             for fn in TRAINING_POLICIES.fns],
            params, net, eff, state.queues)
    else:
        collect = COLLECTION_POLICIES[spec.collection]
        train = TRAINING_POLICIES[spec.training]
        with jax.named_scope("collection"):
            alpha, theta = collect(shape, params, net, eff, state.queues,
                                   spec.exact)
        x, y, z = train(shape, params, net, eff, state.queues, spec.exact, use_lsa)

    with jax.named_scope("update"):
        served = _served(alpha, theta, net, state.queues)
        cost = framework_cost(net, served, x, y)
        queues = apply_decision(shape, state.queues, net, served, x, y)
        mults = update_multipliers(shape, state.mults, net, served, x, y,
                                   use_lsa, params.eps, params)

        emp = state.emp_mults
        if spec.learning_aid:
            # Virtual decisions from plain P1/P2 with the empirical multipliers;
            # they update Theta' only (diminishing step), never the real queues.
            v_alpha, v_theta = _collect_plain(shape, params, net, state.emp_mults,
                                              state.queues, False)
            v_x, v_y, _ = _train_linear(shape, params, net, state.emp_mults,
                                        state.queues, False, use_lsa)
            v_served = _served(v_alpha, v_theta, net, state.queues)
            sigma = params.sigma0 / jnp.sqrt(state.t.astype(jnp.float32) + 1.0)
            emp = update_multipliers(shape, state.emp_mults, net, v_served, v_x, v_y,
                                     use_lsa, sigma, params)
            if switched:
                # learning_aid gate: slices without the aid keep Theta' frozen.
                emp = jax.tree.map(lambda new, old: jnp.where(aid, new, old),
                                   emp, state.emp_mults)

        trained = jnp.sum(x) + jnp.sum(y)
        new_state = SchedulerState(
            queues=queues, mults=mults, emp_mults=emp,
            t=state.t + 1,
            total_cost=state.total_cost + cost,
            total_trained=state.total_trained + trained,
            uploaded=state.uploaded + jnp.sum(served, axis=1),
            rng=rng,
            het_key=state.het_key,
        )
        rec = SlotRecord(
            cost=cost, trained=trained,
            q_backlog=jnp.sum(queues.q), r_backlog=jnp.sum(queues.r),
            skew=skew_degree(shape, queues.omega, params),
        )
    dec = Decision(alpha=alpha, theta=theta, x=x, y=y, z=z)
    return new_state, rec, dec


# --------------------------------------------------------------------------
# Drivers
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _run_scan(shape: ShapeConfig, spec: AlgoSpec, n_slots: int,
              params: SliceParams, state: SchedulerState) -> tuple[SchedulerState, SlotRecord]:
    def body(s, _):
        s2, rec, _ = step(shape, spec, s, params=params)
        return s2, rec

    return jax.lax.scan(body, state, None, length=n_slots)


def run(cfg: CocktailConfig | ShapeConfig, spec: AlgoSpec, n_slots: int,
        state: Optional[SchedulerState] = None,
        params: Optional[SliceParams] = None) -> tuple[SchedulerState, SlotRecord]:
    """Run n_slots of the online algorithm; returns (final state, stacked
    per-slot records). Only ShapeConfig/AlgoSpec trigger recompilation —
    slices that differ only in SliceParams share one compiled program."""
    shape, params = split_config(cfg, params)
    if state is None:
        state = init_state(shape, params, seed=getattr(cfg, "seed", 0))
    if not spec.exact:
        return _run_scan(shape, spec, n_slots, params, state)
    recs = []
    for _ in range(n_slots):
        state, rec, _ = step(shape, spec, state, params=params)
        recs.append(rec)
    return state, stack_slot_records(recs)


_pin_default_policy_ids()
