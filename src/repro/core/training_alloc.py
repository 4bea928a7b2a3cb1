"""Per-slot training-allocation solvers for subproblem P2' (and linear P2).

Three solvers, all pure JAX / jittable:

* ``solo_waterfill``  — problem (20): max sum_i log(beta_i x_i) with one
  compute budget and per-queue caps. Closed form (capped water-filling via
  sort + cumsum).
* ``pair_allocate``   — problem (21): the two-EC convex program. Solved by
  dual subgradient on the three resource constraints (link D_jk, compute F_j,
  F_k) with an inner closed-form coordinate-ascent primal per CU (the caps
  x_ij + y_ijk <= R_ij couple only variables of the *same* CU, so the inner
  problem is separable over i). A final downscaling pass guarantees exact
  feasibility. The paper's testbed used AMPL+IPOPT here; this is the
  TPU-native, fixed-iteration-count replacement (oracle-checked in tests).
* ``linear_*``        — the non-log (plain P2) variants used by L-DS step 3
  and the NO-SLT ablation: fractional-knapsack greedy fills.

Conventions: compute budgets F are in samples/slot (f/rho); a term only
contributes log(u) to an edge weight when u > 0 — allocating nothing to a
source is always feasible and contributes 0 (matches the paper's implicit
restriction to positively-weighted sources; log of a non-positive allocation
is undefined).

Batch-first: every solver here is shape-polymorphic pure JAX over (N,)
vectors — budgets, caps, and weights may all be traced ``SliceParams``-derived
values, and the whole module vmaps transparently over a leading fleet slice
axis (no Python branching on data anywhere).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

_TINY = 1e-9


def solo_waterfill(beta: jax.Array, r: jax.Array, budget: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Problem (20). Returns (x, objective value).

    max sum_{i active} log(beta_i x_i)  s.t. sum x <= budget, 0 <= x_i <= r_i,
    active = {beta_i > 0, r_i > 0}. Optimal x_i = min(r_i, w) with the water
    level w chosen to exhaust min(budget, sum r_active).

    On the caps sorted ascending, w is the candidate level (fill - k smallest
    caps) / (n_act - k) of the first k that does not pass the k-th cap. When
    none stops below a cap (the budget covers all active data, up to
    rounding), w = inf and every active CU trains all it holds. No tolerance
    enters: rounding moves the level by rounding, never to 0.
    """
    n = beta.shape[0]
    active = (beta > 0) & (r > _TINY)
    n_act = jnp.sum(active)
    r_act = jnp.where(active, r, 0.0)
    fill = jnp.minimum(jnp.maximum(budget, 0.0), jnp.sum(r_act))

    s = jnp.sort(jnp.where(active, r, jnp.inf))  # ascending; inactive last
    s_fin = jnp.where(jnp.isfinite(s), s, 0.0)
    cs = jnp.concatenate([jnp.zeros((1,), s.dtype), jnp.cumsum(s_fin)])[:-1]  # cs[k] = k smallest
    k = jnp.arange(n)
    denom = jnp.maximum((n_act - k).astype(r.dtype), 1.0)
    w_k = (fill - cs) / denom
    stops = (k < n_act) & (w_k <= s)
    level = jnp.where(jnp.any(stops), w_k[jnp.argmax(stops)], jnp.inf)
    x = jnp.where(active, jnp.minimum(r, jnp.maximum(level, 0.0)), 0.0)
    pos = x > _TINY
    value = jnp.sum(jnp.where(pos, jnp.log(jnp.maximum(beta * x, _TINY)), 0.0))
    return x, value


class PairAlloc(NamedTuple):
    x_j: jax.Array  # (N,) trained at j from R[:, j]
    x_k: jax.Array  # (N,) trained at k from R[:, k]
    y_jk: jax.Array  # (N,) moved j -> k, trained at k
    y_kj: jax.Array  # (N,) moved k -> j, trained at j
    value: jax.Array  # scalar objective


def _coord_ascent_pair(
    duals: jax.Array,
    b_j: jax.Array, g_kj: jax.Array, b_k: jax.Array, g_jk: jax.Array,
    r_j: jax.Array, r_k: jax.Array,
    sweeps: int,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Closed-form cyclic coordinate ascent for the per-CU subproblem given
    resource prices (a, m_j, m_k): maximize
        log(b_j x_j + g_kj y_kj) + log(b_k x_k + g_jk y_jk)
        - m_j (x_j + y_kj) - m_k (x_k + y_jk) - a (y_jk + y_kj)
    s.t. x_j + y_jk <= r_j,  x_k + y_kj <= r_k,  vars >= 0.

    Each coordinate update of max log(b v + c) - p v with v <= cap is
    v* = clip(1/p - c/b, 0, cap).
    """
    a, m_j, m_k = duals[0], duals[1], duals[2]
    p_xj, p_ykj = m_j + _TINY, m_j + a + _TINY
    p_xk, p_yjk = m_k + _TINY, m_k + a + _TINY

    def upd(w, p, c, cap):
        v = jnp.where(w > 0, 1.0 / p - c / jnp.maximum(w, _TINY), 0.0)
        return jnp.clip(v, 0.0, jnp.maximum(cap, 0.0))

    def sweep(_, vs):
        x_j, y_kj, x_k, y_jk = vs
        x_j = upd(b_j, p_xj, g_kj * y_kj, r_j - y_jk)
        x_k = upd(b_k, p_xk, g_jk * y_jk, r_k - y_kj)
        y_kj = upd(g_kj, p_ykj, b_j * x_j, r_k - x_k)
        y_jk = upd(g_jk, p_yjk, b_k * x_k, r_j - x_j)
        return x_j, y_kj, x_k, y_jk

    zeros = jnp.zeros_like(r_j)
    return jax.lax.fori_loop(0, sweeps, sweep, (zeros, zeros, zeros, zeros))


def pair_allocate(
    b_j: jax.Array, g_kj: jax.Array, b_k: jax.Array, g_jk: jax.Array,
    r_j: jax.Array, r_k: jax.Array,
    budget_j: jax.Array, budget_k: jax.Array, link: jax.Array,
    iters: int = 60, sweeps: int = 4,
) -> PairAlloc:
    """Problem (21) for a pair (j, k) of ECs. All vector args are (N,)."""
    cap = jnp.stack([link, budget_j, budget_k])
    cap = jnp.maximum(cap, 0.0)

    def dual_step(t, duals):
        x_j, y_kj, x_k, y_jk = _coord_ascent_pair(duals, b_j, g_kj, b_k, g_jk, r_j, r_k, sweeps)
        use = jnp.stack([
            jnp.sum(y_jk + y_kj),
            jnp.sum(x_j + y_kj),
            jnp.sum(x_k + y_jk),
        ])
        step = 0.5 / jnp.sqrt(t + 1.0)
        # Rounded in this order, as the plain reference rounds it
        # (bench/reference/cocktail_slot.pair_alloc): a price driven near 0
        # makes the primal 1/price amplify one rounding step into another
        # allocation, so the order is part of the result.
        return jnp.maximum(duals + step * (use - cap) / (cap + 1.0), 0.0)

    duals0 = jnp.ones((3,), jnp.float32) * 0.01
    duals = jax.lax.fori_loop(0, iters, dual_step, duals0)
    x_j, y_kj, x_k, y_jk = _coord_ascent_pair(duals, b_j, g_kj, b_k, g_jk, r_j, r_k, sweeps)

    # Exact feasibility: scale queue caps per-CU, then global resources.
    s_j = jnp.minimum(1.0, r_j / jnp.maximum(x_j + y_jk, _TINY))
    x_j, y_jk = x_j * s_j, y_jk * s_j
    s_k = jnp.minimum(1.0, r_k / jnp.maximum(x_k + y_kj, _TINY))
    x_k, y_kj = x_k * s_k, y_kj * s_k
    s_fj = jnp.minimum(1.0, cap[1] / jnp.maximum(jnp.sum(x_j + y_kj), _TINY))
    x_j, y_kj = x_j * s_fj, y_kj * s_fj
    s_fk = jnp.minimum(1.0, cap[2] / jnp.maximum(jnp.sum(x_k + y_jk), _TINY))
    x_k, y_jk = x_k * s_fk, y_jk * s_fk
    s_l = jnp.minimum(1.0, cap[0] / jnp.maximum(jnp.sum(y_jk + y_kj), _TINY))
    y_jk, y_kj = y_jk * s_l, y_kj * s_l

    u_j = b_j * x_j + g_kj * y_kj
    u_k = b_k * x_k + g_jk * y_jk
    value = jnp.sum(jnp.where(u_j > _TINY, jnp.log(jnp.maximum(u_j, _TINY)), 0.0))
    value += jnp.sum(jnp.where(u_k > _TINY, jnp.log(jnp.maximum(u_k, _TINY)), 0.0))
    return PairAlloc(x_j=x_j, x_k=x_k, y_jk=y_jk, y_kj=y_kj, value=value)


def linear_solo(beta: jax.Array, r: jax.Array, budget: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Plain-P2 solo: max sum beta_i x_i (linear). Fractional knapsack —
    fill caps in descending beta order. Exact. Returns (x, value)."""
    active = (beta > 0) & (r > _TINY)
    order = jnp.argsort(jnp.where(active, -beta, jnp.inf))
    r_ord = jnp.where(active, r, 0.0)[order]
    cs = jnp.concatenate([jnp.zeros((1,), r.dtype), jnp.cumsum(r_ord)])[:-1]
    alloc_ord = jnp.clip(jnp.maximum(budget, 0.0) - cs, 0.0, r_ord)
    x = jnp.zeros_like(r).at[order].set(alloc_ord)
    x = jnp.where(active, x, 0.0)
    return x, jnp.sum(beta * x)


def linear_pair(
    b_j: jax.Array, g_kj: jax.Array, b_k: jax.Array, g_jk: jax.Array,
    r_j: jax.Array, r_k: jax.Array,
    budget_j: jax.Array, budget_k: jax.Array, link: jax.Array,
) -> PairAlloc:
    """Plain-P2 pair: greedy fractional fill by descending linear weight over
    the 4N (variable, CU) slots; respects caps + the three resources. A
    0.5-class greedy for the multi-resource LP (documented approximation)."""
    n = b_j.shape[0]
    # var layout: [x_j | y_kj | x_k | y_jk] each (N,)
    weights = jnp.concatenate([b_j, g_kj, b_k, g_jk])
    order = jnp.argsort(-weights)

    def body(s, carry):
        rem_rj, rem_rk, rem_fj, rem_fk, rem_d, out = carry
        v = order[s]
        kind, i = v // n, v % n
        w = weights[v]
        # resource draw per kind: (queue, compute, link)
        q_rem = jnp.where((kind == 0) | (kind == 3), rem_rj[i], rem_rk[i])
        f_rem = jnp.where((kind == 0) | (kind == 1), rem_fj, rem_fk)
        l_rem = jnp.where((kind == 1) | (kind == 3), rem_d, jnp.inf)
        amt = jnp.where(w > 0, jnp.minimum(jnp.minimum(q_rem, f_rem), l_rem), 0.0)
        amt = jnp.maximum(amt, 0.0)
        dq_j = jnp.where((kind == 0) | (kind == 3), amt, 0.0)
        dq_k = jnp.where((kind == 1) | (kind == 2), amt, 0.0)
        rem_rj = rem_rj.at[i].add(-dq_j)
        rem_rk = rem_rk.at[i].add(-dq_k)
        rem_fj = rem_fj - jnp.where((kind == 0) | (kind == 1), amt, 0.0)
        rem_fk = rem_fk - jnp.where((kind == 2) | (kind == 3), amt, 0.0)
        rem_d = rem_d - jnp.where((kind == 1) | (kind == 3), amt, 0.0)
        out = out.at[v].set(amt)
        return rem_rj, rem_rk, rem_fj, rem_fk, rem_d, out

    carry = (r_j, r_k, jnp.maximum(budget_j, 0.0), jnp.maximum(budget_k, 0.0),
             jnp.maximum(link, 0.0), jnp.zeros((4 * n,), r_j.dtype))
    *_, out = jax.lax.fori_loop(0, 4 * n, body, carry)
    x_j, y_kj, x_k, y_jk = out[:n], out[n:2 * n], out[2 * n:3 * n], out[3 * n:]
    value = jnp.sum(b_j * x_j + g_kj * y_kj + b_k * x_k + g_jk * y_jk)
    return PairAlloc(x_j=x_j, x_k=x_k, y_jk=y_jk, y_kj=y_kj, value=value)


def full_allocate(
    beta: jax.Array,  # (N, M) weight of x[i, j]
    gamma: jax.Array,  # (N, M, M) weight of y[i, j, k]
    r: jax.Array,  # (N, M) queue caps
    budgets: jax.Array,  # (M,) compute budgets (samples)
    links: jax.Array,  # (M, M) link capacities
    iters: int = 40, sweeps: int = 2,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """ECFull baseline: joint allocation with all EC pairs connected
    (constraint (5) removed). gamma[i, j, k] weights y[i, j, k] (from queue
    R[i,j], trained at k). Dual subgradient on compute (M) + link (M, M)
    constraints, inner coordinate ascent, final downscale. Returns
    (x (N,M), y (N,M,M), value)."""
    n, m = beta.shape
    eye = jnp.eye(m, dtype=bool)

    def primal(duals):
        m_dual, a_dual = duals  # (M,), (M, M) symmetric
        p_x = m_dual[None, :] + _TINY  # price of x[i, j]
        # price of y[i, j, k]: compute at k + link (j,k)
        p_y = m_dual[None, None, :] + a_dual[None, :, :] + _TINY

        def sweep(_, vs):
            x, y = vs
            # u[i, k] = beta*x + sum_j gamma[i,j,k] y[i,j,k]
            u_from_y = jnp.einsum("ijk,ijk->ik", gamma, y)
            # update x: max log(beta x + c) - p x, cap r - sum_k y[i,j,k]
            cap_x = jnp.maximum(r - jnp.sum(y, axis=2), 0.0)
            x = jnp.where(
                beta > 0,
                jnp.clip(1.0 / p_x - u_from_y / jnp.maximum(beta, _TINY), 0.0, cap_x),
                0.0,
            )
            # update y jointly per (j, k): treat each y[:, j, k] given others
            def upd_pair(jk, y):
                j, k = jk // m, jk % m
                u_k = beta[:, k] * x[:, k] + jnp.einsum("ij,ij->i", gamma[:, :, k], y[:, :, k])
                c = u_k - gamma[:, j, k] * y[:, j, k]
                cap = jnp.maximum(r[:, j] - x[:, j] - (jnp.sum(y[:, j, :], axis=1) - y[:, j, k]), 0.0)
                g = gamma[:, j, k]
                v = jnp.where((g > 0) & (j != k), jnp.clip(1.0 / p_y[:, j, k] - c / jnp.maximum(g, _TINY), 0.0, cap), 0.0)
                return y.at[:, j, k].set(v)

            y = jax.lax.fori_loop(0, m * m, upd_pair, y)
            return x, y

        return jax.lax.fori_loop(0, sweeps, sweep,
                                 (jnp.zeros_like(beta), jnp.zeros_like(gamma)))

    def dual_step(t, duals):
        m_dual, a_dual = duals
        x, y = primal(duals)
        trained_at = jnp.sum(x, axis=0) + jnp.einsum("ijk->k", y)
        g_m = (trained_at - budgets) / (budgets + 1.0)
        flow = jnp.einsum("ijk->jk", y)
        flow = flow + flow.T
        g_a = (flow - links) / (links + 1.0)
        g_a = jnp.where(eye, 0.0, g_a)
        step = 0.5 / jnp.sqrt(t + 1.0)
        return (jnp.maximum(m_dual + step * g_m, 0.0),
                jnp.maximum(a_dual + step * g_a, 0.0))

    duals = (jnp.full((m,), 0.01, jnp.float32), jnp.full((m, m), 0.01, jnp.float32))
    duals = jax.lax.fori_loop(0, iters, dual_step, duals)
    x, y = primal(duals)

    # Feasibility: queue caps, then compute, then links (downscaling only).
    dep = x + jnp.sum(y, axis=2)
    s_q = jnp.minimum(1.0, r / jnp.maximum(dep, _TINY))
    x = x * s_q
    y = y * s_q[:, :, None]
    trained_at = jnp.sum(x, axis=0) + jnp.einsum("ijk->k", y)
    s_f = jnp.minimum(1.0, budgets / jnp.maximum(trained_at, _TINY))
    x = x * s_f[None, :]
    y = y * s_f[None, None, :]
    flow = jnp.einsum("ijk->jk", y)
    sym_flow = flow + flow.T
    s_l = jnp.minimum(1.0, links / jnp.maximum(sym_flow, _TINY))
    s_l = jnp.where(eye, 1.0, s_l)
    y = y * s_l[None, :, :]

    u = beta * x + jnp.einsum("ijk,ijk->ik", gamma, y)
    value = jnp.sum(jnp.where(u > _TINY, jnp.log(jnp.maximum(u, _TINY)), 0.0))
    return x, y, value
