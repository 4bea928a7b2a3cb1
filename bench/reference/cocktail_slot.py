"""Plain reference of one DataSche (DS) slot of the Cocktail scheduler.

Written from the paper (arXiv:2004.00799, Sec. II-III) and the scheduler's
documented semantics, with no import from the program: the same network
draws from the same keys, the skew-aware greedy collection (P1'), the solo
water-filling (20) and pair allocation (21) solvers, the greedy EC pairing
(Thm. 2), queue dynamics (1, 12) and the multiplier SGD step (16a-d).

Every function takes ``dt``, the float type the slot is computed in:
float32 is the reference, bfloat16 the control that must fail the check.
One slice, unbatched; callers ``jax.vmap`` over slices.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

TINY = 1e-9
NEG = -1e30
HET_SALT = 0x48455400


# ------------------------------------------------------------ parameters

def slice_params(cfg: dict, dt=jnp.float32) -> dict:
    """Per-slice constants from a configuration dict (the bench config's
    ``slice`` block plus ``n_cu``/``n_ec``/``f_base``)."""
    n = cfg["n_cu"]
    zeta = np.full((n,), float(cfg["zeta"]), np.float64)
    props = zeta / zeta.sum()
    f = lambda v: jnp.asarray(v, jnp.float32).astype(dt)
    return {
        "zeta": f(zeta), "props": f(props),
        "d_lo": f(np.maximum(props - cfg["delta"], 0.0)),
        "d_hi": f(np.minimum(props + cfg["delta"], 1.0)),
        "eps": f(cfg["eps"]), "rho": f(cfg["rho"]), "q0": f(cfg["q0"]),
        "d_base": f(cfg["d_base"]), "cap_d_base": f(cfg["cap_d_base"]),
        "f_base": f(np.asarray(cfg["f_base"], np.float64)),
        "c_base": f(cfg["c_base"]), "e_base": f(cfg["e_base"]),
        "p_base": f(cfg["p_base"]),
    }


def init_state(cfg: dict, seed: int) -> dict:
    n, m = cfg["n_cu"], cfg["n_ec"]
    q0, eps = np.float32(cfg["q0"]), np.float32(cfg["eps"])
    key = jax.random.PRNGKey(seed)
    zeros = np.zeros((n, m), np.float32)
    return {
        "q": np.full((n,), q0, np.float32), "r": zeros, "omega": zeros,
        "mu": np.full((n,), q0 * eps, np.float32), "eta": zeros,
        "phi": zeros, "lam": zeros,
        "t": np.int32(0), "total_cost": np.float32(0),
        "total_trained": np.float32(0),
        "uploaded": np.zeros((n,), np.float32),
        "rng": np.asarray(key),
        "het_key": np.asarray(jax.random.fold_in(key, HET_SALT)),
    }


# --------------------------------------------------------------- network

def _keys(key, n):
    return jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(n))


def _per_entity(draw, key, n, m=None):
    """Draw one value per entity (or entity pair) from the key folded with
    its indices, so a value does not depend on the array's shape."""
    if m is None:
        return jax.vmap(draw)(_keys(key, n))
    return jax.vmap(lambda kr: jax.vmap(draw)(_keys(kr, m)))(_keys(key, n))


def _uniform(key, n, m=None, lo=0.0, hi=1.0):
    return _per_entity(
        lambda k: jax.random.uniform(k, (), minval=lo, maxval=hi), key, n, m)


def _beta(key, a, b, n, m=None):
    return _per_entity(lambda k: jax.random.beta(k, a, b), key, n, m)


def network(p: dict, het_key, slot_key, t, n, m, dt=jnp.float32) -> dict:
    """Network state and arrivals of slot ``t`` (paper Sec. IV: capacity =
    baseline x persistent multiplier x (1 - traffic), uniform unit costs)."""
    two_pi = 2.0 * jnp.pi
    link_het = 0.5 + _uniform(jax.random.fold_in(het_key, 0), n, m)
    ec_het = 0.5 + _uniform(jax.random.fold_in(het_key, 1), m, m)
    phase_d = _uniform(jax.random.fold_in(het_key, 2), n, m, 0.0, two_pi)
    phase_D = _uniform(jax.random.fold_in(het_key, 3), m, m, 0.0, two_pi)
    kd, kD, kf, kc, ke, kp, ka, _ = jax.random.split(slot_key, 8)

    def traffic(key, phase, rows, cols):
        noise_key = jax.random.split(key)[1]
        base = 0.35 + 0.3 * jnp.sin(2 * jnp.pi * t / 288.0 + phase)
        return jnp.clip(base + _beta(noise_key, 2.0, 4.0, rows, cols) * 0.4,
                        0.0, 0.95)

    eye = jnp.eye(m)
    d = p["d_base"] * link_het * (1.0 - traffic(kd, phase_d, n, m))
    cap = p["cap_d_base"] * ec_het * (1.0 - traffic(kD, phase_D, m, m))
    cap = 0.5 * (cap + cap.T) * (1.0 - eye)
    f = p["f_base"] * (1.0 - jnp.clip(_beta(kf, 2.0, 5.0, m), 0.0, 0.9))
    c = p["c_base"] * (1.0 + _uniform(kc, n, m))
    e = p["e_base"] * (1.0 + _uniform(ke, m, m))
    e = 0.5 * (e + e.T) * (1.0 - eye)
    cost_p = p["p_base"] * (1.0 + _uniform(kp, m))
    arrivals = p["zeta"] * (0.5 + _uniform(ka, n))
    out = dict(d=d, cap=cap, f=f, c=c, e=e, p=cost_p, arrivals=arrivals)
    return {k: v.astype(jnp.float32).astype(dt) for k, v in out.items()}


# ------------------------------------------------------------ collection

def _crowding(count, dt):
    """Marginal penalty (n+1)log(n+1) - n log n of an EC's (n+1)-th CU."""
    n = count.astype(dt)
    return (n + 1) * jnp.log(n + 1) - n * jnp.where(
        n > 0, jnp.log(jnp.maximum(n, 1)), 0)


def collect(net, mu, eta, dt):
    """P1': greedily connect the (CU, EC) pair of largest marginal gain
    log w_ij - penalty(n_j) until no gain is positive; theta = 1/n_j."""
    n, m = net["d"].shape
    w = net["d"] * (mu[:, None] - eta - net["c"])
    logw = jnp.where(w > 0, jnp.log(jnp.maximum(w, jnp.asarray(TINY, dt))),
                     jnp.asarray(NEG, dt))

    def pick(_, s):
        taken, count, alpha, stop = s
        gain = jnp.where(taken[:, None], jnp.asarray(NEG, dt),
                         logw - _crowding(count, dt)[None, :])
        flat = jnp.argmax(gain)
        i, j = flat // m, flat % m
        ok = (gain[i, j] > 0) & ~stop
        taken = taken.at[i].set(taken[i] | ok)
        count = count.at[j].add(ok.astype(jnp.int32))
        alpha = alpha.at[i, j].set(jnp.where(ok, 1, alpha[i, j]))
        return taken, count, alpha, stop | ~ok

    s0 = (jnp.zeros((n,), bool), jnp.zeros((m,), jnp.int32),
          jnp.zeros((n, m), dt), jnp.asarray(False))
    _, count, alpha, _ = jax.lax.fori_loop(0, n, pick, s0)
    return alpha, alpha / jnp.maximum(count.astype(dt), 1)[None, :]


# -------------------------------------------------------------- training

def waterfill(beta, r, budget):
    """Problem (20): max sum log(beta_i x_i) over the active CUs, sum x <=
    budget, x_i <= r_i: x_i = min(r_i, level), with the level that spends
    min(budget, sum r). On the caps sorted ascending, the level is that of
    the first k whose candidate (fill - k smallest caps) / (n_act - k) does
    not pass the k-th cap; when none stops below a cap, every active CU
    trains all it holds. No tolerance enters: rounding moves the level by
    rounding, never to 0. Returns (x, objective)."""
    dt = r.dtype
    n = r.shape[0]
    act = (beta > 0) & (r > TINY)
    n_act = jnp.sum(act)
    fill = jnp.minimum(jnp.maximum(budget, 0), jnp.sum(jnp.where(act, r, 0)))
    caps = jnp.sort(jnp.where(act, r, jnp.inf))
    finite = jnp.where(jnp.isfinite(caps), caps, 0)
    below = jnp.concatenate([jnp.zeros((1,), dt), jnp.cumsum(finite)])[:-1]
    k = jnp.arange(n)
    level_k = (fill - below) / jnp.maximum((n_act - k).astype(dt), 1)
    stops = (k < n_act) & (level_k <= caps)
    level = jnp.where(jnp.any(stops), level_k[jnp.argmax(stops)], jnp.inf)
    x = jnp.where(act, jnp.minimum(r, jnp.maximum(level, 0)), 0)
    val = jnp.sum(jnp.where(x > TINY, jnp.log(jnp.maximum(beta * x, TINY)), 0))
    return x, val


def pair_alloc(bj, gkj, bk, gjk, rj, rk, fj, fk, link, iters, sweeps=4):
    """Problem (21) for EC pair (j, k): dual subgradient on the link and two
    compute budgets, closed-form coordinate ascent per CU inside, then
    downscaling to exact feasibility. Returns (x_j, x_k, y_jk, y_kj, value)."""
    dt = rj.dtype
    cap = jnp.maximum(jnp.stack([link, fj, fk]), 0)

    def primal(duals):
        a, pj, pk = duals[0], duals[1], duals[2]
        price = (pj + TINY, pj + a + TINY, pk + TINY, pk + a + TINY)

        def best(w, pr, other, lim):
            v = jnp.where(w > 0, 1 / pr - other / jnp.maximum(w, TINY), 0)
            return jnp.clip(v, 0, jnp.maximum(lim, 0))

        def sweep(_, v):
            xj, ykj, xk, yjk = v
            xj = best(bj, price[0], gkj * ykj, rj - yjk)
            xk = best(bk, price[2], gjk * yjk, rk - ykj)
            ykj = best(gkj, price[1], bj * xj, rk - xk)
            yjk = best(gjk, price[3], bk * xk, rj - xj)
            return xj, ykj, xk, yjk

        z = jnp.zeros_like(rj)
        return jax.lax.fori_loop(0, sweeps, sweep, (z, z, z, z))

    def dual(it, duals):
        xj, ykj, xk, yjk = primal(duals)
        used = jnp.stack([jnp.sum(yjk + ykj), jnp.sum(xj + ykj),
                          jnp.sum(xk + yjk)])
        lr = (0.5 / jnp.sqrt(it + 1.0)).astype(dt)
        return jnp.maximum(duals + lr * (used - cap) / (cap + 1), 0)

    duals = jax.lax.fori_loop(0, iters, dual, jnp.full((3,), 0.01, dt))
    xj, ykj, xk, yjk = primal(duals)

    def shrink(limit, used):
        return jnp.minimum(1, limit / jnp.maximum(used, TINY))

    s = shrink(rj, xj + yjk)
    xj, yjk = xj * s, yjk * s
    s = shrink(rk, xk + ykj)
    xk, ykj = xk * s, ykj * s
    s = shrink(cap[1], jnp.sum(xj + ykj))
    xj, ykj = xj * s, ykj * s
    s = shrink(cap[2], jnp.sum(xk + yjk))
    xk, yjk = xk * s, yjk * s
    s = shrink(cap[0], jnp.sum(yjk + ykj))
    yjk, ykj = yjk * s, ykj * s
    uj, uk = bj * xj + gkj * ykj, bk * xk + gjk * yjk
    logu = lambda u: jnp.where(u > TINY, jnp.log(jnp.maximum(u, TINY)), 0)
    return xj, xk, yjk, ykj, jnp.sum(logu(uj)) + jnp.sum(logu(uk))


def pair_ecs(solo, pair, dt):
    """Thm. 2 greedy: take the best free entry (diagonal = train alone,
    off-diagonal = pair) while its value is positive."""
    m = solo.shape[0]
    eye = jnp.eye(m, dtype=dt)
    vals = pair * (1 - eye) + jnp.diag(solo)

    def pick(_, s):
        free, match, stop = s
        g = jnp.where(free[:, None] & free[None, :], vals, jnp.asarray(NEG, dt))
        flat = jnp.argmax(g)
        j, k = flat // m, flat % m
        ok = (g[j, k] > 0) & ~stop
        free = free.at[j].set(free[j] & ~ok).at[k].set(free[k] & ~ok)
        match = match.at[j, k].set(jnp.where(ok, 1, match[j, k]))
        match = match.at[k, j].set(jnp.where(ok, 1, match[k, j]))
        return free, match, stop | ~ok

    s0 = (jnp.ones((m,), bool), jnp.zeros((m, m), dt), jnp.asarray(False))
    return jax.lax.fori_loop(0, m, pick, s0)[1]


def train(p, net, r, eta, phi, lam, pair_iters, dt):
    """P2': per-EC solo water-filling, per-pair allocation, greedy pairing,
    assembled into x (N, M) and y (N, M, M)."""
    n, m = r.shape
    common = jnp.sum(lam * p["d_hi"][:, None] - phi * p["d_lo"][:, None], 0)
    beta = -net["p"][None, :] + eta - lam + phi + common[None, :]
    gamma = (beta[:, None, :] + eta[:, :, None] - eta[:, None, :]
             - net["e"][None, :, :])
    budget = net["f"] / p["rho"]
    x_solo, v_solo = jax.vmap(waterfill, in_axes=(1, 1, 0), out_axes=(1, 0))(
        beta, r, budget)
    pj, pk = (jnp.asarray(a, jnp.int32) for a in np.triu_indices(m, k=1))
    pa = jax.vmap(lambda j, k: pair_alloc(
        beta[:, j], gamma[:, k, j], beta[:, k], gamma[:, j, k], r[:, j],
        r[:, k], budget[j], budget[k], net["cap"][j, k], pair_iters))(pj, pk)
    xj, xk, yjk, ykj, v_pair = pa
    pair = jnp.zeros((m, m), dt).at[pj, pk].set(v_pair)
    match = pair_ecs(v_solo, pair + pair.T, dt)
    on = match[pj, pk]  # (P,)
    x = x_solo * jnp.diagonal(match)[None, :]
    x = x.at[:, pj].add((xj * on[:, None]).T).at[:, pk].add((xk * on[:, None]).T)
    y = jnp.zeros((n, m, m), dt)
    y = y.at[:, pj, pk].add((yjk * on[:, None]).T)
    y = y.at[:, pk, pj].add((ykj * on[:, None]).T)
    return x, y, match * (1 - jnp.eye(m, dtype=dt))


# ------------------------------------------------------------------ slot

def slot(p: dict, s: dict, n: int, m: int, pair_iters: int, dt=jnp.float32):
    """One DS slot from state ``s``: returns (next state, record, decision).
    Arrays of ``s`` are cast to ``dt`` on entry; keys and the counter are
    exact."""
    f = lambda v: jnp.asarray(v).astype(dt)
    q, r, omega = f(s["q"]), f(s["r"]), f(s["omega"])
    mu, eta, phi, lam = f(s["mu"]), f(s["eta"]), f(s["phi"]), f(s["lam"])
    keys = jax.random.split(s["rng"])
    net = network(p, s["het_key"], keys[1], s["t"], n, m, dt)

    alpha, theta = collect(net, mu, eta, dt)
    x, y, z = train(p, net, r, eta, phi, lam, pair_iters, dt)

    want = alpha * theta * net["d"]
    served = want * jnp.minimum(
        1, q / jnp.maximum(jnp.sum(want, 1), TINY))[:, None]
    out_r = x + jnp.sum(y, 2)  # leaves queue R[i, j]
    at = x + jnp.sum(y, 1)  # trained at EC k
    per_ec = jnp.sum(at, 0)
    cost = (jnp.sum(net["c"] * served) + jnp.sum(net["e"][None] * y)
            + jnp.sum(net["p"][None, :] * at))
    eps = p["eps"]
    nxt = {
        "q": jnp.maximum(q - jnp.sum(served, 1), 0) + net["arrivals"],
        "r": jnp.maximum(r - out_r, 0) + served,
        "omega": omega + at,
        "mu": jnp.maximum(mu + eps * (net["arrivals"] - jnp.sum(served, 1)), 0),
        "eta": jnp.maximum(eta + eps * (served - out_r), 0),
        "phi": jnp.maximum(phi + eps * (p["d_lo"][:, None] * per_ec[None] - at), 0),
        "lam": jnp.maximum(lam + eps * (at - p["d_hi"][:, None] * per_ec[None]), 0),
        "t": s["t"] + 1,
        "total_cost": f(s["total_cost"]) + cost,
        "total_trained": f(s["total_trained"]) + jnp.sum(x) + jnp.sum(y),
        "uploaded": f(s["uploaded"]) + jnp.sum(served, 1),
        "rng": keys[0],
        "het_key": s["het_key"],
    }
    tot = jnp.sum(nxt["omega"], 0, keepdims=True)
    dev = jnp.abs(nxt["omega"] / jnp.maximum(tot, TINY) - p["props"][:, None])
    rec = {
        "cost": cost, "trained": jnp.sum(x) + jnp.sum(y),
        "q_backlog": jnp.sum(nxt["q"]), "r_backlog": jnp.sum(nxt["r"]),
        "skew": jnp.max(jnp.where(tot > TINY, dev, 0)),
    }
    dec = {"alpha": alpha, "theta": theta, "x": x, "y": y, "z": z}
    return nxt, rec, dec
