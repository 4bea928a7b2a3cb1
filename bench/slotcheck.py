"""Comparison of scheduler slots, program against the plain reference."""
from __future__ import annotations

import numpy as np

STATE = ("q", "r", "omega", "mu", "eta", "phi", "lam", "uploaded",
         "total_cost", "total_trained")
RECORD = ("cost", "trained", "q_backlog", "r_backlog")
# the slot's decisions, seen through the running totals they move:
# uploads per CU (collection, alpha and theta) and samples of each CU
# trained at each EC (training and pairing, x, y and z)
EFFECTS = ("uploaded", "omega")


def flat_state(s) -> dict:
    """Program SchedulerState -> the reference's flat dict (numpy)."""
    import jax
    s = jax.device_get(s)
    return {"q": s.queues.q, "r": s.queues.r, "omega": s.queues.omega,
            "mu": s.mults.mu, "eta": s.mults.eta, "phi": s.mults.phi,
            "lam": s.mults.lam, "t": s.t, "total_cost": s.total_cost,
            "total_trained": s.total_trained, "uploaded": s.uploaded,
            "rng": s.rng, "het_key": s.het_key}


def flat_record(r, index=0) -> dict:
    """Program SlotRecord -> flat dict; ``index`` picks the slot of a
    time-major record, None takes an unbatched one as is."""
    import jax
    r = jax.device_get(r)
    pick = (lambda a: a) if index is None else (lambda a: a[index])
    return {k: pick(np.asarray(getattr(r, k))) for k in (*RECORD, "skew")}


def _rel_l1(p, r) -> float:
    """Worst over the slices (leading axis) of |p - r|_1 / |r|_1."""
    p, r = np.asarray(p, np.float64), np.asarray(r, np.float64)
    axes = tuple(range(1, p.ndim))
    num = np.abs(p - r).sum(axis=axes)
    den = np.maximum(np.abs(r).sum(axis=axes), 1e-6)
    return float(np.max(num / den))


def compare(prog_in, prog_out, prog_rec, ref_out, ref_rec) -> dict:
    """Gaps of one checked slot, worst over the K slices (leading axis), the
    program's against the reference's from the same input state:
    ``state``     relative L1 gap of each next-state field, worst field;
    ``decision``  relative L1 gap of each decision effect (growth of a
                  running total over the slot), worst effect;
    ``record``    gap of each slot record field, relative; the skew absolute;
    ``exact``     slot counter and keys that differ (must be none)."""
    state = max(_rel_l1(prog_out[k], ref_out[k]) for k in STATE)
    grow = lambda out, k: (np.asarray(out[k], np.float64)
                           - np.asarray(prog_in[k], np.float64))
    decision = max(_rel_l1(grow(prog_out, k), grow(ref_out, k)) for k in EFFECTS)
    record = max(_rel_l1(prog_rec[k], ref_rec[k]) for k in RECORD)
    record = max(record, float(np.max(np.abs(
        np.asarray(prog_rec["skew"], np.float64)
        - np.asarray(ref_rec["skew"], np.float64)))))
    exact = 0
    for k in ("t", "rng", "het_key"):
        exact += int(np.sum(np.asarray(prog_out[k]) != np.asarray(ref_out[k])))
    exact += int(np.sum(np.asarray(prog_in["het_key"]) != np.asarray(prog_out["het_key"])))
    return {"state": state, "decision": decision, "record": record, "exact": exact}
