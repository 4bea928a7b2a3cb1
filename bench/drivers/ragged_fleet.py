"""Ragged fleet cells: K slices of different true sizes, decided together.

Set-up builds one ``SliceJob`` per configured slice, at that slice's own
(N, M) and EC capacities, and ``FleetEngine.from_jobs`` pads them to the
elementwise-max shape with entity masks. The window, the end-to-end metrics
and the program text are the fleet driver's (``drivers/fleet.py``): one
``state, rec = eng.run(1, state)`` per slot, waited for.

Correctness: the first slot and a reservoir of window slots, as in the fleet
driver. Each slice's true-shape region of the program's input state is cut
out here (not by the program's own ``trim_state``), and the slice's next
state, decision effects and record are compared with the plain reference
run at the slice's own (N, M) from that input
(``reference/cocktail_sweep.py``). Every padded entry of the input state,
the next state and the decision effects must be exactly zero
(``pad_nonzero``).
"""
from __future__ import annotations

import pathlib

import numpy as np

import harness
from slotcheck import EFFECTS, compare

_fleet = harness.load_module(pathlib.Path(__file__).resolve().parent / "fleet.py")

# entity axes of each per-entity state field: (CU,) or (CU, EC)
CU_FIELDS = ("q", "mu", "uploaded")
LINK_FIELDS = ("r", "omega", "eta", "phi", "lam")


def region(s: dict, k: int, n: int, m: int) -> dict:
    """Slice ``k``'s true-shape (``n`` CUs, ``m`` ECs) part of a stacked
    flat state; fields with no entity axis are slice ``k``'s as they are."""
    out = {f: np.asarray(v[k]) for f, v in s.items()}
    for f in CU_FIELDS:
        out[f] = out[f][:n]
    for f in LINK_FIELDS:
        out[f] = out[f][:n, :m]
    return out


def pad_nonzero(s: dict, shapes, fields=CU_FIELDS + LINK_FIELDS) -> int:
    """Nonzero entries of the per-entity ``fields`` of a stacked flat state
    outside each slice's true-shape region."""
    count = 0
    for k, (n, m) in enumerate(shapes):
        for f in fields:
            a = np.asarray(s[f][k])
            true = a[:n] if a.ndim == 1 else a[:n, :m]
            count += int(np.count_nonzero(a)) - int(np.count_nonzero(true))
    return count


def _lead(d: dict) -> dict:
    """Give every array a leading slice axis of 1, as ``compare`` takes."""
    return {f: np.asarray(v)[None] for f, v in d.items()}


def _f32(d: dict) -> dict:
    return {f: (np.asarray(v, np.float32) if np.asarray(v).dtype.kind == "f"
                else np.asarray(v)) for f, v in d.items()}


class Driver(_fleet.Driver):
    def __init__(self, config: dict, traffic: dict, seed: int, chips: int):
        super().__init__(config, traffic, seed, chips)
        if self.k != len(config["slices"]):
            raise ValueError(f"traffic has {self.k} slices, the configuration "
                             f"{len(config['slices'])}")
        self.shapes = [(s["n_cu"], s["n_ec"]) for s in config["slices"]]

    def jobs(self) -> list:
        """One ``SliceJob`` per configured slice, at its own size."""
        harness.use_src()
        from repro.core import DS, CocktailConfig, SliceJob
        c = self.config
        return [SliceJob(CocktailConfig(
            n_cu=s["n_cu"], n_ec=s["n_ec"], pair_iters=c["pair_iters"],
            f_base=tuple(float(f) for f in s["f_base"]), seed=seed,
            **c["slice"]), spec=DS) for s, seed in zip(c["slices"], self.seeds)]

    def setup(self) -> None:
        import jax
        jax.config.update("jax_default_matmul_precision",
                          self.config["matmul_precision"])
        jobs = self.jobs()
        from repro.core import FleetEngine
        self.eng = FleetEngine.from_jobs(jobs)
        state = self.eng.init()
        for i in range(int(self.traffic["warm_slots"])):
            nxt, rec = self.eng.run(1, state)
            jax.block_until_ready((nxt, rec))
            if i == 0:
                self.first = (state, nxt, rec)
            state = nxt
        self.state = state

    def check(self, side: str = "program") -> tuple[bool, list, dict]:
        """Compare each slice's slots with the float32 reference at its true
        shape, from the same input states, and count nonzero padding;
        ``side="control"`` puts the reference computed in the
        configuration's control precision in the program's place (it must
        fail)."""
        import jax
        from reference import cocktail_sweep as ref
        if side not in self.SIDES:
            raise ValueError(f"unknown side {side!r}; expected one of {self.SIDES}")
        c, limits, shapes = self.config, self.config["limits"], self.shapes
        with jax.default_matmul_precision("highest"):
            init = ref.init_states(c, self.seeds)
            exact = 0
            for k, (n, m) in enumerate(shapes):
                prog = region(self.first[0], k, n, m)
                exact += sum(int(np.sum(np.asarray(init[k][f]) != prog[f]))
                             for f in init[k])
            gaps = {"state": 0.0, "decision": 0.0, "record": 0.0, "exact": exact}
            pad = 0
            pairs = [self.first] + [(a, b, r) for _, a, b, r in self.kept]
            for s_in, s_out, rec in pairs:
                effects = {f: np.asarray(s_out[f], np.float64)
                           - np.asarray(s_in[f], np.float64) for f in EFFECTS}
                pad += (pad_nonzero(s_in, shapes) + pad_nonzero(s_out, shapes)
                        + pad_nonzero(effects, shapes, EFFECTS))
                ins = [region(s_in, k, n, m) for k, (n, m) in enumerate(shapes)]
                want = ref.slots(c, ins, c["dtype"])
                if side == "control":
                    got = [(_f32(o), _f32(r))
                           for o, r in ref.slots(c, ins, c["control_dtype"])]
                else:
                    got = [(region(s_out, k, n, m),
                            {f: v[k] for f, v in rec.items()})
                           for k, (n, m) in enumerate(shapes)]
                for s_k, (o, r), (o_ref, r_ref) in zip(ins, got, want):
                    g = compare(_lead(s_k), _lead(o), _lead(r), _lead(o_ref),
                                _lead(r_ref))
                    for key in ("state", "decision", "record"):
                        gaps[key] = max(gaps[key], g[key])
                    gaps["exact"] += g["exact"]
        checks = [("state_gap", gaps["state"], limits["state_gap"]),
                  ("decision_gap", gaps["decision"], limits["decision_gap"]),
                  ("record_gap", gaps["record"], limits["record_gap"]),
                  ("exact_mismatch", gaps["exact"], 0),
                  ("pad_nonzero", pad, limits["pad_nonzero"])]
        ok = all(v <= lim for _, v, lim in checks)
        return ok, checks, {"checked": len(pairs)}
