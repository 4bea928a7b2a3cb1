"""Fleet cells: the online loop an operator runs over K network slices.

Set-up builds ``FleetEngine.from_jobs`` for the traffic's slices and drives
the first slots through the same call the window uses. The window calls
``state, rec = eng.run(1, state)`` and waits for the result, once per slot,
with the state carried from call to call, until the time is up.

The program runs as the configuration states it: float32 throughout, so
JAX's default matmul precision is set to the configuration's
``matmul_precision`` before anything is traced (the program leaves the
precision of its decision assembly to that default).

Correctness: a sample of the window's slots, drawn from the seed, together
with the first slot from the initial state, is recomputed by the plain
reference (``reference/cocktail_slot.py``) from the same input state, and
the program's next state, the slot's decision effects and its record are
compared with the reference's.
"""
from __future__ import annotations

import time

import numpy as np

from harness import sub_seed, use_src
from slotcheck import compare, flat_record, flat_state


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int, chips: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.k = int(traffic["slices"])
        self.seeds = [sub_seed(seed, 1, i) for i in range(self.k)]
        self.rng = np.random.default_rng(sub_seed(seed, 2))
        self.kept = []  # (window slot index, state in, state out, record)
        self.first = None

    # ------------------------------------------------------------ set-up

    def setup(self) -> None:
        use_src()
        import jax
        jax.config.update("jax_default_matmul_precision",
                          self.config["matmul_precision"])
        from repro.core import DS, CocktailConfig, FleetEngine, SliceJob
        c = self.config
        sl = c["slice"]
        jobs = [SliceJob(CocktailConfig(
            n_cu=c["n_cu"], n_ec=c["n_ec"], pair_iters=c["pair_iters"],
            delta=sl["delta"], eps=sl["eps"], rho=sl["rho"], q0=sl["q0"],
            zeta=sl["zeta"], d_base=sl["d_base"], cap_d_base=sl["cap_d_base"],
            f_base=tuple(float(f) for f in c["f_base"]), c_base=sl["c_base"],
            e_base=sl["e_base"], p_base=sl["p_base"], seed=s), spec=DS)
            for s in self.seeds]
        self.eng = FleetEngine.from_jobs(jobs)
        state = self.eng.init()
        for i in range(int(self.traffic["warm_slots"])):
            nxt, rec = self.eng.run(1, state)
            jax.block_until_ready((nxt, rec))
            if i == 0:
                self.first = (state, nxt, rec)
            state = nxt
        self.state = state

    # ------------------------------------------------------------ window

    def window(self, seconds: float) -> dict:
        import jax
        run, state = self.eng.run, self.state
        keep = int(self.traffic["check_slots"])
        lat = []
        t_end = time.perf_counter() + seconds
        t0 = time.perf_counter()
        while True:
            t = time.perf_counter()
            if t >= t_end:
                break
            nxt, rec = run(1, state)
            jax.block_until_ready((nxt, rec))
            lat.append(time.perf_counter() - t)
            # reservoir sample of the window's slots, drawn from the seed
            i = len(lat) - 1
            j = i if i < keep else int(self.rng.integers(0, i + 1))
            if j < keep:
                item = (i, state, nxt, rec)
                if i < keep:
                    self.kept.append(item)
                else:
                    self.kept[j] = item
            state = nxt
        elapsed = time.perf_counter() - t0
        self.state = state
        return {"slots": len(lat), "elapsed_s": elapsed, "latency_s": lat,
                "slices": self.k}

    def program_text(self) -> str:
        """HLO text of the compiled slot program the window drives (the
        compile is a cache hit)."""
        return self.eng.lower(1, self.state).compile().as_text()

    @staticmethod
    def end_to_end(counts: dict) -> dict:
        return {"slice_slots_per_s": {
            "value": counts["slices"] * counts["slots"] / counts["elapsed_s"],
            "unit": "slots/s"}}

    # ------------------------------------------------------- correctness

    def free(self) -> None:
        """Copy what the check needs to the host; drop the program's state."""
        self.first = tuple(flat_state(x) if i < 2 else flat_record(x)
                           for i, x in enumerate(self.first))
        self.kept = [(i, flat_state(a), flat_state(b), flat_record(c))
                     for i, a, b, c in self.kept]
        del self.eng, self.state

    def reference(self, dt_name: str = "float32"):
        """The jitted reference slot, vmapped over the K slices."""
        import jax
        import jax.numpy as jnp
        from reference import cocktail_slot as ref
        dt = jnp.dtype(dt_name)
        c = {**self.config["slice"], **{k: self.config[k] for k in
                                         ("n_cu", "n_ec", "f_base")}}
        p = ref.slice_params(c, dt)
        n, m, iters = c["n_cu"], c["n_ec"], self.config["pair_iters"]
        one = lambda s: ref.slot(p, s, n, m, iters, dt)[:2]
        return c, jax.jit(jax.vmap(one))

    SIDES = ("program", "control")

    def check(self, side: str = "program") -> tuple[bool, list, dict]:
        """Compare the program's slots with the float32 reference from the
        same input states; ``side="control"`` puts the reference computed
        in the configuration's control precision in the program's place
        (it must fail)."""
        import jax
        from reference import cocktail_slot as ref
        if side not in self.SIDES:
            raise ValueError(f"unknown side {side!r}; expected one of {self.SIDES}")
        limits = self.config["limits"]
        c, slot = self.reference(self.config["dtype"])
        if side == "control":
            _, low = self.reference(self.config["control_dtype"])
        with jax.default_matmul_precision("highest"):
            init = [ref.init_state(c, s) for s in self.seeds]
            init = {k: np.stack([x[k] for x in init]) for k in init[0]}
            exact = sum(int(np.sum(np.asarray(init[k]) != np.asarray(self.first[0][k])))
                        for k in init)
            gaps = {"state": 0.0, "decision": 0.0, "record": 0.0, "exact": exact}
            pairs = [self.first] + [(a, b, r) for _, a, b, r in self.kept]
            for s_in, s_out, rec in pairs:
                r_out, r_rec = jax.device_get(slot(s_in))
                if side == "control":
                    s_out, rec = jax.device_get(low(s_in))
                    f32 = lambda v: (np.asarray(v, np.float32)
                                     if np.asarray(v).dtype.kind == "f" else np.asarray(v))
                    s_out = {k: f32(v) for k, v in s_out.items()}
                    rec = {k: f32(v) for k, v in rec.items()}
                g = compare(s_in, s_out, rec, r_out, r_rec)
                for k in ("state", "decision", "record"):
                    gaps[k] = max(gaps[k], g[k])
                gaps["exact"] += g["exact"]
        checks = [("state_gap", gaps["state"], limits["state_gap"]),
                  ("decision_gap", gaps["decision"], limits["decision_gap"]),
                  ("record_gap", gaps["record"], limits["record_gap"]),
                  ("exact_mismatch", gaps["exact"], 0)]
        ok = all(v <= lim for _, v, lim in checks)
        return ok, checks, {"checked": len(pairs)}
