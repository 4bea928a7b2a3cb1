"""Smoke test of the system's two programs on one TPU chip.

    python3 chip_smoke.py             # one chip: every phase below
    python3 chip_smoke.py --chips 4   # four chips: the cross-chip paths only

Phases (one process; the default run uses ``jax.devices()[0]`` alone):

  device    the first device must be a TPU; there is no CPU fallback.
  matchers  the three Pallas greedy matchers against their jnp refs on the
            same chip arrays, bit for bit: (20, 5), (1024, 16), a batched
            (8, 1024, 16) and a masked ragged case.
  fleet     the DS slot program through ``FleetEngine.from_jobs(...).run``:
            8 slices at N=1024, M=16 plus one at the paper's simulation
            scale (20 x 5), padded into one program. The compiled program
            must hold the Pallas kernels; records must be finite with skew in
            [0, 1] and data trained after warm-up; the paper-scale slice's
            end-of-run unit cost and skew must agree with the same slice run
            on the host CPU within FLEET_RTOL.
  train     ``repro.launch.train.main`` for whisper-base at full width with
            the DS scheduler in the loop; losses finite, the compiled train
            step holds the Pallas attention kernel, and one batch's loss
            agrees with the chunked-attention model within LOSS_RTOL.

With ``--chips 4`` only the paths that exist across chips run, each against
its one-chip counterpart: a whisper-base train step data-parallel over four
chips (ECs as data-parallel groups) against the same params and global batch
on one chip, and the fleet's K axis sharded over four chips against the
unsharded run.

Earlier lines print each phase's numbers; the last line is one JSON object
``{"ok": true, "device": {...}}``. Any failed check exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

SEED = 0
FLEET_SLOTS = 20
# End-of-run unit cost / skew of the paper-scale slice, TPU vs host CPU.
# The two backends round transcendentals differently, which can flip a
# greedy tie and move the trajectory. Measured on a TPU v5 lite: 3.1e-4
# (unit cost) and 2.0e-3 (skew) after 20 slots.
FLEET_RTOL = 1e-2
# Loss of one batch, Pallas flash attention vs the chunked jnp path, both in
# the model's bf16 compute dtype on the chip. Measured on a TPU v5 lite:
# 5.2e-6.
LOSS_RTOL = 1e-3
# Four chips vs one on one train step (same params, same global batch): the
# partitioned program reduces in another order in bf16.
DP_RTOL = 2e-2


def _log(phase: str, **numbers):
    print(f"[{phase}] " + json.dumps(numbers, sort_keys=True), flush=True)


def _check(cond: bool, what: str):
    if not cond:
        raise AssertionError(what)


def _device_or_exit(n_chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"no TPU: JAX found {devs[0].platform!r} devices only",
              file=sys.stderr)
        sys.exit(2)
    if len(devs) < n_chips:
        print(f"need {n_chips} chips, JAX found {len(devs)}", file=sys.stderr)
        sys.exit(2)
    return devs


def _import_repo():
    root = pathlib.Path(__file__).resolve().parent
    sys.path[:0] = [str(root), str(root / "src")]
    from repro.launch.compile_cache import enable_compile_cache
    return enable_compile_cache()


# ---------------------------------------------------------------- matchers

def _logw(rng, shape, inf_frac=0.2):
    logw = np.log(rng.uniform(0.2, 40.0, shape)).astype(np.float32)
    logw[rng.random(shape) < inf_frac] = -np.inf
    return jnp.asarray(logw)


def _solo_pair(rng, lead, m):
    solo = rng.uniform(-1.0, 5.0, lead + (m,)).astype(np.float32)
    pair = rng.uniform(-2.0, 10.0, lead + (m, m))
    pair = ((pair + np.swapaxes(pair, -1, -2)) / 2.0).astype(np.float32)
    return jnp.asarray(solo), jnp.asarray(pair)


def phase_matchers():
    from repro.kernels.matching import ops

    rng = np.random.default_rng(SEED)
    for shape in [(20, 5), (1024, 16), (8, 1024, 16)]:
        m = shape[-1]
        logw = _logw(rng, shape)
        w = jnp.exp(logw) - 1.0  # linear weights, some <= 0
        solo, pair = _solo_pair(rng, shape[:-2], m)
        cases = {
            "collection": lambda impl: ops.greedy_collection(logw, impl=impl),
            "assignment": lambda impl: ops.greedy_assignment(w, impl=impl),
            "pairing": lambda impl: ops.greedy_pairing(solo, pair, impl=impl),
        }
        for name, fn in cases.items():
            t0 = time.perf_counter()
            got = jax.block_until_ready(fn("pallas"))
            secs = time.perf_counter() - t0
            want = fn("ref")
            for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                _check(bool(jnp.array_equal(g, r)),
                       f"pallas {name} != ref at {shape}")
            sel = float(jnp.sum(jax.tree.leaves(got)[0]))
            _log("matchers", op=name, shape=list(shape), selected=sel,
                 first_call_s=secs, bitexact=True)
    # Ragged fleet slice: 700 of 1024 CUs and 11 of 16 ECs are real.
    n, m = 1024, 16
    cu = jnp.asarray(np.arange(n) < 700, jnp.float32)
    ec = jnp.asarray(np.arange(m) < 11, jnp.float32)
    logw = _logw(rng, (n, m))
    solo, pair = _solo_pair(rng, (), m)
    for name, fn in {
        "collection": lambda impl: ops.greedy_collection(logw, cu, ec, impl=impl),
        "assignment": lambda impl: ops.greedy_assignment(jnp.exp(logw) - 1.0,
                                                         cu, ec, impl=impl),
        "pairing": lambda impl: ops.greedy_pairing(solo, pair, ec, impl=impl),
    }.items():
        got, want = fn("pallas"), fn("ref")
        for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            _check(bool(jnp.array_equal(g, r)), f"masked pallas {name} != ref")
        a = jax.tree.leaves(got)[0]
        _check(float(jnp.sum(a[..., 11:])) == 0.0, f"masked {name} chose padding")
        _log("matchers", op=name, shape=[n, m], masked=True,
             selected=float(jnp.sum(a)), bitexact=True)


# ---------------------------------------------------------------- fleet

def _metro_jobs(k: int):
    """k slices at N=1024 CUs x M=16 ECs, the paper's simulation settings
    otherwise, one seed each."""
    from benchmarks.common import sim_config
    from repro.core import SliceJob
    return [SliceJob(sim_config(n_cu=1024, n_ec=16, seed=SEED + 1 + i),
                     name=f"metro{i}") for i in range(k)]


def _paper_job():
    """One slice at the paper's simulation scale (Sec. IV-C, 20 x 5)."""
    from benchmarks.common import sim_config
    from repro.core import SliceJob
    return SliceJob(sim_config(), name="paper-sim")


def _slice_end(eng, state, recs, k: int):
    from repro.core import metrics
    st = eng.slice_state(state, k)
    return metrics.unit_cost(st), float(recs.skew[-1, k])


def phase_fleet():
    from repro.core import FleetEngine

    eng = FleetEngine.from_jobs(_metro_jobs(8) + [_paper_job()])
    t0 = time.perf_counter()
    state, recs = jax.block_until_ready(eng.run(FLEET_SLOTS))
    first_run_s = time.perf_counter() - t0  # compile included
    t0 = time.perf_counter()
    jax.block_until_ready(eng.run(FLEET_SLOTS))
    run_s = time.perf_counter() - t0
    hlo = eng.lower(FLEET_SLOTS).compile().as_text()  # cached executable
    _check("tpu_custom_call" in hlo, "fleet slot program holds no Pallas kernel")
    for f in recs._fields:
        _check(bool(jnp.all(jnp.isfinite(getattr(recs, f)))), f"non-finite {f}")
    skew = np.asarray(recs.skew)
    _check(bool((skew >= 0).all() and (skew <= 1).all()), "skew outside [0, 1]")
    trained_late = np.asarray(recs.trained)[FLEET_SLOTS // 2:].sum(axis=0)
    _check(bool((trained_late > 0).all()),
           f"a slice trained nothing after warm-up: {trained_late.tolist()}")

    k_paper = eng.n_slices - 1
    tpu_cost, tpu_skew = _slice_end(eng, state, recs, k_paper)
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        ref = FleetEngine.from_jobs([_paper_job()])
        rstate, rrecs = ref.run(FLEET_SLOTS)
        cpu_cost, cpu_skew = _slice_end(ref, rstate, rrecs, 0)
    d_cost = abs(tpu_cost - cpu_cost) / max(abs(cpu_cost), 1e-12)
    d_skew = abs(tpu_skew - cpu_skew) / max(abs(cpu_skew), 1e-12)
    _log("fleet", slices=eng.n_slices, shape=[eng.shape.n_cu, eng.shape.n_ec],
         slots=FLEET_SLOTS, first_run_s=first_run_s, second_run_s=run_s,
         trained_second_half=trained_late.tolist(),
         paper_unit_cost_tpu=tpu_cost, paper_unit_cost_cpu=cpu_cost,
         paper_skew_tpu=tpu_skew, paper_skew_cpu=cpu_skew,
         rel_diff_cost=d_cost, rel_diff_skew=d_skew)
    _check(d_cost <= FLEET_RTOL and d_skew <= FLEET_RTOL,
           f"paper slice TPU vs CPU beyond rtol {FLEET_RTOL}: "
           f"cost {d_cost:.3g}, skew {d_skew:.3g}")


# ---------------------------------------------------------------- training

TRAIN_ARGS = ["--arch", "whisper-base", "--steps", "6", "--slot-every", "2",
              "--batch", "8", "--seq", "448", "--scheduler", "ds",
              "--log-every", "2", "--seed", str(SEED)]


def _one_batch(cfg, batch_size: int, seq: int, key):
    from repro.launch.train import stub_frontend_inputs
    kt, kf = jax.random.split(key)
    tokens = jax.random.randint(kt, (batch_size, seq + 1), 0, cfg.vocab_size)
    return {"tokens": tokens[:, :-1], "labels": tokens[:, 1:],
            "weights": jnp.ones((batch_size,), jnp.float32),
            **stub_frontend_inputs(cfg, batch_size, kf)}


def phase_train():
    from repro.configs import get_config
    from repro.launch import train
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.optim import AdamWConfig
    from repro.parallel.sharding import mesh_context

    t0 = time.perf_counter()
    summary = train.main(TRAIN_ARGS + ["--devices", "1"])
    train_s = time.perf_counter() - t0
    losses = summary["losses"]
    _check(len(losses) == 6 and all(np.isfinite(losses)), f"losses {losses}")

    cfg = get_config("whisper-base")
    batch = _one_batch(cfg, 8, 448, jax.random.PRNGKey(SEED + 1))
    mesh = make_host_mesh(devices=jax.devices()[:1])
    model = build_model(cfg)
    with mesh_context(mesh):
        params, opt_state, step_fn, _, _ = train.place_training(
            model, mesh, AdamWConfig(), 6, SEED)
        batch = train.put_batch(batch, mesh)
        hlo = step_fn.lower(params, opt_state, batch).compile().as_text()
        _check("tpu_custom_call" in hlo, "train step holds no Pallas kernel")
        loss_pallas = float(jax.jit(model.loss)(params, batch)[0])
        chunked = build_model(cfg, impl="chunked")
        loss_chunked = float(jax.jit(chunked.loss)(params, batch)[0])
    rel = abs(loss_pallas - loss_chunked) / abs(loss_chunked)
    _log("train", arch=cfg.name, steps=len(losses), losses=losses,
         wall_s=train_s, sched_trained=summary["sched_trained"],
         skew=summary["skew_degree"], loss_pallas=loss_pallas,
         loss_chunked=loss_chunked, rel_diff=rel)
    _check(rel <= LOSS_RTOL, f"pallas vs chunked loss rel diff {rel:.3g}")


# ---------------------------------------------------------------- 4 chips

def _train_step_on(devices, batch):
    from repro.configs import get_config
    from repro.launch import train
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.optim import AdamWConfig
    from repro.parallel.sharding import mesh_context

    mesh = make_host_mesh(devices=devices)
    with mesh_context(mesh):
        params, opt_state, step_fn, _, _ = train.place_training(
            build_model(get_config("whisper-base")), mesh, AdamWConfig(), 6,
            SEED)
        out = step_fn(params, opt_state, train.put_batch(batch, mesh))
        return float(out[2]["loss"]), float(out[2]["grad_norm"])


def phase_four_chips():
    from repro.configs import get_config
    from repro.core import FleetEngine
    from repro.launch.mesh import make_host_mesh

    devs = jax.devices()[:4]
    batch = _one_batch(get_config("whisper-base"), 8, 448,
                       jax.random.PRNGKey(SEED + 1))
    loss4, gn4 = _train_step_on(devs, batch)
    loss1, gn1 = _train_step_on(devs[:1], batch)
    d_loss = abs(loss4 - loss1) / abs(loss1)
    d_gn = abs(gn4 - gn1) / abs(gn1)
    _log("dp4", loss_4chips=loss4, loss_1chip=loss1, grad_norm_4chips=gn4,
         grad_norm_1chip=gn1, rel_diff_loss=d_loss, rel_diff_grad_norm=d_gn)
    _check(d_loss <= DP_RTOL and d_gn <= DP_RTOL,
           f"4 chips vs 1: loss {d_loss:.3g}, grad norm {d_gn:.3g}")

    eng = FleetEngine.from_jobs(_metro_jobs(8))
    mesh = make_host_mesh(devices=devs)
    state_s, recs_s = jax.block_until_ready(eng.run(FLEET_SLOTS, mesh=mesh))
    used = {d.id for leaf in jax.tree.leaves(state_s)
            for d in leaf.sharding.device_set}
    state_1, recs_1 = eng.run(FLEET_SLOTS)
    diffs = {f: float(np.max(np.abs(np.asarray(getattr(recs_s, f))
                                    - np.asarray(getattr(recs_1, f)))))
             for f in recs_1._fields}
    _log("fleet4", slices=eng.n_slices, devices_used=sorted(used),
         max_abs_diff=diffs)
    _check(len(used) == 4, f"sharded fleet state on devices {sorted(used)}")
    _check(all(v == 0.0 for v in diffs.values()),
           f"sharded fleet records differ from unsharded: {diffs}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    devs = _device_or_exit(args.chips)
    cache = _import_repo()
    dev = devs[0]
    _log("device", platform=dev.platform, kind=dev.device_kind,
         count=len(devs), jax=jax.__version__, compile_cache=cache)
    if args.chips == 4:
        phases = [("four_chips", phase_four_chips)]
    else:
        phases = [("matchers", phase_matchers), ("fleet", phase_fleet),
                  ("train", phase_train)]
    with jax.default_device(dev):
        for name, fn in phases:
            t0 = time.perf_counter()
            fn()
            _log(name, phase_s=time.perf_counter() - t0, ok=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}))


if __name__ == "__main__":
    main()
