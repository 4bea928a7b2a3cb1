"""End-to-end Cocktail training driver.

Wires every layer together on whatever devices exist:

  Cocktail scheduler (core)  ->  per-slot x/y/z decisions
  CocktailSampler (data)     ->  per-EC batch composition + sample weights
  pjit train step (launch)   ->  weighted-psum aggregation == paper eq. 15
  CheckpointManager          ->  atomic snapshots + auto-resume (kill -9 safe)

ECs are the data-parallel shard groups; their simulated capacities f_j(t)
are heterogeneous, so the scheduler naturally throttles slow workers
(straggler mitigation) while the (phi, lam) multipliers repair the induced
data skew — the paper's mechanism doing cluster-scheduler duty.

    PYTHONPATH=src python -m repro.launch.train --arch minitron-4b --reduced \
        --steps 200 --batch 16 --seq 128
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import core
from repro.checkpoint import CheckpointManager
from repro.configs import get_config, reduced as make_reduced
from repro.data import CocktailSampler, TokenSource
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_train_step
from repro.models import build_model
from repro.optim import AdamWConfig, AdamWState, adamw_init
from repro.parallel.sharding import (batch_axes, mesh_context,
                                     shard_params_pspecs)


def build_cocktail(n_cu: int, n_ec: int, seed: int) -> core.CocktailConfig:
    # heterogeneous EC capacities (paper Sec. IV-C): stragglers are the
    # low-capacity workers
    caps = tuple(float(c) for c in
                 np.random.default_rng(seed).choice([8000, 14000, 20000, 48000], n_ec))
    return core.CocktailConfig(n_cu=n_cu, n_ec=n_ec, eps=0.1, delta=0.05,
                               f_base=caps, pair_iters=30, seed=seed)


def place_training(model, mesh, opt_cfg: AdamWConfig, total_steps: int,
                   seed: int):
    """Params and AdamW state from ``seed``, laid out on ``mesh`` by the
    FSDP x TP rules, and the jitted train step. Call inside
    ``mesh_context(mesh)``. Returns (params, opt_state, step_fn, params
    shardings, opt-state shardings)."""
    params = model.init(jax.random.PRNGKey(seed))
    opt_state = adamw_init(params)
    p_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                        shard_params_pspecs(params, mesh),
                        is_leaf=lambda x: isinstance(x, P))
    o_sh = AdamWState(step=NamedSharding(mesh, P()), m=p_sh, v=p_sh)
    params = jax.tree.map(jax.device_put, params, p_sh)
    opt_state = jax.tree.map(jax.device_put, opt_state, o_sh,
                             is_leaf=lambda x: isinstance(x, jax.Array))
    step_fn = jax.jit(make_train_step(model, opt_cfg, total_steps=total_steps),
                      donate_argnums=(0, 1))
    return params, opt_state, step_fn, p_sh, o_sh


def put_batch(batch: dict, mesh) -> dict:
    """Shard every batch leaf's leading (sample) axis over the DP axes: each
    data-parallel group is one EC."""
    bax = batch_axes(mesh)
    return {k: jax.device_put(v, NamedSharding(
        mesh, P(bax, *([None] * (v.ndim - 1))))) for k, v in batch.items()}


def stub_frontend_inputs(cfg, batch_size: int, key) -> dict:
    """Random stand-ins for the stubbed modality frontends (frames for
    enc-dec, image patches for VLM); empty for text-only families."""
    if cfg.family == "encdec":
        return {"frames": jax.random.normal(
            key, (batch_size, cfg.enc_ctx, cfg.d_model))}
    if cfg.family == "vlm":
        return {"patches": jax.random.normal(
            key, (batch_size, cfg.n_img_tokens, cfg.d_model))}
    return {}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minitron-4b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=16)  # global
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--n-cu", type=int, default=12)
    ap.add_argument("--slot-every", type=int, default=10)  # steps per slot
    ap.add_argument("--sched-warmup", type=int, default=8)  # max warmup slots
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--scheduler", default="ds", choices=sorted(core.ALL_SPECS))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--devices", type=int, default=0,
                    help="train on the first N devices (default: all)")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = make_reduced(cfg)
    model = build_model(cfg)
    mesh = make_host_mesh(devices=jax.devices()[:args.devices or None])
    dp = int(np.prod([dict(zip(mesh.axis_names, mesh.devices.shape))[a]
                      for a in batch_axes(mesh)]))
    n_ec = max(dp, 2)
    assert args.batch % n_ec == 0, "global batch must divide into ECs"

    # --- paper core: scheduler + non-IID sources + sampler ---
    ck = build_cocktail(args.n_cu, n_ec, args.seed)
    spec = core.ALL_SPECS[args.scheduler]
    sched_state = core.init_state(ck)
    # warm-up slots: EC-side queues R start empty, so the first few slots
    # only collect; spin the scheduler until data is actually being trained
    warm_dec = None
    for _ in range(args.sched_warmup):
        sched_state, _, warm_dec = core.step(ck, spec, sched_state)
        if float(warm_dec.x.sum() + warm_dec.y.sum()) > 0:
            break
    sources = [TokenSource(i, cfg.vocab_size, args.seq, seed=args.seed)
               for i in range(args.n_cu)]
    sampler = CocktailSampler(ck, sources, batch_per_ec=args.batch // n_ec,
                              seed=args.seed)

    # --- model + optimizer state ---
    opt_cfg = AdamWConfig(lr=args.lr)
    with mesh_context(mesh):
        params, opt_state, step_fn, p_sh, o_sh = place_training(
            model, mesh, opt_cfg, args.steps, args.seed)

        start = 0
        ckpt = None
        if args.checkpoint_dir:
            ckpt = CheckpointManager(args.checkpoint_dir,
                                     every_steps=args.checkpoint_every)
            resumed = ckpt.resume({"params": params, "opt": opt_state},
                                  shardings={"params": p_sh, "opt": o_sh})
            if resumed is not None:
                tree, meta, start = resumed
                params, opt_state = tree["params"], tree["opt"]
                print(f"resumed from step {start}")

        decision = warm_dec
        losses = []
        t0 = time.time()
        for it in range(start, args.steps):
            if decision is None or it % args.slot_every == 0:
                sched_state, rec, new_dec = core.step(ck, spec, sched_state)
                # steps run at a much finer timescale than slots: between
                # scheduler updates workers keep training the last scheduled
                # mix, so an occasional empty slot (multiplier oscillation)
                # does not stall the optimizer
                if decision is None or float(new_dec.x.sum() + new_dec.y.sum()) > 0:
                    decision = new_dec
            host_batch = sampler.sample(decision)
            batch = {
                "tokens": jnp.asarray(host_batch["tokens"]),
                "labels": jnp.asarray(host_batch["labels"]),
                "weights": jnp.asarray(host_batch["weights"]),
            }
            batch.update(stub_frontend_inputs(
                cfg, args.batch,
                jax.random.fold_in(jax.random.PRNGKey(args.seed), it)))
            batch = put_batch(batch, mesh)
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            losses.append(float(metrics["loss"]))
            if ckpt is not None:
                ckpt.maybe_save(it + 1, {"params": params, "opt": opt_state},
                                extra={"arch": cfg.name, "step": it + 1})
            if (it + 1) % args.log_every == 0:
                sk = float(core.skew_degree(ck, sched_state.queues.omega))
                print(f"step {it+1:5d} loss={metrics['loss']:.4f} "
                      f"gnorm={metrics['grad_norm']:.3f} "
                      f"sched_cost={float(sched_state.total_cost):.0f} "
                      f"skew={sk:.4f} "
                      f"({(time.time()-t0)/(it+1-start):.2f}s/step)")

        nonzero = [l for l in losses if l > 0]
        summary = {
            "arch": cfg.name, "steps": args.steps,
            "first_loss": float(np.mean(nonzero[:3])) if nonzero else None,
            "last_loss": float(np.mean(nonzero[-10:])) if nonzero else None,
            "min_loss": float(np.min(nonzero[3:])) if len(nonzero) > 3 else None,
            "scheduler": args.scheduler,
            "sched_cost": float(sched_state.total_cost),
            "sched_trained": float(sched_state.total_trained),
            "skew_degree": float(core.skew_degree(ck, sched_state.queues.omega)),
        }
        print(json.dumps(summary))
        return {**summary, "losses": losses}


if __name__ == "__main__":
    enable_compile_cache()
    main()
