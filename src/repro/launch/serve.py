"""Batched decode serving driver: prefill-free KV-cache generation demo.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-32b --reduced \
        --batch 4 --prompt-len 16 --gen 32
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced as make_reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_serve_step
from repro.models import build_model
from repro.parallel.sharding import mesh_context


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minitron-4b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = make_reduced(cfg)
    model = build_model(cfg)
    mesh = make_host_mesh()

    with mesh_context(mesh):
        params = model.init(jax.random.PRNGKey(args.seed))
        max_len = args.prompt_len + args.gen
        cache = model.init_cache(args.batch, max_len)
        if cfg.family == "encdec":
            from repro.models import encdec
            frames = jax.random.normal(
                jax.random.PRNGKey(1), (args.batch, cfg.enc_ctx, cfg.d_model))
            cache = encdec.prefill_cross(cfg, params, frames, cache)
        step = jax.jit(make_serve_step(model), donate_argnums=(1,))

        rng = np.random.default_rng(args.seed)
        prompt = rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len))
        # teacher-forced prefill via repeated decode (prefill kernel covered
        # by the prefill_32k dry-run cells)
        tok = None
        t0 = time.time()
        for t in range(args.prompt_len):
            tok, cache = step(params, cache,
                              jnp.asarray(prompt[:, t:t + 1], jnp.int32))
        generated = []
        for _ in range(args.gen):
            tok, cache = step(params, cache, tok)
            generated.append(np.asarray(tok)[:, 0])
        dt = time.time() - t0
        out = np.stack(generated, axis=1)
        summary = {
            "arch": cfg.name, "batch": args.batch, "generated": args.gen,
            "tokens_per_s": round(args.batch * (args.prompt_len + args.gen) / dt, 1),
            "sample_tokens": out[0][:8].tolist(),
        }
        print(json.dumps(summary))
        return summary


if __name__ == "__main__":
    enable_compile_cache()
    main()
