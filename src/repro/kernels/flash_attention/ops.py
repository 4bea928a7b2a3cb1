"""Attention front-end: backend dispatch + memory-efficient chunked jnp path.

``flash_attention`` is what models call. Dispatch, by the platform the
program is lowered for:
  * TPU        -> the Pallas online-softmax kernel (kernel.py)
  * elsewhere  -> ``attention_chunked``: double-chunked (q and kv) online
                  softmax in pure jnp. O(Cq*Ck) live logits instead of
                  O(Sq*Skv); sliding-window attention reads only the
                  window-sized KV span (linear in window, not in Skv) via a
                  static-length dynamic slice — this is what makes the
                  long_500k cells lowerable.

Note (roofline): for *full causal* attention the chunked path evaluates all
(q-chunk, kv-chunk) tiles including fully-masked ones (~2x FLOP overcount vs
causal-optimal); the Pallas kernel skips them on TPU. Windowed attention is
tight on both paths. EXPERIMENTS.md corrects for this in MODEL_FLOPS ratios.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from .ref import AttnSpec, attention_mask, attention_ref

_NEG = -1e30


def _chunk_sizes(sq: int, skv: int, q_chunk: int, kv_chunk: int) -> tuple[int, int]:
    qc = min(q_chunk, sq)
    while sq % qc:
        qc //= 2
    kc = min(kv_chunk, skv)
    while skv % kc:
        kc //= 2
    return max(qc, 1), max(kc, 1)


def attention_chunked(q, k, v, q_pos, kv_pos, spec: AttnSpec,
                      kv_valid=None, scale=None,
                      q_chunk: int = 1024, kv_chunk: int = 1024):
    """Online-softmax attention, chunked over q (outer scan) and kv (inner
    scan). Same signature/semantics as attention_ref."""
    b, sq, h, hd = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    group = h // hkv
    scale = hd ** -0.5 if scale is None else scale
    qc, kc = _chunk_sizes(sq, skv, q_chunk, kv_chunk)
    nq = sq // qc

    if kv_valid is None:
        kv_valid = jnp.ones((b, skv), bool)

    # Sliding window: restrict the kv span per q chunk to a static length.
    windowed = spec.window > 0 and spec.prefix_len == 0 and spec.causal
    if windowed:
        span = min(skv, -(-(spec.window + qc) // kc) * kc + kc)
    else:
        span = skv
    nk = span // kc

    q5 = q.reshape(b, nq, qc, h, hd)
    qpos3 = q_pos.reshape(b, nq, qc)

    def q_chunk_body(_, qi):
        qb = q5[:, qi]  # (B, qc, H, hd)
        qp = qpos3[:, qi]  # (B, qc)
        if windowed:
            # static-length slice covering [q_start - window + 1, q_end]
            q_start = qi * qc
            lo = jnp.clip(q_start + qc - span, 0, skv - span)
            kk = jax.lax.dynamic_slice_in_dim(k, lo, span, axis=1)
            vv = jax.lax.dynamic_slice_in_dim(v, lo, span, axis=1)
            kp = jax.lax.dynamic_slice_in_dim(kv_pos, lo, span, axis=1)
            kval = jax.lax.dynamic_slice_in_dim(kv_valid, lo, span, axis=1)
        else:
            kk, vv, kp, kval = k, v, kv_pos, kv_valid

        def kv_chunk_body(carry, ki):
            acc, m, l = carry
            ks = jax.lax.dynamic_slice_in_dim(kk, ki * kc, kc, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(vv, ki * kc, kc, axis=1)
            kps = jax.lax.dynamic_slice_in_dim(kp, ki * kc, kc, axis=1)
            kvs = jax.lax.dynamic_slice_in_dim(kval, ki * kc, kc, axis=1)
            if group > 1:  # GQA by per-chunk head replication
                ks = jnp.repeat(ks, group, axis=2)
                vs = jnp.repeat(vs, group, axis=2)
            logits = jnp.einsum("bqhd,bkhd->bhqk", qb.astype(jnp.float32),
                                ks.astype(jnp.float32)) * scale
            if spec.softcap > 0:
                logits = spec.softcap * jnp.tanh(logits / spec.softcap)
            mask = attention_mask(qp, kps, spec, kvs)  # (B, qc, kc)
            logits = jnp.where(mask[:, None, :, :], logits, _NEG)
            m_new = jnp.maximum(m, logits.max(axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, vs.astype(jnp.float32))
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((b, h, qc, hd), jnp.float32)
        m0 = jnp.full((b, h, qc), _NEG, jnp.float32)
        l0 = jnp.zeros((b, h, qc), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(kv_chunk_body, (acc0, m0, l0),
                                      jnp.arange(nk))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        out = jnp.where((m > _NEG / 2)[..., None], out, 0.0)  # fully-masked q
        return None, jnp.moveaxis(out, 1, 2).astype(q.dtype)  # (B, qc, H, hd)

    # remat: without it the kv-scan stores per-iteration softmax residuals
    # for backward, re-materialising the full O(Sq*Skv) logits
    _, outs = jax.lax.scan(jax.checkpoint(q_chunk_body), None, jnp.arange(nq))
    # outs: (nq, B, qc, H, hd) -> (B, Sq, H, hd)
    return jnp.moveaxis(outs, 0, 1).reshape(b, sq, h, hd)


def _per_shard(attn, q, k, v, q_pos, kv_pos, kv_valid):
    """Run ``attn`` once per shard of the model's mesh, if there is one.

    Mosaic kernels are not partitioned automatically, so under a mesh the
    kernel runs inside ``jax.shard_map``: batch over the data-parallel axes
    and heads over the tensor axis, each where it divides evenly (else that
    dim is whole on every shard). Attention is independent per (sample,
    head), so no collective is needed."""
    from repro.parallel.sharding import batch_axes, current_mesh, current_style
    mesh = current_mesh()
    if kv_valid is None:
        kv_valid = jnp.ones((q.shape[0], k.shape[1]), bool)
    if mesh is None or mesh.size == 1:
        return attn(q, k, v, q_pos, kv_pos, kv_valid)
    from jax.sharding import PartitionSpec as P

    bax = batch_axes(mesh)
    if q.shape[0] % math.prod(mesh.shape[a] for a in bax):
        bax = None
    hax = None
    if current_style() != "fsdp":
        tp = mesh.shape.get("model", 1)
        if tp > 1 and q.shape[2] % tp == 0 and k.shape[2] % tp == 0:
            hax = "model"
    heads, rows = P(bax, None, hax, None), P(bax, None)
    return jax.shard_map(attn, mesh=mesh,
                         in_specs=(heads, heads, heads, rows, rows, rows),
                         out_specs=heads, check_vma=False)(
        q, k, v, q_pos, kv_pos, kv_valid)


def flash_attention(q, k, v, q_pos, kv_pos, spec: AttnSpec, kv_valid=None,
                    scale=None, impl: str = "auto",
                    q_chunk: int = 1024, kv_chunk: int = 1024,
                    interpret: bool = False):
    """Public attention entry point. impl: auto | pallas | chunked | ref.

    ``auto`` is the Pallas kernel where the program is lowered for a TPU and
    the chunked path elsewhere (``jax.lax.platform_dependent``: the platform
    of the compile decides, not the process's default backend)."""
    if impl == "auto":
        def run(which):
            return lambda q, k, v: flash_attention(
                q, k, v, q_pos, kv_pos, spec, kv_valid, scale, which,
                q_chunk, kv_chunk, interpret)
        return jax.lax.platform_dependent(q, k, v, tpu=run("pallas"),
                                          default=run("chunked"))
    if q.shape[1] == 1 and impl == "chunked":
        # decode fast-path: single-pass exact attention. Chunking would
        # dynamic-slice a (possibly sequence-sharded) KV cache and force
        # full-cache all-gathers; the one-shot grouped einsum lets GSPMD keep
        # the contraction local per seq shard (partial softmax + small psum).
        return attention_ref(q, k, v, q_pos, kv_pos, spec, kv_valid, scale,
                             gqa="group")
    if impl == "pallas":
        from . import kernel
        return _per_shard(
            lambda q, k, v, qp, kp, kval: kernel.flash_attention_pallas(
                q, k, v, qp, kp, spec, kv_valid=kval, scale=scale,
                interpret=interpret),
            q, k, v, q_pos, kv_pos, kv_valid)
    if impl == "chunked":
        return attention_chunked(q, k, v, q_pos, kv_pos, spec, kv_valid,
                                 scale, q_chunk, kv_chunk)
    return attention_ref(q, k, v, q_pos, kv_pos, spec, kv_valid, scale)
