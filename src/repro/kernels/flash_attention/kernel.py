"""Pallas TPU flash-attention forward kernel.

Design (TPU-native, not a CUDA port):
  * grid (B, H, n_q_blocks, n_kv_blocks), kv innermost — the online-softmax
    state (m, l, acc) lives in VMEM scratch and survives across kv steps;
  * BlockSpecs stream HBM->VMEM tiles of q (Bq x hd), k/v (Bk x hd) from a
    (B, H, S, hd) layout, so each block's last two dims are (rows, whole
    hd); a sequence longer than one block is padded to whole blocks
    (Bk a multiple of 128) and the padded kv is masked as invalid;
  * GQA handled in the index map: q head h reads kv head h // group — no kv
    replication in memory;
  * causal / sliding-window / prefix masks are computed from the position
    blocks; fully-masked (q_blk, kv_blk) tiles skip the matmuls entirely via
    @pl.when (this is where the kernel beats the chunked-jnp fallback, which
    cannot skip);
  * fp32 accumulation; attention soft-capping (gemma2) fused into the tile.

The backward pass uses jax.custom_vjp with recompute-from-residuals falling
back to the chunked-jnp path — the fwd kernel is the serving/prefill hot
spot the roofline targets. Validated in interpret mode against ref.py;
tests/test_tpu_compile.py compiles it for a TPU v5e.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import AttnSpec

_NEG = -1e30


def _fwd_kernel(qp_ref, kp_ref, kval_ref, q_ref, k_ref, v_ref, o_ref,
                acc_ref, m_ref, l_ref, *, spec: AttnSpec, scale: float,
                n_kv_blocks: int):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qp = qp_ref[...]  # (Bq, 1) q positions as a column
    kp = kp_ref[...]  # (1, Bk) kv positions as a row
    mask = (kp <= qp) if spec.causal else jnp.ones_like(kp <= qp)
    if spec.window > 0:
        mask = mask & (qp - kp < spec.window)
    if spec.prefix_len > 0:
        mask = mask | (kp < spec.prefix_len)
    mask = mask & (kval_ref[...] > 0)

    # block-level skip: no matmul for a tile in which nothing is visible
    @pl.when(jnp.max(jnp.where(mask, 1, 0)) > 0)
    def _tile():
        q = q_ref[...]  # (Bq, hd)
        k = k_ref[...]  # (Bk, hd)
        v = v_ref[...]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if spec.softcap > 0:
            logits = spec.softcap * jnp.tanh(logits / spec.softcap)
        logits = jnp.where(mask, logits, _NEG)
        m_prev = m_ref[...]  # (Bq, 1)
        m_new = jnp.maximum(m_prev, logits.max(axis=1, keepdims=True))
        p = jnp.exp(logits - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == n_kv_blocks - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        out = jnp.where(m_ref[...] > _NEG / 2, out, 0.0)
        o_ref[...] = out.astype(o_ref.dtype)


def _block(size: int, block: int, align: int) -> int:
    """Block length along a sequence axis: the whole axis when it fits one
    block (a block equal to the array's dim is always legal), else the
    requested block rounded up to the TPU tiling ``align``."""
    b = -(-min(block, size) // align) * align
    return size if b >= size else b


def _pad_seq(x, n: int, axis: int):
    """Zero-pad ``axis`` of ``x`` up to length ``n``."""
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, n - x.shape[axis])
    return jnp.pad(x, widths)


def _fwd(q, k, v, q_pos, kv_pos, spec: AttnSpec, kv_valid, scale,
         block_q: int, block_kv: int, interpret: bool):
    """Kernel layout: q/k/v/out as (B, H, S, hd), so every block's last two
    dims are (rows, whole hd); q positions as (B, Sq, 1) columns and kv
    positions/validity as (B, 1, Skv) rows. Sequences that span several
    blocks are padded up to a whole number of blocks (q rows by 8, kv rows
    by 128, the TPU tiling); padded kv entries are invalid, padded q rows
    are sliced off."""
    b, sq, h, hd = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    group = h // hkv
    bq, bk = _block(sq, block_q, 8), _block(skv, block_kv, 128)
    nq, nk = -(-sq // bq), -(-skv // bk)
    sq_p, skv_p = nq * bq, nk * bk

    qt = _pad_seq(jnp.swapaxes(q, 1, 2), sq_p, 2)
    kt = _pad_seq(jnp.swapaxes(k, 1, 2), skv_p, 2)
    vt = _pad_seq(jnp.swapaxes(v, 1, 2), skv_p, 2)
    qp = _pad_seq(q_pos.astype(jnp.int32), sq_p, 1)[:, :, None]
    kp = _pad_seq(kv_pos.astype(jnp.int32), skv_p, 1)[:, None, :]
    kval = _pad_seq(kv_valid.astype(jnp.int32), skv_p, 1)[:, None, :]

    kernel = functools.partial(_fwd_kernel, spec=spec, scale=scale,
                               n_kv_blocks=nk)
    out = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((None, bq, 1), lambda ib, ih, iq, ik: (ib, iq, 0)),
            pl.BlockSpec((None, 1, bk), lambda ib, ih, iq, ik: (ib, 0, ik)),
            pl.BlockSpec((None, 1, bk), lambda ib, ih, iq, ik: (ib, 0, ik)),
            pl.BlockSpec((None, None, bq, hd),
                         lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((None, None, bk, hd),
                         lambda ib, ih, iq, ik: (ib, ih // group, ik, 0)),
            pl.BlockSpec((None, None, bk, hd),
                         lambda ib, ih, iq, ik: (ib, ih // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, bq, hd),
                               lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq_p, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),  # acc
            pltpu.VMEM((bq, 1), jnp.float32),  # m
            pltpu.VMEM((bq, 1), jnp.float32),  # l
        ],
        interpret=interpret,
    )(qp, kp, kval, qt, kt, vt)
    return jnp.swapaxes(out[:, :, :sq], 1, 2)


def flash_attention_pallas(q, k, v, q_pos, kv_pos, spec: AttnSpec,
                           kv_valid=None, scale: Optional[float] = None,
                           block_q: int = 512, block_kv: int = 512,
                           interpret: bool = False):
    """Forward flash attention via Pallas; differentiable via custom_vjp with
    a chunked-jnp backward (recompute)."""
    hd = q.shape[-1]
    scale = hd ** -0.5 if scale is None else scale

    @jax.custom_vjp
    def _attn(q, k, v, q_pos, kv_pos, kv_valid):
        return _fwd(q, k, v, q_pos, kv_pos, spec, kv_valid, scale,
                    block_q, block_kv, interpret)

    def _attn_fwd(q, k, v, q_pos, kv_pos, kv_valid):
        out = _fwd(q, k, v, q_pos, kv_pos, spec, kv_valid, scale,
                   block_q, block_kv, interpret)
        return out, (q, k, v, q_pos, kv_pos, kv_valid)

    def _attn_bwd(res, g):
        from .ops import attention_chunked
        q, k, v, q_pos, kv_pos, kv_valid = res
        _, vjp = jax.vjp(
            lambda q_, k_, v_: attention_chunked(
                q_, k_, v_, q_pos, kv_pos, spec, kv_valid, scale), q, k, v)
        dq, dk, dv = vjp(g)
        return dq, dk, dv, None, None, None

    _attn.defvjp(_attn_fwd, _attn_bwd)
    if kv_valid is None:
        kv_valid = jnp.ones((q.shape[0], k.shape[1]), bool)
    return _attn(q, k, v, q_pos, kv_pos, kv_valid)
