"""Dispatch layer for the three greedy matching primitives.

These are the production entry points for every per-slot subproblem solver in
the core scheduler:

  * ``greedy_collection``  — skew-aware P1' (``datasche._collect_skew``)
  * ``greedy_assignment``  — plain P1 (``datasche._collect_plain``, the L-DS
    virtual step and NO-SDC)
  * ``greedy_pairing``     — Thm.-2 EC pairing (``datasche._train_generic``)

Each routes to the Pallas kernel on TPU and the (bit-identical) jnp reference
elsewhere. The choice follows the platform the program is lowered for
(``jax.lax.platform_dependent``), not the process's default backend, so a
program placed on the CPU of a TPU host runs the refs, and a compile for a
described TPU includes the kernels. ``impl=`` forces a backend and
``interpret=True`` runs the Pallas kernel in interpreter mode (the CPU parity
tests).

Batch-compatible: weights with leading batch axes — e.g. a (K, N, M) fleet
slice axis — are handled by vmapping the 2-D primitive, and calling the 2-D
form under an outer ``jax.vmap`` works as usual (the refs are pure jnp; the
Pallas calls rely on JAX's pallas_call batching rule).

Mask-aware (ragged fleets): optional ``cu_mask`` (..., N) / ``ec_mask``
(..., M) entity masks force the weight of any pair touching a padded entity
to the large negative ``MASKED_WEIGHT`` before dispatch, so neither backend
can ever select it. Masking happens here, once, so the Pallas kernels and
the jnp refs stay mask-free and bit-identical to each other.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.types import MASKED_WEIGHT as _MASKED
from repro.core.types import mask_pairs

from .kernel import (greedy_assignment_pallas, greedy_collection_pallas,
                     greedy_pairing_pallas)
from .ref import (greedy_assignment_ref, greedy_collection_ref,
                  greedy_pairing_ref, pairing_value_matrix)


def _check_impl(impl: str):
    if impl not in ("auto", "pallas", "ref"):
        raise ValueError(f"unknown matching impl {impl!r}; "
                         "expected 'auto', 'pallas' or 'ref'")


def _entity_masked(w, cu_mask, ec_mask):
    """Default missing masks to all-ones and force masked pairs of the
    (..., N, M) weights to MASKED_WEIGHT; no-op when neither mask is given."""
    if cu_mask is None and ec_mask is None:
        return w
    cu = cu_mask if cu_mask is not None else jnp.ones_like(w[..., :, 0])
    ec = ec_mask if ec_mask is not None else jnp.ones_like(w[..., 0, :])
    return mask_pairs(w, cu, ec)


def _dispatch(operands, impl, interpret, pallas_fn, ref_fn):
    """Shared dispatch tail of every op (masking already applied): check
    the impl, vmap away any leading batch axes (the LAST operand is the
    rank-2 reference — (N, M) weights or the (M, M) pair values), then route
    to the Pallas kernel or the jnp ref."""
    _check_impl(impl)
    if operands[-1].ndim > 2:
        return jax.vmap(lambda *ops: _dispatch(
            ops, impl, interpret, pallas_fn, ref_fn))(*operands)
    if impl == "pallas":
        return pallas_fn(*operands, interpret)
    if impl == "ref":
        return ref_fn(*operands)
    return jax.lax.platform_dependent(
        *operands, tpu=lambda *ops: pallas_fn(*ops, interpret), default=ref_fn)


def _assignment_pallas(w, interpret):
    return greedy_assignment_pallas(w, interpret=interpret)


def _collection_pallas(logw, interpret):
    alpha = greedy_collection_pallas(logw, interpret=interpret)
    # theta = 1/n_j from the column sums — the same arithmetic the ref
    # applies to its count vector, so the pair stays bit-exact.
    count = jnp.sum(alpha, axis=0)
    return alpha, alpha / jnp.maximum(count[None, :], 1.0)


def _pairing_pallas(solo, pair, interpret):
    return greedy_pairing_pallas(pairing_value_matrix(solo, pair),
                                 interpret=interpret)


def greedy_assignment(w, cu_mask: Optional[jax.Array] = None,
                      ec_mask: Optional[jax.Array] = None,
                      impl: str = "auto", interpret: bool = False):
    """Plain-P1 assignment: w (..., N, M) -> alpha (..., N, M) in {0,1} with
    at most one EC per CU and one CU per EC, by descending weight."""
    w = _entity_masked(w, cu_mask, ec_mask)
    return _dispatch((w,), impl, interpret, _assignment_pallas,
                     greedy_assignment_ref)


def greedy_collection(logw, cu_mask: Optional[jax.Array] = None,
                      ec_mask: Optional[jax.Array] = None,
                      impl: str = "auto", interpret: bool = False):
    """Skew-aware P1' collection: logw (..., N, M) log-weights -> (alpha,
    theta), both (..., N, M); theta = 1/n_j on the selected connections.

    Masked entities are forced to MASKED_WEIGHT before dispatch (non-finite
    inputs are sanitized the same way by both backends), so a padded pair can
    never be connected."""
    logw = _entity_masked(logw, cu_mask, ec_mask)
    return _dispatch((logw,), impl, interpret, _collection_pallas,
                     greedy_collection_ref)


def greedy_pairing(solo, pair, ec_mask: Optional[jax.Array] = None,
                   impl: str = "auto", interpret: bool = False):
    """Thm.-2 EC pairing: solo (..., M) and pair (..., M, M) values -> the
    symmetric match matrix (..., M, M); match[j,j]=1 solo, match[j,k]=1
    paired.

    A masked EC gets MASKED_WEIGHT solo and pair values, so it can neither
    train alone nor shadow a real EC's solo option through a (real, padded)
    pair."""
    if ec_mask is not None:
        solo = jnp.where(ec_mask > 0, solo, jnp.full_like(solo, _MASKED))
        pair = mask_pairs(pair, ec_mask, ec_mask)
    return _dispatch((solo, pair), impl, interpret, _pairing_pallas,
                     greedy_pairing_ref)
