"""Pallas TPU kernels for the scheduler's three greedy matching hot loops.

These are the paper's scalability hot spot (Sec. III-D): the skew-aware
collection (P1'), the plain-P1 assignment (L-DS step 3 / NO-SDC) and the
Thm.-2 EC pairing all run EVERY slot of the production path, and each is a
sequential argmax-and-mask scan — awkward on accelerators because every
iteration is a full matrix reduction followed by a data-dependent update.

Shared TPU design (all three kernels): one grid step per selected pair. The
weight matrix lives in VMEM for the whole grid; the loop-carried state —
per-CU "assigned"/"taken" masks as (N, 1) columns, per-EC counts and free
masks as (1, M) rows, the early-stop flag as a (1, 1) tile — lives in VMEM
scratch that persists across grid steps. Each step is a masked argmax plus
masked whole-ref updates, all vector ops: the argmax is a max reduction and
an iota/min reduction that picks the first row-major occurrence (the
tie-break of ``jnp.argmax``), and every "store at the selected index" is a
``where`` against the resulting one-hot tile. Mosaic lowers no dynamic
indexing of vector values and no scalar stores to VMEM, so nothing here
indexes by a computed value. The collection kernel additionally computes
the marginal crowding penalty (n+1)log(n+1) - n log n from the on-chip
counts.

All kernels are bit-exact against the jnp references in ``ref.py``
(tests/test_matching_kernels.py runs them in interpret mode on CPU); the
argmax order, penalty arithmetic and early-stop semantics mirror the refs
operation for operation. VMEM limit: the (N, M) tile, its lane axis padded
to 128, must fit — see README.md.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import _marginal_penalty

_NEG = -1e30


def _flat_index(shape, transpose: bool = False):
    """Row-major flat index of every element of a 2-D tile, as exact f32
    (tiles hold far fewer than 2**24 elements). ``transpose`` gives the
    index of the mirrored element (j, i) at (i, j)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    if transpose:
        rows, cols = cols, rows
    return (rows * shape[1] + cols).astype(jnp.float32)


def _max2(x):
    return jnp.max(jnp.max(x, axis=1, keepdims=True), axis=0, keepdims=True)


def _argmax(g):
    """(best, first): the max of the 2-D tile ``g`` and the row-major flat
    index of its first occurrence, both (1, 1). A NaN max matches nothing
    (first = inf), and every caller then takes nothing, as the refs do."""
    best = _max2(g)
    cand = jnp.where(g == best, _flat_index(g.shape), jnp.inf)
    return best, -_max2(-cand)


def _rows(hit):
    """(R, C) one-hot f32 tile -> (R, 1) mask of the rows it touches."""
    return jnp.max(hit, axis=1, keepdims=True)


def _cols(hit):
    """(R, C) one-hot f32 tile -> (1, C) mask of the columns it touches."""
    return jnp.max(hit, axis=0, keepdims=True)


def _assignment_kernel(w_ref, alpha_ref, cu_taken_ref, ec_taken_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        cu_taken_ref[...] = jnp.zeros_like(cu_taken_ref)
        ec_taken_ref[...] = jnp.zeros_like(ec_taken_ref)
        alpha_ref[...] = jnp.zeros_like(alpha_ref)

    w = w_ref[...]  # (N, M) in VMEM
    free = (cu_taken_ref[...] == 0.0) & (ec_taken_ref[...] == 0.0)
    g = jnp.where(free & (w > 0), w, _NEG)
    best, first = _argmax(g)
    hit = jnp.where((_flat_index(g.shape) == first) & (best > 0.0), 1.0, 0.0)
    cu_taken_ref[...] = jnp.maximum(cu_taken_ref[...], _rows(hit))
    ec_taken_ref[...] = jnp.maximum(ec_taken_ref[...], _cols(hit))
    alpha_ref[...] = jnp.maximum(alpha_ref[...], hit)


def greedy_assignment_pallas(w: jax.Array, interpret: bool = False) -> jax.Array:
    """Plain-P1 greedy assignment: w (N, M) -> alpha (N, M) in {0,1} with
    at most one EC per CU and one CU per EC, selected by descending weight.
    Requires the (N, M) tile to fit VMEM."""
    n_cu, n_ec = w.shape
    return pl.pallas_call(
        _assignment_kernel,
        grid=(n_ec,),  # one selected pair per step
        in_specs=[pl.BlockSpec((n_cu, n_ec), lambda it: (0, 0))],
        out_specs=pl.BlockSpec((n_cu, n_ec), lambda it: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_cu, n_ec), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n_cu, 1), jnp.float32),
                        pltpu.VMEM((1, n_ec), jnp.float32)],
        interpret=interpret,
    )(w)


def _collection_kernel(w_ref, alpha_ref, assigned_ref, count_ref, done_ref):
    """Skew-aware P1' greedy: one connection per grid step.

    Scratch (persists across grid steps): per-CU assigned mask (N, 1),
    per-EC connection count (1, M) (f32 — exact for the small integer
    counts) and the early-stop flag (1, 1). Mirrors
    ``ref.greedy_collection_ref`` exactly: sanitize -> marginal penalty from
    counts -> mask assigned rows -> argmax -> take iff gain > 0 and not yet
    stopped.
    """
    @pl.when(pl.program_id(0) == 0)
    def _init():
        assigned_ref[...] = jnp.zeros_like(assigned_ref)
        count_ref[...] = jnp.zeros_like(count_ref)
        done_ref[...] = jnp.zeros_like(done_ref)
        alpha_ref[...] = jnp.zeros_like(alpha_ref)

    w = w_ref[...]  # (N, M) in VMEM
    w = jnp.where(jnp.abs(w) < jnp.inf, w, _NEG)  # isfinite: NaN/inf -> _NEG
    # Marginal crowding penalty of the (n+1)-th CU, from the on-chip counts.
    gain = w - _marginal_penalty(count_ref[...])
    gain = jnp.where(assigned_ref[...] > 0, _NEG, gain)
    best, first = _argmax(gain)
    take = (best > 0.0) & (done_ref[...] == 0.0)  # (1, 1)
    hit = jnp.where((_flat_index(gain.shape) == first) & take, 1.0, 0.0)
    assigned_ref[...] = jnp.maximum(assigned_ref[...], _rows(hit))
    count_ref[...] = count_ref[...] + _cols(hit)
    alpha_ref[...] = jnp.maximum(alpha_ref[...], hit)
    done_ref[...] = jnp.where(take, done_ref[...], 1.0)


def greedy_collection_pallas(logw: jax.Array, interpret: bool = False) -> jax.Array:
    """Skew-aware P1' greedy collection: logw (N, M) -> alpha (N, M) in {0,1}
    with at most one EC per CU; ECs accept multiple CUs, each new connection
    paying the marginal crowding penalty (n+1)log(n+1) - n log n. Returns
    alpha only; theta = alpha / count follows from the column sums (the
    dispatch layer computes it, matching the ref bit-exactly). Requires the
    (N, M) tile to fit VMEM."""
    n_cu, n_ec = logw.shape
    return pl.pallas_call(
        _collection_kernel,
        grid=(n_cu,),  # at most one connection per CU
        in_specs=[pl.BlockSpec((n_cu, n_ec), lambda it: (0, 0))],
        out_specs=pl.BlockSpec((n_cu, n_ec), lambda it: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_cu, n_ec), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n_cu, 1), jnp.float32),
                        pltpu.VMEM((1, n_ec), jnp.float32),
                        pltpu.VMEM((1, 1), jnp.float32)],
        interpret=interpret,
    )(logw)


def _pairing_kernel(w_ref, match_ref, free_col_ref, free_row_ref, done_ref):
    """Thm.-2 EC pairing greedy: one matched pair (or solo) per grid step.

    Scratch: the free-EC mask twice, as a (M, 1) column and a (1, M) row (a
    transpose between the two would be a relayout), plus the early-stop
    flag (1, 1), persisting across grid steps. The diagonal of w carries
    the solo value, off-diagonals the pair value
    (``ref.pairing_value_matrix``); a diagonal argmax hit matches an EC with
    itself (solo training).
    """
    @pl.when(pl.program_id(0) == 0)
    def _init():
        free_col_ref[...] = jnp.ones_like(free_col_ref)
        free_row_ref[...] = jnp.ones_like(free_row_ref)
        done_ref[...] = jnp.zeros_like(done_ref)
        match_ref[...] = jnp.zeros_like(match_ref)

    w = w_ref[...]  # (M, M) in VMEM
    g = jnp.where((free_col_ref[...] > 0) & (free_row_ref[...] > 0), w, _NEG)
    best, first = _argmax(g)
    take = (best > 0.0) & (done_ref[...] == 0.0)  # (1, 1)
    # (j, k) and its mirror (k, j): both ECs leave the free set.
    both = ((_flat_index(g.shape) == first)
            | (_flat_index(g.shape, transpose=True) == first))
    hit = jnp.where(both & take, 1.0, 0.0)
    free_col_ref[...] = free_col_ref[...] * (1.0 - _rows(hit))
    free_row_ref[...] = free_row_ref[...] * (1.0 - _cols(hit))
    match_ref[...] = jnp.maximum(match_ref[...], hit)
    done_ref[...] = jnp.where(take, done_ref[...], 1.0)


def greedy_pairing_pallas(w: jax.Array, interpret: bool = False) -> jax.Array:
    """Thm.-2 greedy EC pairing over the combined solo/pair value matrix
    w (M, M) (diag = solo value, off-diag = pair value; build it with
    ``ref.pairing_value_matrix``). Returns the symmetric match matrix:
    match[j,j] = 1 -> solo, match[j,k] = 1 -> paired."""
    n_ec = w.shape[0]
    return pl.pallas_call(
        _pairing_kernel,
        grid=(n_ec,),  # each step matches >= 1 EC (or stops)
        in_specs=[pl.BlockSpec((n_ec, n_ec), lambda it: (0, 0))],
        out_specs=pl.BlockSpec((n_ec, n_ec), lambda it: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_ec, n_ec), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n_ec, 1), jnp.float32),
                        pltpu.VMEM((1, n_ec), jnp.float32),
                        pltpu.VMEM((1, 1), jnp.float32)],
        interpret=interpret,
    )(w)
