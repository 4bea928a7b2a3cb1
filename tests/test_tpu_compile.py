"""Compile-only guards: the main path's Pallas kernels must lower for a TPU.

Each test compiles one kernel at a width the system runs, for a TPU v5e
chip that is described, not attached, and asserts that the compiled program
holds the Mosaic kernel (``tpu_custom_call``). Nothing runs, so this says
nothing about results or times; the interpret-mode parity tests
(tests/test_matching_kernels.py, tests/test_kernels.py) cover results.

The topology is described inside a module-scoped fixture, never at import:
loading the TPU compiler takes a process-wide lock, so only the worker that
runs this file may do it.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import AttnSpec
from repro.kernels.matching import ops


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it.
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args):
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


MATCHERS = {
    "collection": lambda w: ops.greedy_collection(w, impl="pallas"),
    "assignment": lambda w: ops.greedy_assignment(w, impl="pallas"),
}


@pytest.mark.parametrize("shape", [(20, 5), (1024, 16)], ids=str)
@pytest.mark.parametrize("op", ["collection", "assignment", "pairing"])
def test_matcher_lowers(one_chip, op, shape):
    """Paper simulation scale (20 x 5) and a metro slice (1024 x 16)."""
    if op == "pairing":
        m = shape[1]
        _assert_kernel(lambda s, p: ops.greedy_pairing(s, p, impl="pallas"),
                       _shape(one_chip, (m,)), _shape(one_chip, (m, m)))
    else:
        _assert_kernel(MATCHERS[op], _shape(one_chip, shape))


def test_fleet_batched_collection_lowers(one_chip):
    """The fleet vmaps the matchers over K slices: the batched pallas_call."""
    _assert_kernel(MATCHERS["collection"], _shape(one_chip, (8, 1024, 16)))


WHISPER_BASE_ATTN = {  # (B, Sq, Skv, H, hd, spec) at enc_ctx 1500, 448 tokens
    "encoder": (4, 1500, 1500, 8, 64, AttnSpec(causal=False)),
    "decoder": (4, 448, 448, 8, 64, AttnSpec(causal=True)),
    "cross": (4, 448, 1500, 8, 64, AttnSpec(causal=False)),
}


@pytest.mark.parametrize("which", sorted(WHISPER_BASE_ATTN))
def test_flash_attention_lowers(one_chip, which):
    b, sq, skv, h, hd, spec = WHISPER_BASE_ATTN[which]
    _assert_kernel(
        lambda q, k, v, qp, kp, ok: flash_attention_pallas(
            q, k, v, qp, kp, spec, kv_valid=ok),
        _shape(one_chip, (b, sq, h, hd), jnp.bfloat16),
        _shape(one_chip, (b, skv, h, hd), jnp.bfloat16),
        _shape(one_chip, (b, skv, h, hd), jnp.bfloat16),
        _shape(one_chip, (b, sq), jnp.int32),
        _shape(one_chip, (b, skv), jnp.int32),
        _shape(one_chip, (b, skv), jnp.bool_))
