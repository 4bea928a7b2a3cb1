"""Plain reference of one DS slot for a fleet of slices of different sizes.

Each slice is its own problem at its own (N, M): its slot is
``cocktail_slot.slot`` at that shape, from that slice's state. Nothing here
pads or masks; a slice's arrays have its true shape throughout. The slot is
jitted once per distinct slice configuration (five in the sweep).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from reference import cocktail_slot


def slice_config(config: dict, k: int) -> dict:
    """Slice ``k``'s configuration as ``cocktail_slot`` takes it: the shared
    ``slice`` constants with the slice's own ``n_cu``, ``n_ec`` and
    ``f_base``."""
    return {**config["slice"], **config["slices"][k]}


def init_states(config: dict, seeds) -> list[dict]:
    """Each slice's initial state at its true shape, from its seed."""
    return [cocktail_slot.init_state(slice_config(config, k), s)
            for k, s in enumerate(seeds)]


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def _slot(slice_items: tuple, s: dict, pair_iters: int, dt_name: str):
    """One slot of the slice whose configuration is ``dict(slice_items)``
    (static, so its constants are built once per slice, at trace time)."""
    c, dt = dict(slice_items), jnp.dtype(dt_name)
    p = cocktail_slot.slice_params(c, dt)
    return cocktail_slot.slot(p, s, c["n_cu"], c["n_ec"], pair_iters, dt)[:2]


def _items(c: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in c.items()))


def slots(config: dict, states: list[dict], dt_name: str = "float32"):
    """``[(next state, record)]``, one slot of each slice from its state,
    computed in ``dt_name``."""
    return jax.device_get([
        _slot(_items(slice_config(config, k)), s, config["pair_iters"], dt_name)
        for k, s in enumerate(states)])
