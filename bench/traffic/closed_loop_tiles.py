"""Closed-loop frame traffic: one client sends a batch of tiles, waits for
the detection maps, and sends the next.

Reads a mix file (``bench/traffic/<mix>.json``) of parameters:

    tile            side of a square RGB tile in pixels
    tiles_per_scene tiles a scene is cut into (a square grid)
    scenes          scenes in the pool made during set-up

The pool is made on the device from the seed.  Scenes are sent in a cycle
whose order is drawn from the seed, so each is sent equally often.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def make_pool(mix: dict, key) -> jax.Array:
    """(scenes, tiles_per_scene, tile, tile, 3) float32 in [0, 1), cut from
    scenes of side ``sqrt(tiles_per_scene) * tile`` made on the device."""
    g = int(math.isqrt(mix["tiles_per_scene"]))
    if g * g != mix["tiles_per_scene"]:
        raise ValueError("tiles_per_scene must be a square")
    t, s = mix["tile"], mix["scenes"]

    @jax.jit
    def make(key):
        scenes = jax.random.uniform(key, (s, g * t, g * t, 3), jnp.float32)
        tiles = scenes.reshape(s, g, t, g, t, 3).transpose(0, 1, 3, 2, 4, 5)
        return tiles.reshape(s, g * g, t, t, 3)

    return make(key)


def order(mix: dict, rng: np.random.Generator) -> np.ndarray:
    """One cycle of scene indices in an order drawn from the seed; batch
    ``n`` sends scene ``order[n % scenes]``."""
    return rng.permutation(mix["scenes"])
