"""Where the program runs: the rules that keep it on the device it was given.

Three rules live here and nowhere else:

  * ``pallas_call`` — every Pallas kernel of the repo is built through it.
    The kernel is staged for both platforms and the choice is made when the
    program is lowered (``lax.platform_dependent``): lowered for the CPU it
    runs in interpret mode, lowered for anything else it is compiled by
    Mosaic.  No caller can ask for interpret mode, so a kernel lowered for
    the TPU can never be interpreted there in silence.
  * ``forbid_child_processes`` — a chip belongs to one process.  A parent
    that has touched JAX on an accelerator holds it, and a spawned child
    that needs it then fails or hangs; process pools therefore run only on
    a CPU host and refuse with a clear error anywhere else.
  * ``enable_compile_cache`` — JAX's persistent compilation cache, at
    ``$JAX_COMPILATION_CACHE_DIR`` when that is set, else at a fixed
    directory inside the checkout (the path is part of the cache key, so it
    never moves).
"""
from __future__ import annotations

import os
import pathlib

import jax
from jax.experimental import pallas as pl

REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]
CACHE_DIR = REPO_ROOT / ".jax_cache"


def pallas_call(kernel, **kwargs):
    """``pl.pallas_call`` compiled on the chip, interpreted only on the CPU.

    Same arguments as ``pl.pallas_call`` minus ``interpret``; returns the
    callable that takes the kernel operands."""
    compiled = pl.pallas_call(kernel, **kwargs)
    interpreted = pl.pallas_call(kernel, interpret=True, **kwargs)

    def call(*args):
        return jax.lax.platform_dependent(*args, cpu=interpreted,
                                          default=compiled)

    return call


def forbid_child_processes(what: str) -> None:
    """Raise unless this process runs JAX on the CPU.

    ``what`` names the pool or transport for the error message."""
    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"{what} spawns worker processes, which runs only on a CPU host: "
            f"this process holds the {backend} device, and a child that "
            f"needs it would fail or hang. Run it in one process, or under "
            f"JAX_PLATFORMS=cpu.")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and nothing
    is set here."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
