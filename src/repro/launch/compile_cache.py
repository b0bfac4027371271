"""JAX's persistent compilation cache for the command-line entry points.

A cold start on the chip spends most of its set-up compiling (tens of
seconds for one full-width paged decode step). The on-disk cache turns a
later process's compiles into reads, so its directory is the fixed
``<checkout>/.jax_cache`` (gitignored), never a temporary, per-process or
dated path.

An entry is found again only from the same checkout path when the
program holds a Pallas kernel: JAX strips source locations from the
module it hashes, but not from the serialized Mosaic body inside the
kernel's custom call, which names the kernel's source file. Programs
without a kernel (the parameter init) hit the cache from any path.

The cache is sound on the CPU backend of the installed jaxlib too: a
training run resumed from a checkpoint with every executable read back
from the cache reproduces the uncached run's losses exactly. So the same
rule holds on every backend.
"""

from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is: JAX reads
    the variable itself and no other directory is set here. Otherwise the
    cache lives in :data:`DEFAULT_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    DEFAULT_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
