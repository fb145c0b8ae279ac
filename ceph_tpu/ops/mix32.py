"""Shared u32 splitmix-style mixer, numpy and jnp twins.

Benchmarks generate data ON DEVICE (loading it counts as set-up, so it
is made where it is used) and pin correctness against the native
oracle on a HOST mirror of the same bytes — which only works if the
device generator and the host mirror compute bit-identical streams.
Keeping both twins in one module removes the four-copy drift hazard the
round-4 review flagged.
"""

from __future__ import annotations

import numpy as np

_C1, _C2, _C3 = 0x9E3779B9, 0x85EBCA6B, 0xC2B2AE35


def mix_np(i: np.ndarray) -> np.ndarray:
    """u32 ndarray -> mixed u32 ndarray (wrapping arithmetic)."""
    i = i.astype(np.uint32, copy=False)
    z = (i ^ np.uint32(_C1)) * np.uint32(_C2)
    z = (z ^ (z >> np.uint32(13))) * np.uint32(_C3)
    return z ^ (z >> np.uint32(16))


def mix_jnp(i):
    """jnp u32 array -> mixed u32 array; EXACTLY mirrors mix_np."""
    import jax.numpy as jnp

    z = (i ^ jnp.uint32(_C1)) * jnp.uint32(_C2)
    z = (z ^ (z >> jnp.uint32(13))) * jnp.uint32(_C3)
    return z ^ (z >> jnp.uint32(16))
