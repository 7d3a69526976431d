"""States made on the device from the run's seed."""
from __future__ import annotations

import numpy as np

import jax


def seed_key(seed: int, salt: int = 0):
    """A JAX key from any whole-number seed (wider than 32 bits too)."""
    words = np.random.SeedSequence([int(seed), int(salt)]).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32))


def make_state(seed: int, shape, dtype: str, sharding, salt: int = 0):
    """A standard-normal state made on the device(s), in one jitted call."""
    fn = jax.jit(lambda k: jax.random.normal(k, tuple(shape), dtype),
                 out_shardings=sharding)
    return fn(seed_key(seed, salt))
