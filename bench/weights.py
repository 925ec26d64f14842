"""Seeded leaves that every architecture's weights are made of.

The benchmark makes the weights, on the device, in one jitted call, in the
type they are served in (the configuration's dtype), so that the plain
reference can use the very same values without taking anything the
program made. Each architecture module (``bench/archs/<name>.py``) lays
them out in its own canonical form and re-nests the same arrays into the
program's parameter tree; the driver checks that tree against the
program's own. The leaves here are shared: one seeded distribution, made a
layer at a time, and the embedding made in row blocks.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

EMBED_BLOCKS = 8     # the embedding is made in this many row blocks


def root_key(seed: int, salt: int = 0):
    """A PRNG key from a seed of up to 64 bits (seeds may pass 2**32)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & (2**64 - 1)), salt)


def leaf(key, shape, std, dtype, mean=0.0, layers=None, heavy=False):
    """``mean + std·z`` in ``dtype``, one layer at a time when ``layers`` is
    given (so the float32 temporaries stay one layer's size and only the
    stored type is stacked), z standard normal or,
    with ``heavy``, Student-t with 4 degrees of freedom scaled to unit
    variance: z·(E₁ + E₂)^(-1/2) with E₁, E₂ exponential (χ²₄ = 2·(E₁ + E₂),
    and t₄ = z·(χ²₄/4)^(-1/2) has variance 2)."""
    def one(k):
        kz, k1, k2 = jax.random.split(k, 3)
        z = jax.random.normal(kz, shape, jnp.float32)
        if heavy:
            chi = jax.random.exponential(k1, shape, jnp.float32) \
                + jax.random.exponential(k2, shape, jnp.float32)
            z = z * jax.lax.rsqrt(chi)
        return (mean + std * z).astype(dtype)
    if layers is None:
        return one(key)
    return jax.lax.map(lambda l: one(jax.random.fold_in(key, l)),
                       jnp.arange(layers))


def embedding(key, shape, dtype):
    """A ``[vocab, d]`` embedding N(0, 0.02²), made in ``EMBED_BLOCKS`` row
    blocks (one block where the rows do not divide) so that its float32
    temporaries stay a block's size."""
    blocks = EMBED_BLOCKS if shape[0] % EMBED_BLOCKS == 0 else 1
    return leaf(key, (shape[0] // blocks, shape[1]), 0.02, dtype, 0.0,
                blocks).reshape(shape)
