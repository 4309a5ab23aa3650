"""Seeded inputs: clustered 3-D point sets, made on the device.

Points lie in the unit box: a share in Gaussian clusters, every
``uniform_every``-th point uniform; weights uniform in [weight_lo,
weight_hi). The cluster centres come from the configuration's
``centres_seed``, the same for every run: a run's seed draws the points
around them, so every seed gives the same density and the same work.
One jitted program per shape. Later batches (``batch > 0``) draw new
points around the same centres.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def seed_key(seed: int, *salt: int) -> jax.Array:
    """A PRNG key from any non-negative seed (also past 32 bits)."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)), seed >> 31)
    for s in salt:
        key = jax.random.fold_in(key, s)
    return key


@functools.partial(jax.jit, static_argnames=("n", "d", "clusters", "uniform_every"))
def _clustered(centre_key, key, n, d, clusters, uniform_every, sigma, w_lo, w_hi):
    centres = jax.random.uniform(centre_key, (clusters, d), minval=0.1, maxval=0.9)
    k = jax.random.split(key, 4)
    which = jax.random.randint(k[0], (n,), 0, clusters)
    blob = centres[which] + sigma * jax.random.normal(k[1], (n, d))
    uniform = jax.random.uniform(k[2], (n, d))
    pts = jnp.where((jnp.arange(n) % uniform_every == 0)[:, None], uniform, blob)
    pts = jnp.clip(pts, 0.0, 1.0).astype(jnp.float32)
    w = jax.random.uniform(k[3], (n,), minval=w_lo, maxval=w_hi).astype(jnp.float32)
    return pts, w


def clustered_points(seed: int, n: int, cfg: dict, batch: int = 0):
    """(n, d) float32 points and (n,) float32 weights for batch ``batch``."""
    return _clustered(
        seed_key(cfg["centres_seed"], 0), seed_key(seed, 1, batch), n, cfg["dims"], cfg["clusters"],
        cfg["uniform_every"], cfg["cluster_sigma"], cfg["weight_lo"], cfg["weight_hi"],
    )
