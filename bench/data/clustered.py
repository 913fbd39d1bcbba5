"""Clustered Gaussian mixture: stored rows and a fresh test set drawn from
the same cluster centres, made on the device in one jitted call.

Parameters (the configuration's ``data`` block):
    clusters  number of centres
    spread    per-dimension standard deviation around a centre
    centres   "uniform" (centres uniform in [0, 1]^dims) or "sphere"
              (centres and points normalised to unit length, for angular
              data sets searched by inner product)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("rows", "dims", "queries",
                                             "clusters", "spread",
                                             "centres"))
def generate(key, *, rows: int, dims: int, queries: int, clusters: int,
             spread: float, centres: str = "uniform"):
    """(rows, dims) stored rows and (queries, dims) test queries, f32."""
    kc, ka, kn, kqa, kqn = jax.random.split(key, 5)
    if centres == "uniform":
        c = jax.random.uniform(kc, (clusters, dims))
    elif centres == "sphere":
        c = jax.random.normal(kc, (clusters, dims))
        c = c / jnp.linalg.norm(c, axis=-1, keepdims=True)
    else:
        raise ValueError(f"centres must be 'uniform' or 'sphere', "
                         f"not {centres!r}")

    def draw(ka, kn, n):
        x = c[jax.random.randint(ka, (n,), 0, clusters)]
        x = x + spread * jax.random.normal(kn, (n, dims))
        if centres == "sphere":
            x = x / jnp.linalg.norm(x, axis=-1, keepdims=True)
        return x

    return draw(ka, kn, rows), draw(kqa, kqn, queries)
