"""Point sets made on the device from a seed.

``mixture`` is the benchmark's own copy of the made-up point distribution
the program ships as a stand-in for descriptors
(``repro.data.pipeline.feature_mixture``): Gaussian clusters whose centres
lie near a random low-dimensional subspace of R^d, each point its centre
plus isotropic noise in all d dimensions. Here it is one jitted call on the device, not a host loop,
so a run's set-up does not pay for it point by point. Each point draws its
cluster uniformly, which is the program's multinomial sizing followed by
its shuffle, in distribution.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("n", "d", "clusters", "sub"))
def mixture(key, *, n: int, d: int, clusters: int, sub: int,
            spread: float, scale: float) -> jax.Array:
    """(n, d) float32 points: ``clusters`` centres ``scale * c @ B`` with
    ``c`` standard normal in ``sub`` dimensions and ``B`` a random
    ``(sub, d)`` basis over ``sqrt(sub)``, each point its centre plus
    ``spread`` times standard normal noise."""
    kb, kc, kl, kn = jax.random.split(key, 4)
    basis = jax.random.normal(kb, (sub, d), jnp.float32) / jnp.sqrt(sub)
    centres = jnp.dot(jax.random.normal(kc, (clusters, sub), jnp.float32),
                      basis, precision=jax.lax.Precision.HIGHEST) * scale
    labels = jax.random.randint(kl, (n,), 0, clusters)
    noise = jax.random.normal(kn, (n, d), jnp.float32)
    return centres[labels] + spread * noise


def points(config: dict, member: int = 0) -> jax.Array:
    """Point set ``member`` of a configuration, from the configuration's
    own data seed. Every run seed gets the same points in the same order:
    the plan's tile shapes, and so the work, depend on both (the ELL width
    moved by 2 % between shuffles of one set), so the run seed draws only
    what a user would vary: charges, samples, the order of builds."""
    base = jax.random.fold_in(jax.random.PRNGKey(config["data_seed"]),
                              member)
    return mixture(base, **generator_args(config))


def generator_args(config: dict) -> dict:
    """The keyword arguments of :func:`mixture` a configuration states."""
    g = config["generator"]
    return {"n": config["n"], "d": config["d"], "clusters": g["clusters"],
            "sub": g["subspace"], "spread": g["spread"], "scale": g["scale"]}
