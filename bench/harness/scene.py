"""Procedural 3DGS scenes made from a seed, in one jitted call on the device.

A copy of the program's ``structured_scene`` recipe (Gaussians on a sphere,
a plane and a torus with smooth color fields), kept here so the benchmark,
not the program, owns the weights both the program and the reference see.
The scene is a dict of raw (pre-activation) parameters:

  means [N,3], log_scales [N,3], quats [N,4] (w,x,y,z), opacity_logit [N],
  sh_dc [N,3], sh_rest [N,3,3] (degree-1 coefficients, basis x RGB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

SH_C0 = 0.28209479177387814


def _sphere(key, n, center, radius, base):
    k1, k2 = jax.random.split(key)
    d = jax.random.normal(k1, (n, 3))
    d = d / (jnp.linalg.norm(d, axis=-1, keepdims=True) + 1e-9)
    return jnp.asarray(center) + radius * d, jnp.asarray(base) + 0.35 * d, k2


def _plane(key, n, origin, u, v, base):
    k1, k2 = jax.random.split(key)
    ab = jax.random.uniform(k1, (n, 2), minval=-1.0, maxval=1.0)
    means = (jnp.asarray(origin) + ab[:, :1] * jnp.asarray(u)
             + ab[:, 1:2] * jnp.asarray(v))
    col = jnp.asarray(base) + 0.25 * jnp.concatenate(
        [jnp.sin(3 * ab), jnp.cos(2 * ab[:, :1] + ab[:, 1:2])], axis=-1)
    return means, col, k2


def _torus(key, n, center, r_major, r_minor, base):
    k1, k2, k3 = jax.random.split(key, 3)
    th = jax.random.uniform(k1, (n,), minval=0, maxval=2 * jnp.pi)
    ph = jax.random.uniform(k2, (n,), minval=0, maxval=2 * jnp.pi)
    x = (r_major + r_minor * jnp.cos(ph)) * jnp.cos(th)
    y = r_minor * jnp.sin(ph)
    z = (r_major + r_minor * jnp.cos(ph)) * jnp.sin(th)
    means = jnp.asarray(center) + jnp.stack([x, y, z], axis=-1)
    col = jnp.asarray(base) + 0.3 * jnp.stack(
        [jnp.cos(th), jnp.sin(2 * ph), jnp.sin(th + ph)], axis=-1)
    return means, col, k3


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, n):
    n1 = n2 = n // 3
    n3 = n - n1 - n2
    m1, c1, key = _sphere(key, n1, (0.0, 0.1, 0.0), 0.45, (0.7, 0.3, 0.25))
    m2, c2, key = _plane(key, n2, (0.0, -0.5, 0.0), (1.2, 0.0, 0.0),
                         (0.0, 0.0, 1.2), (0.25, 0.55, 0.3))
    m3, c3, key = _torus(key, n3, (0.0, 0.35, 0.0), 0.7, 0.12,
                         (0.3, 0.35, 0.75))
    means = jnp.concatenate([m1, m2, m3])
    colors = jnp.clip(jnp.concatenate([c1, c2, c3]), 0.02, 0.98)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    log_scales = jnp.log(jax.random.uniform(k1, (n, 3), minval=0.015,
                                            maxval=0.06))
    quats = jax.random.normal(k2, (n, 4)).at[:, 0].add(3.0)
    opacity_logit = jax.random.uniform(k3, (n,), minval=0.5, maxval=3.0)
    sh_dc = (colors - 0.5) / SH_C0
    sh_rest = 0.08 * jax.random.normal(k4, (n, 3, 3))
    return dict(means=means, log_scales=log_scales, quats=quats,
                opacity_logit=opacity_logit, sh_dc=sh_dc, sh_rest=sh_rest)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, also one wider than 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def make_scene(seed: int, num_gaussians: int) -> dict:
    """The float32 scene of ``num_gaussians`` Gaussians for ``seed``."""
    return _make(seed_key(seed), int(num_gaussians))
