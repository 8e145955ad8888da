"""The fit data set with a continuous response on correlated features, made
on the device.

The random-effect shards and the uniform id column are ``fit_uniform``'s to
the bit: the same keys split the same way from ``data.BASE_SEED`` as
``data._glmix`` splits them, so the seed renames the entities and keeps the
rows. New are the fixed shard and the labels:

- the fixed shard keeps column 0 at 1 (the intercept) and gives columns
  1..d-1 unit variance and correlation ρ^|i−j|, an AR(1) structure: the
  standard-normal draws ``data._features`` makes, times Lᵀ with
  L = chol(ρ^|i−j|), one matrix product at ``Precision.HIGHEST``;
- y = x·w* + x_u·w_u[id] + ε: w* ~ N(0, 1/d) from the key ``data._glmix``
  draws its fixed effect from, per-entity effects of scale
  ``truth["re_scale"]`` from the key it draws its effects from, and
  ε ~ N(0, ``truth["noise"]``²) from the key behind its labels.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from benchmark import data


def ar1_mix(d_fix: int, rho: float):
    """(d_fix, d_fix) float32 M with x_raw·M the fixed shard: M[0, 0] = 1 for
    the intercept and Lᵀ on the feature columns, L = chol(ρ^|i−j|)."""
    k = jnp.arange(d_fix - 1)
    cov = jnp.float32(rho) ** jnp.abs(k[:, None] - k[None, :]).astype(jnp.float32)
    chol = jnp.linalg.cholesky(cov)
    return jnp.zeros((d_fix, d_fix), jnp.float32).at[0, 0].set(1.0).at[1:, 1:].set(
        chol.T)


@functools.partial(jax.jit, static_argnames=("n", "d_fix", "re", "truth"))
def _glmix(base, key, n: int, d_fix: int, re: Tuple, truth: Tuple):
    rho, re_scale, noise = truth
    k_fix, k_wfix, k_lab, k_re = jax.random.split(base, 4)
    xf = jnp.matmul(data._features(k_fix, n, d_fix), ar1_mix(d_fix, rho),
                    precision=jax.lax.Precision.HIGHEST)
    w_fix = jax.random.normal(k_wfix, (d_fix,), jnp.float32) / jnp.sqrt(
        jnp.float32(d_fix))
    mean = jnp.matmul(xf, w_fix, precision=jax.lax.Precision.HIGHEST)
    shards, ids = {}, {}
    for i, (name, d_re, entities) in enumerate(re):
        k_x, k_id, k_w = jax.random.split(jax.random.fold_in(k_re, i), 3)
        xr = data._features(k_x, n, d_re)
        eid = jax.random.randint(k_id, (n,), 0, entities, jnp.int32)
        w_re = re_scale * jax.random.normal(k_w, (entities, d_re), jnp.float32)
        mean = mean + jnp.sum(xr * w_re[eid], axis=-1)
        names = jax.random.permutation(jax.random.fold_in(key, i), entities)
        shards[name], ids[name] = xr, names.astype(jnp.int32)[eid]
    y = mean + noise * jax.random.normal(k_lab, (n,), jnp.float32)
    return xf, shards, ids, y


def make_glmix(seed: int, n: int, d_fix: int, re: Dict[str, Tuple[int, int]],
               truth: dict):
    """``(xf, {name: xr}, {name: ids}, y)`` on the default device, as
    ``data.make_glmix`` gives them, with AR(1) fixed features and a
    continuous response. ``truth`` is the traffic file's: ``rho``,
    ``re_scale``, ``noise``."""
    spec = tuple((name, int(d), int(e)) for name, (d, e) in re.items())
    gen = (float(truth["rho"]), float(truth["re_scale"]), float(truth["noise"]))
    if not 0.0 <= gen[0] < 1.0:
        raise ValueError(f"truth.rho {gen[0]} must lie in [0, 1)")
    return _glmix(data.root_key(data.BASE_SEED), data.root_key(seed),
                  n=int(n), d_fix=int(d_fix), re=spec, truth=gen)
