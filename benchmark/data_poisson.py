"""The fit data set with a count response, made on the device.

Every feature row is ``data._glmix``'s: the same keys split the same way
from ``data.BASE_SEED``, so the fixed effect's X, every random-effect shard
and the uniform id column are ``fit_uniform``'s to the bit, and the seed
renames the entities and keeps the rows. New are the truth and the labels:

- an intercept (``truth["intercept"]``) and a SPARSE fixed effect:
  ``truth["support"]`` of the features carry ±``truth["norm"]``/√support
  (which ones, and the signs, from the key ``data._glmix`` draws its dense
  fixed effect from), the rest are exactly zero, which is what the
  configuration's elastic net is there to find;
- per-entity effects of scale ``truth["re_scale"]`` on every column of each
  random-effect shard (from the key ``data._glmix`` draws its effects from);
- y ~ Poisson(exp(log-rate)), float32 counts, from the key that gives
  ``data._glmix`` the uniforms behind its labels.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from benchmark import data


def fixed_truth(key, d_fix: int, intercept: float, support: int, norm: float):
    """(d_fix,) float32: the intercept in column 0, ``support`` of the other
    columns at ±norm/√support, zeros elsewhere."""
    k_which, k_sign = jax.random.split(key)
    which = jax.random.permutation(k_which, d_fix - 1)[:support] + 1
    signs = jnp.where(jax.random.bernoulli(k_sign, 0.5, (support,)), 1.0, -1.0)
    w = jnp.zeros((d_fix,), jnp.float32).at[0].set(intercept)
    return w.at[which].set(signs.astype(jnp.float32) * (norm / support ** 0.5))


@functools.partial(jax.jit, static_argnames=("n", "d_fix", "re", "truth"))
def _glmix(base, key, n: int, d_fix: int, re: Tuple, truth: Tuple):
    intercept, support, norm, re_scale = truth
    k_fix, k_wfix, k_lab, k_re = jax.random.split(base, 4)
    xf = data._features(k_fix, n, d_fix)
    log_rate = jnp.sum(xf * fixed_truth(k_wfix, d_fix, intercept, support, norm),
                       axis=-1)
    shards, ids = {}, {}
    for i, (name, d_re, entities) in enumerate(re):
        k_x, k_id, k_w = jax.random.split(jax.random.fold_in(k_re, i), 3)
        xr = data._features(k_x, n, d_re)
        eid = jax.random.randint(k_id, (n,), 0, entities, jnp.int32)
        w_re = re_scale * jax.random.normal(k_w, (entities, d_re), jnp.float32)
        log_rate = log_rate + jnp.sum(xr * w_re[eid], axis=-1)
        names = jax.random.permutation(jax.random.fold_in(key, i), entities)
        shards[name], ids[name] = xr, names.astype(jnp.int32)[eid]
    y = jax.random.poisson(k_lab, jnp.exp(log_rate)).astype(jnp.float32)
    return xf, shards, ids, y


def make_glmix(seed: int, n: int, d_fix: int, re: Dict[str, Tuple[int, int]],
               truth: dict):
    """``(xf, {name: xr}, {name: ids}, y)`` on the default device, as
    ``data.make_glmix`` gives them, with Poisson counts for labels. ``truth``
    is the traffic file's: ``intercept``, ``support``, ``norm``, ``re_scale``."""
    spec = tuple((name, int(d), int(e)) for name, (d, e) in re.items())
    gen = (float(truth["intercept"]), int(truth["support"]),
           float(truth["norm"]), float(truth["re_scale"]))
    if not 0 < gen[1] < int(d_fix):
        raise ValueError(f"truth.support {gen[1]} must lie in (0, {int(d_fix) - 1}]")
    return _glmix(data.root_key(data.BASE_SEED), data.root_key(seed),
                  n=int(n), d_fix=int(d_fix), re=spec, truth=gen)
