"""The fit data set with entity ids drawn from a law, made on the device.

Everything but the id column is ``data._glmix``'s: the same keys split the
same way from ``data.BASE_SEED``, so the feature rows (fixed effect and
every random-effect shard), the generating coefficients and the uniforms
behind the labels are the ones ``fit_uniform`` sees. Only which entity a row
belongs to follows the traffic file's ``law`` (the labels follow from it:
a row's logit holds its entity's effect). The seed renames the entities and
keeps rows and counts, as in the other fit cells.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import data


def zipf_cdf(entities: int, exponent: float) -> np.ndarray:
    """Cumulative p(rank r) ∝ r^-exponent over ranks 1..entities, float64."""
    p = np.arange(1, entities + 1, dtype=np.float64) ** -float(exponent)
    return np.cumsum(p / p.sum())


def _draw_ids(key, n: int, entities: int, law: Tuple):
    """(n,) int32 entity ranks (0 = the most frequent), i.i.d."""
    kind, exponent = law
    if kind == "uniform":
        return jax.random.randint(key, (n,), 0, entities, jnp.int32)
    if kind != "zipf":
        raise ValueError(f"no entity law {kind!r}; data_ragged has uniform, zipf")
    cdf = jnp.asarray(zipf_cdf(entities, exponent), jnp.float32)
    u = jax.random.uniform(key, (n,), jnp.float32)
    return jnp.minimum(jnp.searchsorted(cdf, u, side="right"),
                       entities - 1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("n", "d_fix", "re"))
def _glmix(base, key, n: int, d_fix: int, re: Tuple):
    k_fix, k_wfix, k_lab, k_re = jax.random.split(base, 4)
    xf = data._features(k_fix, n, d_fix)
    w_fix = jax.random.normal(k_wfix, (d_fix,), jnp.float32) / jnp.sqrt(
        jnp.float32(d_fix))
    logits = jnp.sum(xf * w_fix, axis=-1)
    shards, ids = {}, {}
    for i, (name, d_re, entities, law) in enumerate(re):
        k_x, k_id, k_w = jax.random.split(jax.random.fold_in(k_re, i), 3)
        xr = data._features(k_x, n, d_re)
        eid = _draw_ids(k_id, n, entities, law)
        w_re = 0.5 * jax.random.normal(k_w, (entities, d_re), jnp.float32)
        logits = logits + jnp.sum(xr * w_re[eid], axis=-1)
        names = jax.random.permutation(jax.random.fold_in(key, i), entities)
        shards[name], ids[name] = xr, names.astype(jnp.int32)[eid]
    y = (jax.random.uniform(k_lab, (n,), jnp.float32)
         < jax.nn.sigmoid(logits)).astype(jnp.float32)
    return xf, shards, ids, y


def make_glmix(seed: int, n: int, d_fix: int, re: Dict[str, Tuple[int, int]],
               laws: Dict[str, dict]):
    """``(xf, {name: xr}, {name: ids}, y)`` on the default device, as
    ``data.make_glmix`` gives them. ``laws`` maps a random-effect
    coordinate's name to ``{"kind": "zipf", "exponent": s}``; a coordinate
    without one is uniform."""
    spec = tuple(
        (name, int(d), int(e),
         (laws.get(name, {}).get("kind", "uniform"),
          float(laws.get(name, {}).get("exponent", 0.0))))
        for name, (d, e) in re.items())
    return _glmix(data.root_key(data.BASE_SEED), data.root_key(seed),
                  n=int(n), d_fix=int(d_fix), re=spec)
