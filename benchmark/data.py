"""Inputs and weights from ``--seed``, made on the device in one jitted call.

The generative model is ``chip_smoke.py::make_glmix_arrays``: standard-normal
features with an intercept in column 0 of every shard, a fixed effect of
norm ~1, per-entity effects of scale 0.5, Bernoulli labels from the logistic
of the summed margins. Entity ids are uniform over each population. The seed
chooses the entities' names, not the rows.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp


def root_key(seed: int, stream: int = 0):
    """A key from any whole number up to a little over 2**31 (more than 32
    signed bits hold): the low 31 bits seed the key, the rest is folded in."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, seed >> 31)
    return jax.random.fold_in(key, stream)


BASE_SEED = 20260930  # the one data set; a run's seed renames its entities


def _features(key, n: int, d: int):
    """(n, d) float32 standard normal with column 0 set to 1, in one fused
    elementwise pass (no second copy of the matrix)."""
    x = jax.random.normal(key, (n, d), jnp.float32)
    col = jax.lax.broadcasted_iota(jnp.int32, (n, d), 1)
    return jnp.where(col == 0, jnp.float32(1.0), x)


@functools.partial(jax.jit, static_argnames=("n", "d_fix", "re"))
def _glmix(base, key, n: int, d_fix: int, re: Tuple[Tuple[str, int, int], ...]):
    k_fix, k_wfix, k_lab, k_re = jax.random.split(base, 4)
    xf = _features(k_fix, n, d_fix)
    w_fix = jax.random.normal(k_wfix, (d_fix,), jnp.float32) / jnp.sqrt(
        jnp.float32(d_fix))
    logits = jnp.sum(xf * w_fix, axis=-1)
    shards, ids = {}, {}
    for i, (name, d_re, entities) in enumerate(re):
        k_x, k_id, k_w = jax.random.split(jax.random.fold_in(k_re, i), 3)
        xr = _features(k_x, n, d_re)
        eid = jax.random.randint(k_id, (n,), 0, entities, jnp.int32)
        w_re = 0.5 * jax.random.normal(k_w, (entities, d_re), jnp.float32)
        logits = logits + jnp.sum(xr * w_re[eid], axis=-1)
        names = jax.random.permutation(jax.random.fold_in(key, i), entities)
        shards[name], ids[name] = xr, names.astype(jnp.int32)[eid]
    y = (jax.random.uniform(k_lab, (n,), jnp.float32)
         < jax.nn.sigmoid(logits)).astype(jnp.float32)
    return xf, shards, ids, y


def make_glmix(seed: int, n: int, d_fix: int, re: Dict[str, Tuple[int, int]],
               fresh_rows: bool = False):
    """``(xf, {name: xr}, {name: ids}, y)`` on the default device. ``re`` maps
    a random-effect coordinate's name to ``(d_re, entities)``.

    Every seed gets the SAME rows (made from ``BASE_SEED``) in the same
    order; the seed renames the entities, so which table row an entity's
    model lands in, and which block lane solves it, differ from seed to seed
    while every sum the solvers take is over the same numbers in the same
    order. Measured on the chip (PR 26): two runs of one seed agree in
    ``fit_s`` to 0.03 %, while rows drawn afresh per seed moved it ±10 % and
    the same rows in another order ±2 % — rounding flips an L-BFGS iteration
    or the Newton loop of a block's slowest entity. The seed was changing the
    work, so it no longer does.

    ``fresh_rows`` draws the rows from the seed as well: never used by a
    run, kept for ``control.py --fresh-rows``, which reads ``correct``'s
    numbers on data sets the runs do not see."""
    spec = tuple((name, int(d), int(e)) for name, (d, e) in re.items())
    base = root_key(seed, 7) if fresh_rows else root_key(BASE_SEED)
    return _glmix(base, root_key(seed), n=int(n), d_fix=int(d_fix), re=spec)


@functools.partial(jax.jit, static_argnames=("entities", "d", "scale"))
def _table(key, entities: int, d: int, scale: float):
    return scale * jax.random.normal(key, (entities, d), jnp.float32)


def make_table(seed: int, stream: int, entities: int, d: int, scale: float = 0.5):
    """One seeded coefficient table (entities, d) float32 on the device — a
    serving model's random effect, or (entities = 1) its fixed effect."""
    return _table(root_key(seed, 1000 + stream), entities=int(entities),
                  d=int(d), scale=float(scale))
