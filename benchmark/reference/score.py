"""Plain reference of GLMix scoring: x_fix·w + Σ x_re·W[entity], every
product a float32 multiply and every sum a float32 sum, in row blocks on the
device. An entity id outside the table (cold start) contributes 0.

``control=True`` cuts features and coefficients to bfloat16 first.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

ROW_BLOCK = 1 << 15


@functools.partial(jax.jit, static_argnames=("control",))
def _block(xf, w, xr: Dict, tables: Dict, ids: Dict, control: bool):
    cut = (lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)) if control \
        else (lambda a: a)
    total = jnp.sum(cut(xf) * cut(w), axis=-1)
    for cid, table in tables.items():
        eid = ids[cid]
        known = (eid >= 0) & (eid < table.shape[0])
        rows = table[jnp.where(known, eid, 0)]
        part = jnp.sum(cut(xr[cid]) * cut(rows), axis=-1)
        total = total + jnp.where(known, part, 0.0)
    return total


def score(xf, w, xr: Dict, tables: Dict, ids: Dict, control: bool = False):
    """(n,) float32 scores for n requests, in row blocks. Entity ids must
    fit int32 where they index a table (cold ids are clamped before)."""
    n = xf.shape[0]
    out = []
    for lo in range(0, n, ROW_BLOCK):
        sl = slice(lo, lo + ROW_BLOCK)
        out.append(_block(xf[sl], w, {k: v[sl] for k, v in xr.items()}, tables,
                          {k: v[sl] for k, v in ids.items()}, control))
    return jnp.concatenate(out) if out else jnp.zeros((0,), jnp.float32)
