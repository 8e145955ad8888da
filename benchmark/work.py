"""Operations and bytes a call needs, from shapes and the solvers' reported
counts, and the chip's peaks.

"Needs" is the algorithm's need, the same whatever kernel implements it:
unpadded shapes (rows × d_re, not a 128-lane padding), one read of a matrix
where one read can serve, float32 at 4 bytes. The shares built from these
can therefore not pass 100 % unless a count here is too high or a time
leaves out part of the work.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
F32 = 4


def peaks(device_kind: str) -> dict:
    """The peaks of one chip of this kind. A kind that is not in the table
    is an error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}; benchmark/peaks.json "
            f"has {sorted(table)}")
    return table[device_kind]


def least_seconds(flops: float, bytes_: float, peak: dict) -> Tuple[float, str]:
    """The roofline bound: the least time the chip could take, and which of
    the two limits sets it."""
    t_flops = flops / peak["flops_per_s"]
    t_bytes = bytes_ / peak["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")


def fe_solve(rows: int, dim: int, evals: int, eval_unit: str) -> Dict[str, float]:
    """The fixed-effect solve: the solver's reported evaluations, each a
    number of passes over X (rows × dim float32). A pass is one matrix-vector
    product, X·v or Xᵀ·r: 2·rows·dim flops, one read of X. An
    ``objective_evals`` unit is a value-and-gradient, two passes; an
    ``x_passes`` unit (margin-space L-BFGS) is one. The passes of one
    iteration depend on each other through the line search, so each needs
    its own read."""
    per = {"objective_evals": 2, "x_passes": 1}[eval_unit]
    passes = per * evals
    return dict(flops=2.0 * rows * dim * passes,
                bytes=float(rows) * dim * F32 * passes, passes=passes)


def re_newton_system(rows: int, dim: int, iterations_per_entity: float
                     ) -> Dict[str, float]:
    """The Newton-system kernel of a random effect: per Newton iteration and
    entity, H = Xᵀ·diag(d2)·X and g = Xᵀ·dz over that entity's rows, one read
    of its slab. ``rows`` is the coordinate's unpadded total over entities;
    ``iterations_per_entity`` the reported mean (over entities and passes
    summed), so rows × iterations is Σ_e rows_e·its_e for even entities."""
    per_row = 2.0 * dim * dim + 2.0 * dim
    per_row_bytes = (dim + 2.0) * F32
    return dict(flops=rows * iterations_per_entity * per_row,
                bytes=rows * iterations_per_entity * per_row_bytes)


def re_solve(rows: int, entities: int, dim: int, iterations_per_entity: float
             ) -> Dict[str, float]:
    """The whole random-effect solve: the system above, the margins x·w
    (2·dim flops a row) and a Cholesky solve (dim³/3 + 2·dim² flops an
    entity) per iteration."""
    sys_ = re_newton_system(rows, dim, iterations_per_entity)
    its = iterations_per_entity
    return dict(
        flops=sys_["flops"] + rows * its * 2.0 * dim
        + entities * its * (dim ** 3 / 3.0 + 2.0 * dim * dim),
        bytes=sys_["bytes"] + rows * its * 2 * F32)


def score_batch(rows: float, dims: Dict[str, int], random: Tuple[str, ...]
                ) -> Dict[str, float]:
    """One scoring micro-batch: every shard's features in, each random
    effect's gathered coefficient rows, the fixed effect's vector, scores
    out; a multiply-add per feature."""
    width = sum(dims.values())
    gathered = sum(dims[c] for c in random)
    fixed = width - gathered
    return dict(flops=2.0 * rows * width,
                bytes=(rows * (width + gathered) + fixed + rows) * F32)


# ---- the same counts, taken from a run's facts (what the readers call) ------


def _random(facts: dict):
    return [(cid, c) for cid, c in facts["counts"].items() if c["type"] == "random"]


def fit_fe_solve(facts: dict) -> Dict[str, float]:
    """All fixed-effect solves of ONE fit (its passes summed)."""
    out = dict(flops=0.0, bytes=0.0)
    for cid, c in facts["counts"].items():
        if c["type"] == "fixed":
            w = fe_solve(facts["rows"], facts["dims"][cid], c["evals"], c["eval_unit"])
            out["flops"] += w["flops"]
            out["bytes"] += w["bytes"]
    return out


def fit_re_newton_system(facts: dict) -> Dict[str, float]:
    """The Newton-system work of all random-effect solves of ONE fit."""
    out = dict(flops=0.0, bytes=0.0)
    for cid, c in _random(facts):
        w = re_newton_system(facts["rows"], facts["dims"][cid],
                             c["newton_iterations"] / max(c["entities"], 1))
        out["flops"] += w["flops"]
        out["bytes"] += w["bytes"]
    return out


def fit_total(facts: dict) -> Dict[str, float]:
    """Everything ONE fit needs: its fixed-effect solves, its random-effect
    solves, and a scoring pass per coordinate and CD pass (2·dim flops a row)
    for the residual exchange."""
    out = fit_fe_solve(facts)
    for cid, c in facts["counts"].items():
        dim = facts["dims"][cid]
        out["flops"] += c["passes"] * 2.0 * facts["rows"] * dim
        out["bytes"] += c["passes"] * float(facts["rows"]) * dim * F32
        if c["type"] == "random":
            w = re_solve(facts["rows"], c["entities"], dim,
                         c["newton_iterations"] / max(c["entities"], 1))
            out["flops"] += w["flops"]
            out["bytes"] += w["bytes"]
    return out


def serve_batch(facts: dict) -> Dict[str, float]:
    """One scoring micro-batch of the window's mean size."""
    return score_batch(facts["mean_batch_rows"], facts["dims"],
                       tuple(facts["random"]))


def serve_total(facts: dict) -> Dict[str, float]:
    """Every request the window answered, scored once."""
    return score_batch(facts["answered"], facts["dims"], tuple(facts["random"]))
