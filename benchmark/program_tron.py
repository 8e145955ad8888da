"""``program.build_fit`` for a configuration whose fixed effect states its
solver's stopping rule: ``max_iter``, ``tol`` and ``max_cg_iter`` beside
``optimizer``.

``program.build_fit`` hands ``FixedEffectCoordinateConfig`` the optimizer
alone, which leaves the solver at its defaults; this hands it
``max_iter`` and ``tol`` too, and is otherwise that function: the same
``GameEstimator``, reached through the same public entries, with no option
the estimator does not give every caller. ``max_cg_iter`` has no such entry:
the configuration states the default, and another value is refused.
``tracker_counts`` adds TRON's CG and rejected steps to ``program``'s.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

from benchmark import program


def build_fit(config: dict, xf, shards: Dict, ids: Dict, y, entities: Dict[str, int]):
    """``(estimator, batch, optimization_config)`` on device-resident arrays;
    ``shards``/``ids`` are keyed by coordinate id."""
    from photon_tpu.estimators.config import FixedEffectCoordinateConfig
    from photon_tpu.optim.factory import OptimizerSpec

    (fixed,) = program.coordinates(config, "fixed")
    if int(fixed["max_cg_iter"]) != OptimizerSpec.max_cg_iter:
        raise ValueError(f"max_cg_iter {fixed['max_cg_iter']}: the estimator "
                         f"takes only the default, {OptimizerSpec.max_cg_iter}")
    estimator, batch, opt = program.build_fit(config, xf, shards, ids, y, entities)
    estimator.coordinate_configs = [
        dataclasses.replace(c, max_iter=int(fixed["max_iter"]),
                            tol=float(fixed["tol"]))
        if isinstance(c, FixedEffectCoordinateConfig) else c
        for c in estimator.coordinate_configs]
    return estimator, batch, opt


def tracker_counts(config: dict, tracker: Dict[str, list]) -> Dict[str, dict]:
    """``program.tracker_counts`` with, for a fixed effect, the iterations
    of each pass (``pass_iterations``) and, where its solver reports them,
    ``cg_steps`` and ``rejected_steps`` summed over the fit's passes. A
    program whose tracker has neither reports neither. A device→host read:
    call after the window."""
    out = program.tracker_counts(config, tracker)
    for c in program.coordinates(config, "fixed"):
        diags = [d.diagnostics_dict() for d in tracker[c["id"]]]
        out[c["id"]]["pass_iterations"] = [d["iterations"] for d in diags]
        for key in ("cg_steps", "rejected_steps"):
            if all(key in d for d in diags):
                out[c["id"]][key] = sum(d[key] for d in diags)
    return out
