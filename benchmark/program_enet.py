"""``program.build_fit`` for a configuration whose coordinates state an
elastic net: ``reg_weight`` and ``alpha`` in the place of ``l2``.

``program.build_fit`` hands ``RegularizationConfig`` a weight alone; this
hands it the split too, and is otherwise that function: the same
``GameEstimator``, reached through the same public entries, with no option
the estimator does not give every caller. Everything else of the system
under test (``fit_once``, ``tracker_counts``, the registry) is ``program``'s.
"""

from __future__ import annotations

from typing import Dict

from benchmark import program


def build_fit(config: dict, xf, shards: Dict, ids: Dict, y, entities: Dict[str, int]):
    """``(estimator, batch, optimization_config)`` on device-resident arrays;
    ``shards``/``ids`` are keyed by coordinate id."""
    from photon_tpu.estimators.config import (
        GameOptimizationConfig, RegularizationConfig,
    )

    plain = dict(config, coordinates=[dict(c, l2=0.0)
                                      for c in config["coordinates"]])
    estimator, batch, _ = program.build_fit(plain, xf, shards, ids, y, entities)
    opt = GameOptimizationConfig(reg={
        c["id"]: RegularizationConfig(weight=float(c["reg_weight"]),
                                      alpha=float(c["alpha"]))
        for c in config["coordinates"]})
    return estimator, batch, opt


def quarantined(config: dict, tracker: Dict[str, list]) -> int:
    """Entities of ONE fit whose solve ended DIVERGED (kept at their start by
    the program's quarantine), random effects and passes summed, plus the
    fixed-effect solves that ended so. A device→host read: after the window."""
    total = 0
    for c in config["coordinates"]:
        for d in (t.diagnostics_dict() for t in tracker[c["id"]]):
            total += (d["quarantined"] if c["type"] == "random"
                      else int(d["reason"] == "DIVERGED"))
    return total
