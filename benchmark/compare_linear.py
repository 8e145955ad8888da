"""``correct`` for a fit on a squared loss: ``compare.model_gaps``'s three
numbers (``fixed_gap``, ``random_gap``, ``random_row_gap``) of every model
the window's fits returned, against the normal-equations reference
(``reference/glmix_linear.py``)."""

from __future__ import annotations

import time
from typing import Dict, List

from benchmark import compare


def fit_models(ctx, config: dict, traffic: dict, models: List[Dict], xf, shards,
               ids, y, entities) -> List[compare.Check]:
    """Every model against the reference's, each number the worst over the
    window's fits."""
    from benchmark.reference import glmix_linear

    t0 = time.perf_counter()
    ref = glmix_linear.fit(config, xf, shards, ids, y, entities, log=ctx.log)
    gaps: Dict[str, float] = {}
    for model in models:
        for key, gap in compare.model_gaps(config, model, ref).items():
            gaps[key] = max(gaps.get(key, 0.0), gap)
    if not models:
        gaps = {"fixed_gap": float("inf")}
    ctx.log(f"reference and comparison of {len(models)} models took "
            f"{time.perf_counter() - t0:.1f}s")
    limits = traffic["limits"]
    return [(name, value, limits[name]) for name, value in gaps.items()]
