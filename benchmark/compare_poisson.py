"""``correct`` for a fit under an elastic net: ``compare.model_gaps``'s three
numbers and, beside them, whether the penalty's zeros are where the
reference's are.

``support_diff`` counts the fixed-effect coefficients that are exactly zero
on one side and larger than ``SUPPORT_FLOOR`` × max|w_ref| on the other: a
coefficient the optimum sets to zero and the program leaves at rounding's
size does not count, one that a fit without the L1 part leaves at a
thousandth of the largest does.
"""

from __future__ import annotations

import time
from typing import Dict, List

from benchmark import compare

SUPPORT_FLOOR = 1e-3


def support_diff(got, want) -> int:
    import jax.numpy as jnp

    floor = SUPPORT_FLOOR * jnp.max(jnp.abs(want))
    return int(jnp.sum(((got == 0) & (jnp.abs(want) > floor))
                       | ((want == 0) & (jnp.abs(got) > floor))))


def model_gaps(config: dict, model: Dict, ref: Dict) -> Dict[str, float]:
    """``compare.model_gaps`` plus ``support_diff`` over the fixed effects; a
    missing or misshapen fixed effect counts every coefficient."""
    gaps = compare.model_gaps(config, model, ref)
    gaps["support_diff"] = 0
    for c in config["coordinates"]:
        if c["type"] != "fixed":
            continue
        got, want = model.get(c["id"]), ref[c["id"]]
        sound = got is not None and got.shape == want.shape
        gaps["support_diff"] += support_diff(got, want) if sound else want.shape[0]
    return gaps


def zero_share(config: dict, model: Dict) -> float:
    """Share of the fixed effects' FEATURE coefficients (the intercept left
    out) that are exactly zero."""
    import jax.numpy as jnp

    zeros = features = 0
    for c in config["coordinates"]:
        if c["type"] == "fixed":
            w = model[c["id"]]
            if c.get("intercept") is not None:
                w = jnp.delete(w, c["intercept"])
            zeros += int(jnp.sum(w == 0))
            features += w.shape[0]
    return zeros / max(features, 1)


def fit_models(ctx, config: dict, traffic: dict, models: List[Dict], xf, shards,
               ids, y, entities) -> List[compare.Check]:
    """Every model the window's fits returned against the proximal-Newton
    reference, each number the worst over the window's fits."""
    from benchmark.reference import glmix_poisson_enet

    t0 = time.perf_counter()
    ref = glmix_poisson_enet.fit(config, xf, shards, ids, y, entities, log=ctx.log)
    gaps: Dict[str, float] = {}
    for model in models:
        for key, gap in model_gaps(config, model, ref).items():
            gaps[key] = max(gaps.get(key, 0.0), gap)
    if not models:
        gaps = {"fixed_gap": float("inf")}
    ctx.log(f"reference and comparison of {len(models)} models took "
            f"{time.perf_counter() - t0:.1f}s; the reference's fixed effect has "
            f"{100 * zero_share(config, ref):.1f} % of its feature coefficients "
            f"at exactly zero")
    limits = traffic["limits"]
    return [(name, value, limits[name]) for name, value in gaps.items()]
