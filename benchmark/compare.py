"""The comparison that decides ``correct``: what the timed path produced,
against the plain reference, each number beside its limit.

The references import nothing of the program and take nothing it has made;
they run once the window has closed, the peak memory has been read and the
program's state has been freed.
"""

from __future__ import annotations

import time
from typing import Dict, List, Tuple

Check = Tuple[str, float, float]


def rel_gap(got, want) -> float:
    """‖got − want‖ / ‖want‖ (Frobenius), float32 on the device."""
    import jax.numpy as jnp

    return float(jnp.linalg.norm((got - want).ravel())
                 / jnp.linalg.norm(want.ravel()))


def row_gap(got, want) -> float:
    """The worst entity of a coefficient table: ‖got_e − want_e‖ over the
    larger of ‖want_e‖ and the median entity's norm (some entities' effects
    are all but zero)."""
    import jax.numpy as jnp

    norms = jnp.linalg.norm(want, axis=1)
    floor = jnp.maximum(norms, jnp.median(norms))
    return float(jnp.max(jnp.linalg.norm(got - want, axis=1) / floor))


def model_gaps(config: dict, model: Dict, ref: Dict) -> Dict[str, float]:
    """One model against the reference's: ``fixed_gap``, and over the
    random-effect tables the widest ``random_gap`` (whole table) and
    ``random_row_gap`` (worst entity). A missing or misshapen table reads 1."""
    fixed = {c["id"] for c in config["coordinates"] if c["type"] == "fixed"}
    gaps: Dict[str, float] = {}
    for cid, want in ref.items():
        got = model.get(cid)
        sound = got is not None and got.shape == want.shape
        pairs = [("fixed_gap" if cid in fixed else "random_gap",
                  rel_gap(got, want) if sound else 1.0)]
        if cid not in fixed:
            pairs.append(("random_row_gap", row_gap(got, want) if sound else 1.0))
        for key, gap in pairs:
            gap = gap if gap == gap else float("inf")   # NaN: never within a limit
            gaps[key] = max(gaps.get(key, 0.0), gap)
    return gaps


def fit_models(ctx, config: dict, traffic: dict, models: List[Dict], xf, shards,
               ids, y, entities) -> List[Check]:
    """Every model the window's fits returned against the reference model
    (``model_gaps``), each number the worst over the window's fits."""
    from benchmark.reference import glmix

    t0 = time.perf_counter()
    ref = glmix.fit(config, xf, shards, ids, y, entities, log=ctx.log)
    gaps: Dict[str, float] = {}
    for model in models:
        for key, gap in model_gaps(config, model, ref).items():
            gaps[key] = max(gaps.get(key, 0.0), gap)
    if not models:
        gaps = {"fixed_gap": float("inf")}
    ctx.log(f"reference and comparison of {len(models)} models took "
            f"{time.perf_counter() - t0:.1f}s")
    limits = traffic["limits"]
    return [(name, value, limits[name]) for name, value in gaps.items()]


def scores(traffic: dict, served, reference, answered) -> List[Check]:
    """Every score the window's requests got back against the reference's
    score of the same request: the widest |gap| relative to max(1, |ref|),
    over the requests that were answered."""
    import numpy as np

    served = np.asarray(served, np.float64)
    reference = np.asarray(reference, np.float64)
    answered = np.asarray(answered, bool)
    if not answered.any():
        gap = float("inf")
    else:
        diff = np.abs(served[answered] - reference[answered])
        gap = float(np.max(diff / np.maximum(1.0, np.abs(reference[answered]))))
        if gap != gap:
            gap = float("inf")
    return [("score_gap", gap, traffic["limits"]["score_gap"])]


def verdict(checks: List[Check]) -> bool:
    return all(value == value and value <= limit for _, value, limit in checks)
