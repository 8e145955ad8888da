"""The system under test, reached through its public entries only.

This is the one module of the benchmark that imports ``photon_tpu``
(``GameEstimator``, ``ServingEngine``, ``registry()``, the model containers
the two take). Everything it hands back to the harness is plain arrays and
numbers; the references never see it.
"""

from __future__ import annotations

import contextlib
from typing import Dict, List


def coordinates(config: dict, kind: str) -> List[dict]:
    return [c for c in config["coordinates"] if c["type"] == kind]


def build_fit(config: dict, xf, shards: Dict, ids: Dict, y, entities: Dict[str, int]):
    """``(estimator, batch, optimization_config)`` for one configuration on
    device-resident arrays. ``shards``/``ids`` are keyed by coordinate id."""
    import jax.numpy as jnp

    from photon_tpu.data.game_data import GameBatch
    from photon_tpu.estimators.config import (
        FixedEffectCoordinateConfig, GameOptimizationConfig,
        RandomEffectCoordinateConfig, RegularizationConfig,
    )
    from photon_tpu.estimators.game_estimator import GameEstimator
    from photon_tpu.types import OptimizerType, TaskType

    (fixed,) = coordinates(config, "fixed")
    random = coordinates(config, "random")
    n = y.shape[0]
    batch = GameBatch(
        label=y,
        offset=jnp.zeros((n,), jnp.float32),
        weight=jnp.ones((n,), jnp.float32),
        features={fixed["feature_shard"]: xf,
                  **{c["feature_shard"]: shards[c["id"]] for c in random}},
        entity_ids={c["re_type"]: ids[c["id"]] for c in random},
    )
    coord_cfgs = [FixedEffectCoordinateConfig(
        fixed["id"], fixed["feature_shard"],
        optimizer=OptimizerType[fixed["optimizer"]])]
    coord_cfgs += [RandomEffectCoordinateConfig(
        c["id"], c["re_type"], c["feature_shard"],
        optimizer=OptimizerType[c["optimizer"]]) for c in random]
    estimator = GameEstimator(
        task=TaskType[config["task"]],
        coordinate_configs=coord_cfgs,
        num_iterations=int(config["cd_passes"]),
        intercept_indices={c["feature_shard"]: c["intercept"]
                           for c in config["coordinates"]
                           if c.get("intercept") is not None},
        num_entities={c["re_type"]: int(entities[c["id"]]) for c in random},
    )
    opt = GameOptimizationConfig(reg={
        c["id"]: RegularizationConfig(weight=float(c["l2"]))
        for c in config["coordinates"]})
    return estimator, batch, opt


def fit_once(estimator, batch, opt):
    """One ``GameEstimator.fit`` from zero coefficients, fenced on the
    model's leaves. Returns ``(coefficients by coordinate id, tracker)``; the
    coefficient arrays stay on the device."""
    import jax

    (result,) = estimator.fit(batch, optimization_configs=[opt])
    jax.block_until_ready(jax.tree_util.tree_leaves(result.model))
    return model_arrays(result.model), result.tracker


def model_arrays(model) -> Dict[str, object]:
    out = {}
    for cid, sub in model.models.items():
        if hasattr(sub, "coefficients"):
            out[cid] = sub.coefficients          # RandomEffectModel: (E, d)
        else:
            out[cid] = sub.model.coefficients.means  # FixedEffectModel: (d,)
    return out


def tracker_counts(config: dict, tracker: Dict[str, list]) -> Dict[str, dict]:
    """The solvers' own work counts of ONE fit, summed over its passes:
    fixed effect ``evals`` (unit reported beside it) and ``iterations``;
    random effects ``entities``, Newton ``iterations`` (mean × entities) and
    ``max_iterations`` (the slowest entity's, which sets how long a block's
    Newton loop runs). Device→host reads: call after the window."""
    out = {}
    for c in config["coordinates"]:
        diags = [d.diagnostics_dict() for d in tracker[c["id"]]]
        if c["type"] == "fixed":
            out[c["id"]] = dict(
                type="fixed", passes=len(diags),
                evals=sum(d["evals"] for d in diags),
                eval_unit=diags[0]["eval_unit"],
                iterations=sum(d["iterations"] for d in diags),
                reasons=[d["reason"] for d in diags])
        else:
            out[c["id"]] = dict(
                type="random", passes=len(diags),
                entities=diags[0]["entities"],
                converged=[d["converged"] for d in diags],
                newton_iterations=sum(d["mean_iterations"] * d["entities"]
                                      for d in diags),
                max_iterations=sum(d["max_iterations"] for d in diags))
    return out


@contextlib.contextmanager
def re_kernel_forced(kernel: str):
    """Route the random-effect Newton system through another of the
    program's own lowerings (``xla``, ``pallas``, ``pallas_bf16x``) while the
    block is open. ``GameEstimator`` exposes no such option, so this swaps
    the resolver it calls. Only ``control.py``'s witness readings use it;
    a run of the benchmark never does."""
    from photon_tpu.ops import pallas_newton

    if kernel not in pallas_newton.RE_KERNELS or kernel == "auto":
        raise ValueError(f"no concrete RE kernel {kernel!r}")
    real = pallas_newton.resolve_re_kernel
    pallas_newton.resolve_re_kernel = lambda _requested: kernel
    try:
        yield
    finally:
        pallas_newton.resolve_re_kernel = real


def build_engine(config: dict, tables: Dict[str, object], serve: dict):
    """A ``ServingEngine`` over a model of the given coefficient tables
    (host numpy arrays keyed by coordinate id; the fixed effect's is (d,))."""
    from photon_tpu.models.coefficients import Coefficients
    from photon_tpu.models.game import (
        FixedEffectModel, GameModel, RandomEffectModel,
    )
    from photon_tpu.models.glm import GeneralizedLinearModel
    from photon_tpu.serve.engine import ServeConfig, ServingEngine
    from photon_tpu.types import TaskType

    task = TaskType[config["task"]]
    models = {}
    for c in config["coordinates"]:
        if c["type"] == "fixed":
            models[c["id"]] = FixedEffectModel(
                GeneralizedLinearModel(Coefficients(tables[c["id"]]), task),
                c["feature_shard"])
        else:
            models[c["id"]] = RandomEffectModel(
                tables[c["id"]], c["re_type"], c["feature_shard"], task)
    cfg = ServeConfig(
        max_batch_size=int(serve["max_batch_size"]),
        max_delay_ms=float(serve["max_delay_ms"]),
        queue_cap=int(serve["queue_cap"]),
        hot_bytes=int(serve["hot_bytes"]),
    )
    return ServingEngine(GameModel(models), config=cfg)


def score_request(config: dict, features: Dict[str, object], entity: Dict[str, int]):
    from photon_tpu.serve.batcher import ScoreRequest

    by_shard = {c["feature_shard"]: features[c["id"]] for c in config["coordinates"]}
    ids = {c["re_type"]: entity[c["id"]] for c in coordinates(config, "random")}
    return ScoreRequest(by_shard, ids)


def registry_snapshot() -> List[dict]:
    from photon_tpu.obs.metrics import registry

    return registry().snapshot()
