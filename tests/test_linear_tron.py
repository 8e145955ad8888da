"""A squared loss with a trust-region Newton fixed effect through the
estimator's normal path: ``TaskType.LINEAR_REGRESSION`` with
``FixedEffectCoordinateConfig(optimizer=TRON, max_iter, tol)`` and a
per-user random effect.

What is held here, on the CPU at a small size (8,192 rows, a fixed shard 32
wide with AR(1) ρ = 0.8 features, 64 users of width 8): ``GameEstimator.fit``
against the benchmark's normal-equations reference (which imports nothing
of the program), within tolerances that the reference at bfloat16 products
and TRON stopped after one iteration both fail; TRON's CG and rejected steps
published once when a tracker is read, and not before.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

from benchmark import compare, data_linear, program, program_tron
from benchmark.reference import glmix_linear as reference
from photon_tpu.obs.metrics import registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS, D_FIX, D_RE, USERS = 8192, 32, 8, 64
TRUTH = dict(rho=0.8, re_scale=0.5, noise=1.0)
# Read at this size (seed 11): the program 1.6e-4 / 1.2e-5 / 2.3e-5, the
# bfloat16 control 8.1e-4 / 8.8e-4 / 1.5e-3, TRON stopped after one
# iteration 8.7e-2 / 1.5e-2 / 2.0e-2. Each tolerance lies between the
# program's reading and the control's, with room on both sides.
TOLERANCES = {"fixed_gap": 4e-4, "random_gap": 2e-4, "random_row_gap": 4e-4}


def small_config(**fixed) -> dict:
    """benchmark/configs/glmix2-linear-tron.json at the small widths, its
    stopping rule kept unless ``fixed`` changes it."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "glmix2-linear-tron.json")) as f:
        config = json.load(f)
    for c in config["coordinates"]:
        c["dim"] = D_FIX if c["type"] == "fixed" else D_RE
        if c["type"] == "fixed":
            c.update(fixed)
    return config


def fit(config, data):
    estimator, batch, opt = program_tron.build_fit(config, *data,
                                                   {"per_user": USERS})
    return program.fit_once(estimator, batch, opt)


@pytest.fixture(scope="module")
def fitted():
    config = small_config()
    data = data_linear.make_glmix(11, ROWS, D_FIX, {"per_user": (D_RE, USERS)},
                                  TRUTH)
    registry().reset()
    model, tracker = fit(config, data)
    ref = reference.fit(config, *data, {"per_user": USERS})
    return config, data, model, tracker, ref


def _failing(config, model, ref):
    gaps = compare.model_gaps(config, model, ref)
    return {k for k, limit in TOLERANCES.items() if not gaps[k] <= limit}, gaps


def test_fit_agrees_with_the_normal_equations_reference(fitted):
    config, _data, model, tracker, ref = fitted
    failing, gaps = _failing(config, model, ref)
    assert not failing, gaps
    fixed = [d.diagnostics_dict() for d in tracker["global"]]
    assert all(d["eval_unit"] == "x_passes" for d in fixed)
    # the solve works by CG: several products an outer iteration
    assert sum(d["cg_steps"] for d in fixed) >= 3 * sum(d["iterations"] for d in fixed)
    assert all(d["quarantined"] == 0 for d in
               (t.diagnostics_dict() for t in tracker["per_user"]))


def test_bfloat16_control_fails_the_tolerances(fitted):
    config, data, _model, _tracker, ref = fitted
    control = reference.fit(config, *data, {"per_user": USERS}, control=True)
    failing, gaps = _failing(config, control, ref)
    assert {"fixed_gap", "random_gap", "random_row_gap"} <= failing, gaps


def test_tron_stopped_after_one_iteration_fails_the_tolerances(fitted):
    config, data, _model, _tracker, ref = fitted
    model, tracker = fit(small_config(max_iter=1), data)
    assert [d.diagnostics_dict()["iterations"] for d in tracker["global"]] == [1, 1]
    failing, gaps = _failing(config, model, ref)
    assert "fixed_gap" in failing, gaps


def test_cg_and_rejected_steps_are_published_once_a_tracker_is_read(fitted):
    _config, _data, _model, tracker, _ref = fitted
    registry().reset()
    # the fixture's trackers may have been read by an earlier test: read copies
    fresh = [dataclasses.replace(d) for d in tracker["global"]]
    assert registry().find("fe_tron_cg_steps_total", coordinate="global") is None
    diags = [d.diagnostics_dict() for d in fresh]
    for d in fresh:
        d.summary()   # a second read publishes nothing more
    cg = registry().find("fe_tron_cg_steps_total", coordinate="global")
    rejected = registry().find("fe_tron_rejected_steps_total", coordinate="global")
    assert cg.value == sum(d["cg_steps"] for d in diags) > 0
    assert rejected.value == sum(d["rejected_steps"] for d in diags)
    assert registry().find("fe_solver_evals_total", unit="x_passes",
                           coordinate="global", optimizer="tron").value == sum(
        d["evals"] for d in diags)
    products = registry().find("fe_tron_products_total", coordinate="global")
    assert products.value == cg.value + sum(d["iterations"] for d in diags)
    assert registry().find("fe_tron_one_read_products_total",
                           coordinate="global").value == 0  # off a TPU
    assert np.all([d["rejected_steps"] <= d["iterations"] for d in diags])
