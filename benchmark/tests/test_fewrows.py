"""The few-rows fit cell on the CPU: the generator's population, the window
rehearsed through ``run.run_cell`` with the three metrics this cell brings,
and the cell's control and each of its five faults coming out not correct
(the two that hold the deployment's guarantee among them)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare, control_fewrows, control_ragged, data_ragged, layers
from benchmark import program, run
from benchmark.reference import glmix_ragged
from benchmark.tests import tiny

CELL, CONFIG = "fit.glmix2-fewrows", "glmix2-logistic-fewrows"
USERS, ROWS, D_RE = 512, 1 << 12, 16
LAW = dict(kind="zipf", exponent=0.5)
FIT = dict(rows=ROWS, entities={"per_user": USERS}, trace_fits=2,
           limits={"fixed_gap": 6e-4, "random_gap": 5e-4, "random_row_gap": 2e-3})


@pytest.fixture(scope="module", autouse=True)
def _drop_this_modules_programs():
    yield
    jax.clear_caches()    # as test_ragged: the serving cell's trace wants them gone


def _config():
    return tiny.shrink_config(CONFIG, random_dim=D_RE)


def _run(trace=False, seed=2**31 + 38):
    return run.run_cell(tiny.bench(), CELL, seed=seed, seconds=0.5, trace=trace,
                        device_block=tiny.CPU,
                        overrides=dict(config=_config(), traffic=FIT))


def _tiny_data(seed=5):
    config = dict(_config(), cd_passes=2)
    made = data_ragged.make_glmix(seed, ROWS, 24, {"per_user": (D_RE, USERS)},
                                  {"per_user": LAW})
    return config, made, {"per_user": USERS}


# ---- the configuration and its population ------------------------------------


def test_cell_is_the_one_the_issue_names():
    _, config, traffic = run.load_cell(tiny.bench(), CELL)
    assert traffic["kind"] == "fit_loop_ragged" and traffic["rows"] == 1 << 22
    assert traffic["entities"] == {"per_user": 524288}
    assert traffic["law"] == {"per_user": {"kind": "zipf", "exponent": 0.5}}
    fixed, users = config["coordinates"]
    assert (fixed["dim"], users["dim"], fixed["l2"], users["l2"]) == (256, 16, 1.0, 1.0)
    assert config["cd_passes"] == 2 and config["task"] == "LOGISTIC_REGRESSION"
    # the one key that differs from glmix2-logistic: every per-user coefficient is penalised
    assert fixed["intercept"] == 0 and users["intercept"] is None
    assert set(traffic["limits"]) == {"fixed_gap", "random_gap", "random_row_gap"}
    assert set(traffic["limits"]) <= set(traffic["limits_why"])


def test_most_users_hold_fewer_rows_than_coefficients():
    _, (_xf, _shards, ids, _y), _ = _tiny_data()
    counts = np.bincount(np.asarray(ids["per_user"]), minlength=USERS)
    held = counts[counts > 0]
    assert held.size >= 0.98 * USERS and np.median(held) <= 8
    assert np.mean(held < D_RE) > 0.85
    assert held.max() > 8 * np.median(held)


def test_a_one_label_user_has_a_finite_model_only_under_the_penalised_column():
    """Why the configuration penalises the per-user constant column: with it
    exempt, the reference's users of one label never settle (the last Newton
    move stays large); penalised, every user does."""
    config, made, entities = _tiny_data()
    xf, shards, ids, y = made
    order, xs, sid = glmix_ragged.sort_by_entity(shards["per_user"], ids["per_user"])
    lam = lambda icpt: glmix_ragged._lam(D_RE, 1.0, icpt)
    zero = jnp.zeros((ROWS,), jnp.float32)

    def last_move(intercept):
        w = jnp.zeros((USERS, D_RE), jnp.float32)
        for _ in range(25):
            w, moved = glmix_ragged._re_newton(w, xs, y[order], zero, sid,
                                               lam(intercept), USERS, False)
        return float(moved)

    assert last_move(None) <= 1e-5
    assert last_move(0) > 1e-2


# ---- the cell, rehearsed -----------------------------------------------------------


@pytest.mark.parametrize("trace", [False, True])
def test_fewrows_cell_runs_and_is_correct(trace, monkeypatch):
    from benchmark import tracing

    monkeypatch.setattr(tracing.Tracer, "start", lambda self: None)
    result = _run(trace)
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["checks"]) == {"fixed_gap", "random_gap", "random_row_gap",
                                     "compiles_in_window"}
    if not trace:
        assert set(result["metrics"]) == {"fit_s", "setup_s"}
        return
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert {"re_lockstep_idle_share", "re_underdetermined_share", "re_lanes_max",
            "re_pad_rows_share", "re_blocks_per_pass", "re_geometries",
            "fe_evals_per_fit", "re_newton_iters_per_fit", "prepare_s"} <= set(metrics)
    assert 85.0 < metrics["re_underdetermined_share"] < 100.0
    assert 0.0 < metrics["re_lockstep_idle_share"] < 100.0
    assert metrics["re_lanes_max"] >= 64


def test_new_metrics_read_a_recorded_snapshot_and_nothing_from_an_older_program():
    def rec(metric, value):
        return dict(metric=metric, type="counter", value=value, stats=None,
                    labels=dict(coordinate="per_user"))

    snapshot = [rec("re_lane_iterations_used_total", 300.0),
                rec("re_lane_iterations_run_total", 1200.0),
                rec("re_entities", 500.0), rec("re_entities_rows_ge_dim", 40.0),
                rec("re_lanes_max", 163840.0)]
    facts = dict(registry_after=snapshot)
    assert layers.read_metric("re_lockstep_idle_share", facts) == pytest.approx(75.0)
    assert layers.read_metric("re_underdetermined_share", facts) == pytest.approx(92.0)
    assert layers.read_metric("re_lanes_max", facts) == 163840.0
    for name in ("re_lockstep_idle_share", "re_underdetermined_share", "re_lanes_max"):
        assert layers.read_metric(name, dict(registry_after=[])) is None


# ---- the control and the faults: correct has to come out false ------------------


def test_control_is_not_correct_by_the_cells_limits():
    config, made, entities = _tiny_data()
    want = glmix_ragged.fit(config, *made, entities)
    got = glmix_ragged.fit(config, *made, entities, control=True)
    gaps = compare.model_gaps(config, got, want)
    assert gaps["fixed_gap"] > FIT["limits"]["fixed_gap"]
    assert gaps["random_gap"] > FIT["limits"]["random_gap"]


def _replace_model(monkeypatch, change):
    real = program.fit_once

    def fit_once(estimator, batch, opt):
        model, tracker = real(estimator, batch, opt)
        return change(dict(model)), tracker

    monkeypatch.setattr(program, "fit_once", fit_once)


def test_state_left_unchanged_is_not_correct(monkeypatch):
    _replace_model(monkeypatch,
                   lambda m: {k: jnp.zeros_like(v) for k, v in m.items()})
    result = _run()
    assert not result["correct"]
    assert result["checks"]["random_gap"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    real = program.build_fit
    monkeypatch.setattr(
        program, "build_fit",
        lambda config, xf, shards, ids, y, entities: real(
            config, *control_ragged.take(slice(0, ROWS // 2), xf, shards, ids, y),
            entities))
    checks = _run()["checks"]
    assert checks["fixed_gap"]["value"] > checks["fixed_gap"]["limit"]
    assert checks["random_gap"]["value"] > checks["random_gap"]["limit"]


@pytest.mark.parametrize("cid", ["global", "per_user"])
def test_one_coefficient_altered_is_not_correct(monkeypatch, cid):
    def altered(model):
        model[cid] = model[cid].at[(0,) * model[cid].ndim].add(0.05)
        return model

    _replace_model(monkeypatch, altered)
    assert not _run()["correct"]


@pytest.mark.parametrize("fault", sorted(control_fewrows.GUARANTEE_FAULTS))
def test_guarantee_broken_is_not_correct(monkeypatch, fault):
    """Users under 16 rows left at zero, and the features-to-samples cap
    applied: the program with an option of its own that breaks what the
    configuration guarantees gives another model, and the cell says so."""
    options = control_fewrows.GUARANTEE_FAULTS[fault](1.0)
    monkeypatch.setattr(program, "build_fit",
                        control_fewrows.build_fit_with(program.build_fit, **options))
    result = _run()
    checks = result["checks"]
    assert not result["correct"]
    assert checks["random_gap"]["value"] > checks["random_gap"]["limit"]
    assert checks["random_row_gap"]["value"] > checks["random_row_gap"]["limit"]
