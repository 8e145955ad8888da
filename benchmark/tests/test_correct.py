"""``correct`` has to come out false when it should: the control (the
reference at the nearest precision below, put in the program's place) and
each fault the cells can have, planted under a run that is otherwise whole."""

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare, data, program, run
from benchmark.reference import glmix, score as score_ref
from benchmark.tests import tiny


def _fit(cell="fit.glmix2", config="glmix2-logistic"):
    return run.run_cell(tiny.bench(), cell, seed=5, seconds=0.5, trace=False,
                        device_block=tiny.CPU,
                        overrides=dict(config=tiny.shrink_config(config),
                                       traffic=tiny.FIT))


def _serve():
    return run.run_cell(tiny.bench(), "serve.glmix2", seed=5, seconds=1.0,
                        trace=False, device_block=tiny.CPU,
                        overrides=dict(config=tiny.shrink_config("glmix2-logistic"),
                                       traffic=tiny.SERVE))


def test_fit_state_left_unchanged_is_not_correct(monkeypatch):
    real = program.fit_once

    def unchanged(estimator, batch, opt):
        model, tracker = real(estimator, batch, opt)
        return {k: jnp.zeros_like(v) for k, v in model.items()}, tracker

    monkeypatch.setattr(program, "fit_once", unchanged)
    result = _fit()
    assert not result["correct"]
    assert result["checks"]["fixed_gap"]["value"] == pytest.approx(1.0)


def test_fit_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    real = program.build_fit

    def half(config, xf, shards, ids, y, entities):
        n = y.shape[0] // 2
        return real(config, xf[:n], {k: v[:n] for k, v in shards.items()},
                    {k: v[:n] for k, v in ids.items()}, y[:n], entities)

    monkeypatch.setattr(program, "build_fit", half)
    result = _fit()
    assert not result["correct"]
    checks = result["checks"]
    assert checks["fixed_gap"]["value"] > checks["fixed_gap"]["limit"]
    assert checks["random_gap"]["value"] > checks["random_gap"]["limit"]


@pytest.mark.parametrize("cell,config,cid", [
    ("fit.glmix2", "glmix2-logistic", "global"),
    ("fit.glmix2", "glmix2-logistic", "per_user"),
    ("fit.glmix3", "glmix3-logistic", "per_item"),
])
def test_fit_answer_altered_where_it_is_produced_is_not_correct(monkeypatch, cell,
                                                                config, cid):
    real = program.fit_once

    def altered(estimator, batch, opt):
        model, tracker = real(estimator, batch, opt)
        model = dict(model)
        first = (0,) * model[cid].ndim
        model[cid] = model[cid].at[first].add(0.05)   # one coefficient
        return model, tracker

    monkeypatch.setattr(program, "fit_once", altered)
    assert not _fit(cell=cell, config=config)["correct"]


def test_fit_control_in_the_programs_place_is_not_correct(monkeypatch):
    """The reference with every matrix product cut to bfloat16, returned as
    if the program had fitted it."""

    def control(estimator, batch, opt):
        cfg = control.config
        xf = batch.features["global"]
        shards = {"per_user": batch.features["per_user"]}
        ids = {"per_user": batch.entity_ids["userId"]}
        ent = {"per_user": tiny.FIT["entities"]["per_user"]}
        return glmix.fit(cfg, xf, shards, ids, batch.label, ent, control=True), None

    control.config = dict(tiny.shrink_config("glmix2-logistic"), cd_passes=2)
    monkeypatch.setattr(program, "fit_once", control)
    monkeypatch.setattr(program, "tracker_counts", lambda config, tracker: None)
    result = _fit()
    assert not result["correct"]
    assert result["checks"]["fixed_gap"]["value"] > result["checks"]["fixed_gap"]["limit"]


def test_serve_answer_altered_is_not_correct(monkeypatch):
    real = program.build_engine

    def wrong_table(config, tables, serve):
        tables = dict(tables)
        tables["per_user"] = tables["per_user"] + np.float32(0.01)
        return real(config, tables, serve)

    monkeypatch.setattr(program, "build_engine", wrong_table)
    result = _serve()
    assert not result["correct"]
    assert result["checks"]["score_gap"]["value"] > 1e-3


def test_serve_unseen_ids_scored_as_seen_is_not_correct(monkeypatch):
    real = program.score_request

    def wrap(config, features, entity):
        e = tiny.SERVE["entities"]["per_user"]
        return real(config, features, {k: v % e for k, v in entity.items()})

    monkeypatch.setattr(program, "score_request", wrap)
    assert not _serve()["correct"]


def test_serve_control_is_not_correct():
    """The scoring reference with features and coefficients cut to bfloat16,
    against the float32 reference, at the check's own limit."""
    rng = np.random.default_rng(0)
    n, e = 512, 64
    xf = jnp.asarray(rng.standard_normal((n, 24), dtype=np.float32))
    xr = {"per_user": jnp.asarray(rng.standard_normal((n, 4), dtype=np.float32))}
    w = data.make_table(1, 0, 1, 24, 0.2)[0]
    tables = {"per_user": data.make_table(1, 1, e, 4)}
    ids = {"per_user": jnp.asarray(rng.integers(-1, e, n).astype(np.int32))}
    want = score_ref.score(xf, w, xr, tables, ids)
    got = score_ref.score(xf, w, xr, tables, ids, control=True)

    ok = compare.scores(dict(limits=tiny.SERVE["limits"]), want, want,
                        np.ones(n, bool))
    bad = compare.scores(dict(limits=tiny.SERVE["limits"]), got, want,
                         np.ones(n, bool))
    assert compare.verdict(ok) and not compare.verdict(bad)
    # cold ids contribute nothing
    cold = np.asarray(ids["per_user"]) < 0
    only_fixed = np.asarray(jnp.sum(xf * w, axis=-1))
    np.testing.assert_allclose(np.asarray(want)[cold], only_fixed[cold], rtol=1e-6)


def test_control_readings_rehearsal():
    """``control.py``'s readings at a tiny size: the control and the faults
    read above the program, as they have to on the chip."""
    from benchmark import control

    config = {**run.load_json("benchmark", "configs", "glmix2-logistic.json"),
              **tiny.shrink_config("glmix2-logistic")}
    fit = {**run.load_json("benchmark", "workloads", "fit_uniform.json"), **tiny.FIT}
    rec = control.fit_readings(config, fit, 3,
                               {"program", "control", "half", "altered",
                                "witness:xla:highest"}, fresh_rows=True)
    assert rec["program"]["fixed_gap"] < rec["control"]["fixed_gap"] < rec["half"]["fixed_gap"]
    assert rec["altered"]["random_row_gap"] > 10 * rec["program"]["random_row_gap"]
    # the witness is the program too: it sides with the reference
    assert rec["witness:xla:highest"]["random_gap"] < rec["control"]["random_gap"]
    assert rec["witness:xla:highest.run"]["counts"]["per_user"]["max_iterations"] > 0
    traffic = {**run.load_json("benchmark", "workloads", "serve_zipf_pinned.json"),
               **tiny.SERVE}
    serve = control.serve_readings(config, traffic, 3, seconds=1.0)
    assert serve["requests"] == 400 and serve["control"]["score_gap"] > 1e-3
