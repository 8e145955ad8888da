"""The squared-loss / TRON fit cell on the CPU: its generator beside
``fit_uniform``'s, the normal-equations reference at its optimum, the cell's
control and each of its faults coming out not correct, the window rehearsed
through ``run.run_cell``, and the new metrics read from a tiny traced fit's
facts and from recorded ones."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import (compare, control_linear, data, data_linear, layers, program,
                       program_tron, run, work_tron)
from benchmark.reference import glmix_linear as reference
from benchmark.tests import tiny

CELL, CONFIG = "fit.glmix2-linear-tron", "glmix2-linear-tron"
USERS, ROWS, D_FIX, D_RE = 64, 1 << 13, 24, 4
TRUTH = dict(rho=0.8, re_scale=0.5, noise=1.0)
LIMITS = {"fixed_gap": 4e-4, "random_gap": 2e-4, "random_row_gap": 5e-4}
FIT = dict(rows=ROWS, entities={"per_user": USERS}, trace_fits=2, truth=TRUTH,
           limits=LIMITS)


@pytest.fixture(scope="module", autouse=True)
def _drop_this_modules_programs():
    yield   # as test_ragged: the programs compiled here go when it is done
    jax.clear_caches()


def tiny_config() -> dict:
    """The configuration's coordinates at the tiny widths, its solver's
    stopping rule kept."""
    return tiny.shrink_config(CONFIG, D_FIX, D_RE)


def _run(trace=False, seed=2**31 + 19):
    return run.run_cell(tiny.bench(), CELL, seed=seed, seconds=0.5, trace=trace,
                        device_block=tiny.CPU,
                        overrides=dict(config=tiny_config(), traffic=FIT))


def _data(seed=5):
    return data_linear.make_glmix(seed, ROWS, D_FIX, {"per_user": (D_RE, USERS)},
                                  TRUTH)


@pytest.fixture(scope="module")
def fitted():
    """The tiny data set, its configuration and the reference's model."""
    config = dict(tiny_config(), cd_passes=2)
    xf, shards, ids, y = _data()
    entities = {"per_user": USERS}
    ref = reference.fit(config, xf, shards, ids, y, entities)
    return config, (xf, shards, ids, y, entities), ref


# ---- the generator -------------------------------------------------------------


def test_generator_keeps_fit_uniforms_users_and_correlates_the_fixed_shard():
    xf, shards, ids, y = _data()
    _, ushards, uids, _ = data.make_glmix(5, ROWS, D_FIX, {"per_user": (D_RE, USERS)})
    assert jnp.array_equal(shards["per_user"], ushards["per_user"])
    assert jnp.array_equal(ids["per_user"], uids["per_user"])
    x = np.asarray(xf, np.float64)
    assert np.all(x[:, 0] == 1.0)
    corr = np.corrcoef(x[:, 1:], rowvar=False)
    lag = lambda k: np.mean(np.diagonal(corr, k))  # noqa: E731
    assert lag(1) == pytest.approx(0.8, abs=0.02)
    assert lag(3) == pytest.approx(0.8 ** 3, abs=0.03)
    assert np.std(x[:, 1:]) == pytest.approx(1.0, abs=0.02)
    assert np.all(np.isfinite(np.asarray(y))) and float(np.std(np.asarray(y))) > 1.0
    # another seed renames the users and keeps rows and labels
    xf2, _, ids2, y2 = _data(6)
    assert jnp.array_equal(xf, xf2) and jnp.array_equal(y, y2)
    assert not jnp.array_equal(ids["per_user"], ids2["per_user"])


def test_ar1_mix_is_the_cholesky_factor_of_the_toeplitz_matrix():
    m = np.asarray(data_linear.ar1_mix(6, 0.8), np.float64)
    assert m[0, 0] == 1.0 and not m[0, 1:].any() and not m[1:, 0].any()
    k = np.arange(5)
    np.testing.assert_allclose(m[1:, 1:].T @ m[1:, 1:],
                               0.8 ** np.abs(k[:, None] - k[None, :]), atol=1e-6)


# ---- the reference ---------------------------------------------------------------


def test_reference_blocks_are_at_their_optimum(fitted):
    """Each block's gradient, in float64 against the other's scores, is at
    rounding's size: the fixed effect after both passes has nothing to gain."""
    config, (xf, shards, ids, y, _entities), ref = fitted
    x = np.asarray(xf, np.float64)
    w = np.asarray(ref["global"], np.float64)
    users = np.asarray(ids["per_user"])
    offset = np.sum(np.asarray(shards["per_user"], np.float64)
                    * np.asarray(ref["per_user"], np.float64)[users], axis=1)
    lam = np.full(D_FIX, 1.0)
    lam[0] = 0.0
    g = x.T @ (x @ w + offset - np.asarray(y, np.float64)) + lam * w
    scale = np.linalg.norm(x.T @ np.asarray(y, np.float64))
    # the fixed effect was solved last against the first pass's users; the
    # second pass's users moved it a little, so the gradient is small, not 0
    assert np.linalg.norm(g) <= 1e-3 * scale
    # the last block solved (the users) is at its optimum to float32 accuracy
    x_u = np.asarray(shards["per_user"], np.float64)
    r = x @ w + offset - np.asarray(y, np.float64)
    g_u = np.zeros((USERS, D_RE))
    np.add.at(g_u, users, x_u * r[:, None])
    lam_u = np.full(D_RE, 1.0)
    lam_u[0] = 0.0
    g_u += lam_u * np.asarray(ref["per_user"], np.float64)
    assert np.max(np.abs(g_u)) <= 1e-5 * scale


def test_control_is_not_correct_by_the_cells_limits(fitted):
    config, (xf, shards, ids, y, entities), ref = fitted
    got = reference.fit(config, xf, shards, ids, y, entities, control=True)
    gaps = compare.model_gaps(config, got, ref)
    assert any(gaps[k] > LIMITS[k] for k in LIMITS), gaps


# ---- the cell, rehearsed ---------------------------------------------------------


@pytest.mark.parametrize("trace", [False, True])
def test_tron_cell_runs_and_is_correct(trace, monkeypatch):
    from benchmark import tracing

    # as test_ragged: the profiler's own session is rehearsed in test_rehearsal
    monkeypatch.setattr(tracing.Tracer, "start", lambda self: None)
    result = _run(trace)
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["checks"]) == {"fixed_gap", "random_gap", "random_row_gap",
                                     "compiles_in_window"}
    fixed = result["counts"]["global"]
    assert fixed["eval_unit"] == "x_passes"
    assert fixed["evals"] == (3 * fixed["passes"] + 5 * fixed["iterations"]
                              + 2 * fixed["cg_steps"])
    if trace:
        assert {"fe_tron_iters_per_fit", "fe_cg_steps_per_iter", "fe_evals_per_fit",
                "re_newton_iters_per_fit", "prepare_s"} <= set(result["metrics"])
        assert result["metrics"]["fe_cg_steps_per_iter"]["value"] >= 1.0
    else:
        assert set(result["metrics"]) == {"fit_s", "setup_s"}


# ---- faults: correct has to come out false ------------------------------------------


def _failing(result):
    return {k for k, c in result["checks"].items() if not c["value"] <= c["limit"]}


def test_state_left_unchanged_is_not_correct(monkeypatch):
    real = program.fit_once

    def unchanged(estimator, batch, opt):
        model, tracker = real(estimator, batch, opt)
        return {k: jnp.zeros_like(v) for k, v in model.items()}, tracker

    monkeypatch.setattr(program, "fit_once", unchanged)
    result = _run()
    assert not result["correct"]
    assert result["checks"]["fixed_gap"]["value"] == pytest.approx(1.0)


def _fit_with(change):
    """``program_tron.build_fit`` given ``change(config, xf, shards, ids, y)``."""
    real = program_tron.build_fit

    def build(config, xf, shards, ids, y, entities):
        return real(*change(config, xf, shards, ids, y), entities)

    return build


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    half = lambda c, xf, sh, ids, y: (  # noqa: E731
        c, xf[:ROWS // 2], {k: v[:ROWS // 2] for k, v in sh.items()},
        {k: v[:ROWS // 2] for k, v in ids.items()}, y[:ROWS // 2])
    monkeypatch.setattr(program_tron, "build_fit", _fit_with(half))
    assert {"fixed_gap", "random_gap"} <= _failing(_run())


def test_tron_stopped_after_one_iteration_is_not_correct(monkeypatch):
    one = lambda c, *data_: (control_linear.with_fixed(c, max_iter=1), *data_)  # noqa: E731
    monkeypatch.setattr(program_tron, "build_fit", _fit_with(one))
    assert "fixed_gap" in _failing(_run())


@pytest.mark.parametrize("cid,index", [("global", (1,)), ("per_user", (0, 0))])
def test_one_coefficient_altered_is_not_correct(monkeypatch, cid, index):
    real = program.fit_once

    def altered(estimator, batch, opt):
        model, tracker = real(estimator, batch, opt)
        model = dict(model)
        model[cid] = model[cid].at[index].add(0.05)
        return model, tracker

    monkeypatch.setattr(program, "fit_once", altered)
    assert not _run()["correct"]


def test_max_cg_iter_other_than_the_default_is_refused(fitted):
    config, (xf, shards, ids, y, entities), _ref = fitted
    with pytest.raises(ValueError, match="max_cg_iter"):
        program_tron.build_fit(control_linear.with_fixed(config, max_cg_iter=10),
                               xf, shards, ids, y, entities)


# ---- the new metrics, from recorded facts ----------------------------------------------


FACTS = dict(
    rows=1 << 22, dims={"global": 256, "per_user": 16}, traced_fits=3,
    device_kind="TPU v5 lite",
    counts={"global": dict(type="fixed", passes=2, evals=189, iterations=8,
                           cg_steps=72, rejected_steps=0, eval_unit="x_passes"),
            "per_user": dict(type="random", passes=2, entities=8192,
                             newton_iterations=8192 * 2.0, max_iterations=4)},
)


@pytest.mark.parametrize("metric,want", [
    ("fe_tron_iters_per_fit", 8.0),
    ("fe_cg_steps_per_iter", 9.0),
])
def test_new_counts_read_recorded_facts(metric, want):
    assert layers.read_metric(metric, FACTS) == pytest.approx(want)


def test_new_metrics_are_silent_on_a_program_without_the_counts(monkeypatch):
    # the parent reports no CG steps: its counts lack the key
    older = dict(FACTS, counts={"global": {k: v for k, v in FACTS["counts"]["global"]
                                           .items() if k not in ("cg_steps",
                                                                 "rejected_steps")},
                                "per_user": FACTS["counts"]["per_user"]})
    assert layers.read_metric("fe_cg_steps_per_iter", older) is None
    monkeypatch.setattr(layers, "read_metric", lambda name, facts: {
        "fe_update_ms": 900.0}.get(name))
    from benchmark.readers import module_work_share

    spec = layers.metric_file("fe_tron_roofline")
    assert module_work_share.read(spec["params"], older) is None
    assert layers.read_metric("fe_tron_iters_per_fit", {}) is None


def test_fe_tron_roofline_is_the_reads_the_counts_need_over_the_update_span(
        monkeypatch):
    # (2 solves + 8 iterations + 72 CG steps) reads of 4.29 GB at 819 GB/s
    monkeypatch.setattr(layers, "read_metric", lambda name, facts: {
        "fe_update_ms": 900.0}.get(name))
    from benchmark.readers import module_work_share

    spec = layers.metric_file("fe_tron_roofline")
    share = module_work_share.read(spec["params"], FACTS)
    least_ms = 82 * (1 << 22) * 256 * 4 / 819e9 * 1e3
    assert share == pytest.approx(100.0 * least_ms / 900.0, rel=1e-6)
    assert work_tron.fit_tron_reads(FACTS)["bytes"] == 82 * (1 << 22) * 256 * 4
    # no span in the trace: nothing, not 0
    monkeypatch.setattr(layers, "read_metric", lambda name, facts: None)
    assert module_work_share.read(spec["params"], FACTS) is None


def test_a_tiny_traced_fit_reads_every_new_metric_and_the_share_stays_under_100(
        monkeypatch):
    """Each new metric file reads a number from a tiny traced fit's facts;
    the share's time is the update span's, which no pass of the program can
    beat, so it stays under 100 % (its device time here a stand-in: a CPU
    run times no device)."""
    from benchmark import tracing
    from benchmark.traffic import fit_loop_tron

    monkeypatch.setattr(tracing.Tracer, "start", lambda self: None)
    captured = {}
    real = fit_loop_tron.run

    def keep(ctx):
        out = real(ctx)
        captured.update(out["facts"], device_kind="TPU v5 lite")
        return out

    monkeypatch.setattr(fit_loop_tron, "run", keep)
    _run(trace=True)
    fixed = captured["counts"]["global"]
    for metric in ("fe_tron_iters_per_fit", "fe_cg_steps_per_iter"):
        assert layers.read_metric(metric, captured) > 0
    # the update's time at the least the program's own passes take
    passes_ms = (fixed["evals"] * ROWS * D_FIX * 4 / 819e9) * 1e3
    monkeypatch.setattr(layers, "read_metric", lambda name, facts: {
        "fe_update_ms": passes_ms}.get(name))
    from benchmark.readers import module_work_share

    share = module_work_share.read(layers.metric_file("fe_tron_roofline")["params"],
                                   captured)
    assert 0 < share <= 100.0
