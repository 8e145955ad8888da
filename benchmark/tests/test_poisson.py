"""The Poisson / elastic-net fit cell on the CPU: its generator beside
``fit_uniform``'s, the cell's control and each of its faults coming out not correct,
the window rehearsed through ``run.run_cell``, and the new metrics read
from recorded facts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import (compare_poisson, control_poisson, data, data_poisson, layers,
                       program, program_enet, run)
from benchmark.reference import glmix_poisson_enet as reference
from benchmark.tests import tiny

CELL, CONFIG = "fit.glmix2-poisson", "glmix2-poisson-enet"
USERS, ROWS, D_FIX, D_RE = 64, 1 << 13, 24, 4
TRUTH = dict(intercept=-1.0, support=6, norm=0.5, re_scale=0.125)
LIMITS = {"fixed_gap": 5e-4, "random_gap": 9e-4, "random_row_gap": 4e-3,
          "support_diff": 0}
FIT = dict(rows=ROWS, entities={"per_user": USERS}, trace_fits=2, truth=TRUTH,
           limits=LIMITS)


def tiny_config() -> dict:
    """The configuration at the tiny widths, its reg_weight scaled with the
    root of the rows (8192 at 2^22 rows is 362 at 2^13): a null feature's
    gradient grows with the root, so l1 stays 2.7 of its standard deviations
    and zeroes the null features here as it does there."""
    config = tiny.shrink_config(CONFIG, D_FIX, D_RE)
    for c in config["coordinates"]:
        if c["type"] == "fixed":
            c["reg_weight"] = c["reg_weight"] * (ROWS / (1 << 22)) ** 0.5
    return config


@pytest.fixture(scope="module", autouse=True)
def _drop_this_modules_programs():
    yield   # as test_ragged: the programs compiled here go when it is done
    jax.clear_caches()


def _run(trace=False, seed=2**31 + 17):
    return run.run_cell(tiny.bench(), CELL, seed=seed, seconds=0.5, trace=trace,
                        device_block=tiny.CPU,
                        overrides=dict(config=tiny_config(), traffic=FIT))


@pytest.fixture(scope="module")
def fitted():
    """The tiny data set, its configuration and the reference's model."""
    config = dict(tiny_config(), cd_passes=2)
    xf, shards, ids, y = data_poisson.make_glmix(
        5, ROWS, D_FIX, {"per_user": (D_RE, USERS)}, TRUTH)
    entities = {"per_user": USERS}
    ref = reference.fit(config, xf, shards, ids, y, entities)
    return config, (xf, shards, ids, y, entities), ref


# ---- the generator -------------------------------------------------------------


def test_generator_keeps_fit_uniforms_rows_and_draws_counts():
    xf, shards, ids, y = data_poisson.make_glmix(
        5, ROWS, D_FIX, {"per_user": (D_RE, USERS)}, TRUTH)
    uxf, ushards, uids, _ = data.make_glmix(5, ROWS, D_FIX,
                                            {"per_user": (D_RE, USERS)})
    assert jnp.array_equal(xf, uxf)
    assert jnp.array_equal(shards["per_user"], ushards["per_user"])
    assert jnp.array_equal(ids["per_user"], uids["per_user"])
    counts = np.asarray(y)
    assert counts.dtype == np.float32 and np.array_equal(counts, np.round(counts))
    assert counts.min() == 0 and counts.max() >= 2       # counts, not clicks
    assert 0.35 < counts.mean() < 0.65                   # exp(-1 + 0.5·0.5) ≈ 0.47
    # another seed renames the users and keeps rows and labels
    xf2, _, ids2, y2 = data_poisson.make_glmix(
        6, ROWS, D_FIX, {"per_user": (D_RE, USERS)}, TRUTH)
    assert jnp.array_equal(xf, xf2) and jnp.array_equal(y, y2)
    assert not jnp.array_equal(ids["per_user"], ids2["per_user"])


def test_truth_is_sparse_with_the_stated_norm():
    w = np.asarray(data_poisson.fixed_truth(data.root_key(3), 256, -1.0, 64, 0.5))
    assert w[0] == -1.0 and np.count_nonzero(w[1:]) == 64
    assert np.linalg.norm(w[1:]) == pytest.approx(0.5, rel=1e-6)
    assert set(np.abs(w[1:][w[1:] != 0]).round(6)) == {0.0625}


# ---- the reference ---------------------------------------------------------------


def test_reference_without_l1_is_plain_newton_and_has_no_zeros(fitted):
    config, (xf, shards, ids, y, entities), _ = fitted
    smooth = reference.fit(control_poisson.pure_l2(config), xf, shards, ids, y,
                           entities)
    assert compare_poisson.zero_share(config, smooth) == 0.0


def test_control_is_not_correct_by_the_cells_limits(fitted):
    config, (xf, shards, ids, y, entities), ref = fitted
    got = reference.fit(config, xf, shards, ids, y, entities, control=True)
    gaps = compare_poisson.model_gaps(config, got, ref)
    assert any(gaps[k] > LIMITS[k] for k in LIMITS), gaps


# ---- the cell, rehearsed ---------------------------------------------------------


@pytest.mark.parametrize("trace", [False, True])
def test_poisson_cell_runs_and_is_correct(trace, monkeypatch):
    from benchmark import tracing

    # as test_ragged: the profiler's own session is rehearsed in test_rehearsal
    monkeypatch.setattr(tracing.Tracer, "start", lambda self: None)
    result = _run(trace)
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["checks"]) == {
        "fixed_gap", "random_gap", "random_row_gap", "support_diff",
        "diverged_users", "compiles_in_window"}
    fixed = result["counts"]["global"]
    assert fixed["eval_unit"] == "objective_evals"
    assert fixed["evals"] > fixed["iterations"] > 0
    if trace:
        assert {"fe_owlqn_iters_per_fit", "fe_evals_per_iter", "fe_nonzeros",
                "fe_evals_per_fit", "re_newton_iters_per_fit", "re_blocks_per_pass",
                "re_pad_rows_share", "prepare_s"} <= set(result["metrics"])
        assert result["metrics"]["fe_evals_per_iter"]["value"] >= 1.0
        assert 1 < result["metrics"]["fe_nonzeros"]["value"] < D_FIX
    else:
        assert set(result["metrics"]) == {"fit_s", "setup_s"}


# ---- faults: correct has to come out false ------------------------------------------


def test_state_left_unchanged_is_not_correct(monkeypatch):
    real = program.fit_once

    def unchanged(estimator, batch, opt):
        model, tracker = real(estimator, batch, opt)
        return {k: jnp.zeros_like(v) for k, v in model.items()}, tracker

    monkeypatch.setattr(program, "fit_once", unchanged)
    result = _run()
    assert not result["correct"]
    assert result["checks"]["fixed_gap"]["value"] == pytest.approx(1.0)
    assert result["checks"]["support_diff"]["value"] >= TRUTH["support"]


def _fit_with(change):
    """``program_enet.build_fit`` given ``change(config, xf, shards, ids, y)``."""
    real = program_enet.build_fit

    def build(config, xf, shards, ids, y, entities):
        return real(*change(config, xf, shards, ids, y), entities)

    return build


def _failing(result):
    return {k for k, c in result["checks"].items() if not c["value"] <= c["limit"]}


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    half = lambda c, xf, sh, ids, y: (  # noqa: E731
        c, xf[:ROWS // 2], {k: v[:ROWS // 2] for k, v in sh.items()},
        {k: v[:ROWS // 2] for k, v in ids.items()}, y[:ROWS // 2])
    monkeypatch.setattr(program_enet, "build_fit", _fit_with(half))
    assert {"fixed_gap", "random_gap"} <= _failing(_run())


def test_l1_part_dropped_is_not_correct(monkeypatch):
    """The same reg_weight as pure L2: no coefficient is zero any more."""
    smooth = lambda c, *data_: (control_poisson.pure_l2(c), *data_)  # noqa: E731
    monkeypatch.setattr(program_enet, "build_fit", _fit_with(smooth))
    failing = _failing(_run())
    assert "support_diff" in failing or "fixed_gap" in failing, failing


def test_labels_clipped_to_clicks_is_not_correct(monkeypatch):
    clipped = lambda c, xf, sh, ids, y: (c, xf, sh, ids, jnp.minimum(y, 1.0))  # noqa: E731
    monkeypatch.setattr(program_enet, "build_fit", _fit_with(clipped))
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


def test_a_quarantined_user_is_not_correct(monkeypatch):
    monkeypatch.setattr(program_enet, "quarantined", lambda config, tracker: 1)
    result = _run()
    assert not result["correct"]
    assert _failing(result) == {"diverged_users"}


# ---- support_diff and the new metrics, from recorded facts ---------------------------


def test_support_diff_counts_zeros_on_one_side_only_above_the_floor():
    want = jnp.asarray([-1.0, 0.0, 0.0, 0.5, 0.0005, 0.2])
    got = jnp.asarray([-1.0, 0.002, 0.0005, 0.0, 0.0, 0.2])
    # index 1: zero in the reference, 2e-3 > 1e-3·1.0 here; index 3: the reverse;
    # indices 2 and 4 differ by less than the floor and do not count
    assert compare_poisson.support_diff(got, want) == 2
    assert compare_poisson.support_diff(want, want) == 0


FACTS = dict(
    rows=1 << 22, dims={"global": 256, "per_user": 16}, traced_fits=3,
    device_kind="TPU v5 lite",
    counts={"global": dict(type="fixed", passes=2, evals=60, iterations=40,
                           eval_unit="objective_evals"),
            "per_user": dict(type="random", passes=2, entities=8192,
                             newton_iterations=8192 * 9.0, max_iterations=24)},
    registry_after=[dict(metric="fe_nonzero_coefficients", type="gauge", value=66,
                         stats=None, labels=dict(coordinate="global"))],
)


@pytest.mark.parametrize("metric,want", [
    ("fe_owlqn_iters_per_fit", 40.0),
    ("fe_evals_per_iter", 1.5),
    ("fe_nonzeros", 66.0),
])
def test_new_counts_read_recorded_facts(metric, want):
    assert layers.read_metric(metric, FACTS) == pytest.approx(want)


def test_new_counts_are_silent_on_a_program_without_them():
    # the parent publishes no gauge; a cell without counts reads nothing
    assert layers.read_metric("fe_nonzeros", dict(registry_after=[])) is None
    assert layers.read_metric("fe_owlqn_iters_per_fit", {}) is None
    assert layers.read_metric("fe_evals_per_iter", {}) is None


def test_fe_update_roofline_is_the_bytes_of_the_evaluations_over_the_update_span(
        monkeypatch):
    # 60 evaluations × 2 passes × 4.29 GB ÷ 819 GB/s = 629.3 ms least; 900 ms busy
    monkeypatch.setattr(layers, "read_metric", lambda name, facts: {
        "fe_update_ms": 900.0}.get(name))
    from benchmark.readers import work_share

    spec = layers.metric_file("fe_update_roofline")
    share = work_share.read(spec["params"], FACTS)
    least_ms = 60 * 2 * (1 << 22) * 256 * 4 / 819e9 * 1e3
    assert share == pytest.approx(100.0 * least_ms / 900.0, rel=1e-6)
    # no span in the trace (a program without it): nothing, not 0
    monkeypatch.setattr(layers, "read_metric", lambda name, facts: None)
    assert work_share.read(spec["params"], FACTS) is None
