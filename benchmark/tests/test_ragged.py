"""The heavy-tailed fit cell on the CPU: the layout-free reference against
the padded one, the cell's control and each of its faults coming out not
correct, the window rehearsed through ``run.run_cell``, and the block plan's
metrics read from a recorded registry snapshot."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare, control_ragged, data, data_ragged, layers, program, run
from benchmark.reference import glmix, glmix_ragged
from benchmark.tests import tiny

CELL, CONFIG = "fit.glmix2-zipf", "glmix2-logistic-zipf"
USERS = 64
FIT = dict(rows=1 << 13, entities={"per_user": USERS}, trace_fits=2,
           limits={"fixed_gap": 5e-4, "random_gap": 5e-4, "random_row_gap": 5e-3})


@pytest.fixture(scope="module", autouse=True)
def _drop_this_modules_programs():
    # The programs this module compiled go when it is done. With them held,
    # the serving cell's timer-stopped trace in test_yardstick overran its 1 s
    # window and stopped twice in 3 of 3 runs of the whole directory; dropped,
    # 0 of 3 (the race itself is ``tracing.Tracer.stop``'s: see
    # test_zipf_cell_runs_and_is_correct).
    yield
    jax.clear_caches()


def _run(trace=False, seed=2**31 + 13):
    return run.run_cell(tiny.bench(), CELL, seed=seed, seconds=0.5, trace=trace,
                        device_block=tiny.CPU,
                        overrides=dict(config=tiny.shrink_config(CONFIG),
                                       traffic=FIT))


def _tiny_data(seed=5, law=None):
    config = dict(tiny.shrink_config(CONFIG), cd_passes=2)
    laws = {"per_user": law} if law else {}
    xf, shards, ids, y = data_ragged.make_glmix(
        seed, FIT["rows"], 24, {"per_user": (4, USERS)}, laws)
    return config, xf, shards, ids, y, {"per_user": USERS}


# ---- the generator -------------------------------------------------------------


def test_generator_keeps_fit_uniforms_rows_and_follows_the_law():
    _, xf, shards, ids, _y, _ = _tiny_data(law=dict(kind="zipf", exponent=1.0))
    uxf, ushards, uids, _uy = data.make_glmix(5, FIT["rows"], 24,
                                              {"per_user": (4, USERS)})
    assert jnp.array_equal(xf, uxf)
    assert jnp.array_equal(shards["per_user"], ushards["per_user"])
    # with no law the id column is data.make_glmix's too
    _, _, _, same_ids, _, _ = _tiny_data()
    assert jnp.array_equal(same_ids["per_user"], uids["per_user"])
    counts = np.sort(np.bincount(np.asarray(ids["per_user"]), minlength=USERS))[::-1]
    share = 1.0 / np.sum(1.0 / np.arange(1, USERS + 1))
    assert counts[0] == pytest.approx(share * FIT["rows"], rel=0.1)
    assert counts[0] > 20 * np.median(counts)
    # another seed renames the users and keeps rows and counts
    _, xf2, _, ids2, _, _ = _tiny_data(seed=6, law=dict(kind="zipf", exponent=1.0))
    assert jnp.array_equal(xf, xf2)
    assert not jnp.array_equal(ids["per_user"], ids2["per_user"])
    counts2 = np.sort(np.bincount(np.asarray(ids2["per_user"]), minlength=USERS))
    assert np.array_equal(counts2[::-1], counts)


# ---- the two references ----------------------------------------------------------


def test_ragged_reference_agrees_with_the_padded_one_on_uniform_data():
    config, xf, shards, ids, y, entities = _tiny_data()
    want = glmix.fit(config, xf, shards, ids, y, entities)
    got = glmix_ragged.fit(config, xf, shards, ids, y, entities)
    for cid in want:
        assert compare.rel_gap(got[cid], want[cid]) <= 1e-6, cid
    assert compare.row_gap(got["per_user"], want["per_user"]) <= 1e-5


def test_ragged_control_is_not_correct_by_the_cells_limits():
    config, xf, shards, ids, y, entities = _tiny_data(
        law=dict(kind="zipf", exponent=1.0))
    want = glmix_ragged.fit(config, xf, shards, ids, y, entities)
    got = glmix_ragged.fit(config, xf, shards, ids, y, entities, control=True)
    gaps = compare.model_gaps(config, got, want)
    assert gaps["fixed_gap"] > FIT["limits"]["fixed_gap"]
    assert gaps["random_gap"] > FIT["limits"]["random_gap"]


# ---- the cell, rehearsed ---------------------------------------------------------


@pytest.mark.parametrize("trace", [False, True])
def test_zipf_cell_runs_and_is_correct(trace, monkeypatch):
    # The traced line's metrics are read with the profiler's own session left
    # out: ``tracing.Slice`` is fit_loop's and is rehearsed with a session in
    # test_rehearsal, and one more session in this process makes the serving
    # cell's timer-stopped trace (test_yardstick) overrun its 1 s window and
    # stop twice (``Tracer.stop`` sets ``stopped`` after ``stop_trace``
    # returns: a ``benchmark`` PR's to close).
    from benchmark import tracing

    monkeypatch.setattr(tracing.Tracer, "start", lambda self: None)
    result = _run(trace)
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["checks"]) == {"fixed_gap", "random_gap", "random_row_gap",
                                     "compiles_in_window"}
    users = result["counts"]["per_user"]
    # the iterations work.fit_total reads are weighted by rows; the mean user's stay beside them
    assert users["newton_iterations_by_entity"] > 0
    assert users["newton_iterations"] != users["newton_iterations_by_entity"]
    if trace:
        assert {"re_pad_rows_share", "re_blocks_per_pass", "re_geometries",
                "fe_evals_per_fit", "re_newton_iters_per_fit",
                "prepare_s"} <= set(result["metrics"])
        assert 0 < result["metrics"]["re_pad_rows_share"]["value"] <= 35.0
        assert result["metrics"]["re_blocks_per_pass"]["value"] >= 4
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
    assert result["checks"]["random_gap"]["value"] == pytest.approx(1.0)


def _fit_on(rows_of):
    """``program.build_fit`` given only the rows ``rows_of(ids)`` keeps."""
    real = program.build_fit

    def build(config, xf, shards, ids, y, entities):
        keep = rows_of(ids["per_user"])
        return real(config, *control_ragged.take(keep, xf, shards, ids, y), entities)

    return build


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    monkeypatch.setattr(program, "build_fit",
                        _fit_on(lambda ids: slice(0, ids.shape[0] // 2)))
    checks = _run()["checks"]
    assert checks["fixed_gap"]["value"] > checks["fixed_gap"]["limit"]
    assert checks["random_gap"]["value"] > checks["random_gap"]["limit"]


def test_largest_users_rows_capped_is_not_correct(monkeypatch):
    """The guarantee that every row of every user trains: the heaviest user
    cut to its first 64 rows moves that user's model alone."""
    monkeypatch.setattr(
        program, "build_fit",
        _fit_on(lambda ids: jnp.asarray(control_ragged.cap_largest(ids, 64))))
    result = _run()
    checks = result["checks"]
    assert not result["correct"]
    assert checks["random_row_gap"]["value"] > checks["random_row_gap"]["limit"]


@pytest.mark.parametrize("cid", ["global", "per_user"])
def test_one_coefficient_altered_is_not_correct(monkeypatch, cid):
    real = program.fit_once

    def altered(estimator, batch, opt):
        model, tracker = real(estimator, batch, opt)
        model = dict(model)
        model[cid] = model[cid].at[(0,) * model[cid].ndim].add(0.05)
        return model, tracker

    monkeypatch.setattr(program, "fit_once", altered)
    assert not _run()["correct"]


# ---- the block plan's metrics, from a recorded snapshot ------------------------------

SNAPSHOT = [
    dict(metric="bucket_used_total", type="counter", value=3000.0, stats=None,
         labels=dict(re_type="userId", dim="samples")),
    dict(metric="bucket_alloc_total", type="counter", value=4000.0, stats=None,
         labels=dict(re_type="userId", dim="samples")),
    dict(metric="bucket_used_total", type="counter", value=1000.0, stats=None,
         labels=dict(re_type="itemId", dim="samples")),
    dict(metric="bucket_alloc_total", type="counter", value=1000.0, stats=None,
         labels=dict(re_type="itemId", dim="samples")),
    dict(metric="bucket_used_total", type="counter", value=7.0, stats=None,
         labels=dict(re_type="userId", dim="entities")),
    dict(metric="bucket_alloc_total", type="counter", value=8.0, stats=None,
         labels=dict(re_type="userId", dim="entities")),
    dict(metric="re_blocks", type="gauge", value=19, stats=None,
         labels=dict(coordinate="per_user")),
    dict(metric="re_blocks", type="gauge", value=4, stats=None,
         labels=dict(coordinate="per_item")),
    dict(metric="re_block_geometries", type="gauge", value=19, stats=None,
         labels=dict(coordinate="per_user")),
    dict(metric="re_block_geometries", type="gauge", value=2, stats=None,
         labels=dict(coordinate="per_item")),
    dict(metric="serve_h2d_bytes", type="histogram", value=None,
         stats=dict(count=3, sum=9.0), labels={}),
]


@pytest.mark.parametrize("metric,want", [
    ("re_pad_rows_share", 20.0),      # 1 − (3000 + 1000) ÷ (4000 + 1000), rows only
    ("re_blocks_per_pass", 23.0),
    ("re_geometries", 21.0),
])
def test_plan_metrics_read_a_recorded_snapshot(metric, want):
    facts = dict(registry_after=SNAPSHOT)
    assert layers.read_metric(metric, facts) == pytest.approx(want)
    # a program that publishes none of it (an older one) reads nothing, and does not raise
    assert layers.read_metric(metric, dict(registry_after=[])) is None


def test_plan_metrics_read_the_live_registry_where_no_snapshot_was_taken():
    _run()   # builds a data set in this process
    assert layers.read_metric("re_blocks_per_pass", {}) >= 1
    assert 0 <= layers.read_metric("re_pad_rows_share", {}) < 50


# ---- the launches that gather a block's solve inputs ----------------------------

def test_block_inputs_metric_reads_its_launches_and_is_silent_without_them():
    from benchmark import reduce

    dev = [dict(name="/device:TPU:0",
                modules=[("jit__block_inputs(7)", 0.0, 1.0), ("jit_traced(1)", 1.0, 4.0),
                         ("jit__block_inputs(7)", 5.0, 2.0), ("jit_gather(3)", 8.0, 1.0)],
                ops=[("fusion.1", 0.0, 1.0), ("fusion", 1.0, 4.0),
                     ("fusion.1", 5.0, 2.0), ("fusion", 8.0, 1.0)])]
    facts = dict(trace=reduce.reduce_events(dev, []), traced_fits=2)
    assert layers.read_metric("re_block_inputs_ms", facts) == pytest.approx(1500.0)
    # the score path's gathers stay re_gather_ms's
    assert layers.read_metric("re_gather_ms", facts) == pytest.approx(500.0)
    # a program that gathers in launches of their own (before PR 31) reads nothing
    dev[0]["modules"] = [m for m in dev[0]["modules"] if "block_inputs" not in m[0]]
    facts = dict(trace=reduce.reduce_events(dev, []), traced_fits=2)
    assert layers.read_metric("re_block_inputs_ms", facts) is None
    assert layers.read_metric("re_block_inputs_ms", dict(traced_fits=2)) is None
