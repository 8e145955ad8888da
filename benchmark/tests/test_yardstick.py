"""The yardstick's own arithmetic: work counts by hand, the peaks table, the
open-loop schedule and percentile, the reducer on a recorded trace, and
``BENCHMARK.json`` against the files it names."""

import json
import os
import re

import numpy as np
import pytest

from benchmark import layers, reduce, work
from benchmark.tests import tiny
from benchmark.traffic import open_loop_score as ols

HERE = os.path.dirname(os.path.abspath(__file__))


# ---- work.py, by hand -------------------------------------------------------


def test_fe_solve_work_by_hand():
    # 1000 rows x 10 columns, 3 value-and-gradient evaluations = 6 passes:
    # 6 x 2 x 1000 x 10 flops, 6 reads of 1000 x 10 x 4 bytes.
    w = work.fe_solve(1000, 10, 3, "objective_evals")
    assert w == dict(flops=120000.0, bytes=240000.0, passes=6)
    # margin-space L-BFGS reports passes directly
    assert work.fe_solve(1000, 10, 6, "x_passes") == w


def test_re_newton_system_work_by_hand():
    # 100 rows of width 4, 2 iterations an entity: per row and iteration
    # H costs 2*4*4 and g 2*4 flops; the slab row and its two factors are read.
    w = work.re_newton_system(100, 4, 2.0)
    assert w["flops"] == 100 * 2 * (32 + 8)
    assert w["bytes"] == 100 * 2 * (4 + 2) * 4
    whole = work.re_solve(100, 5, 4, 2.0)
    assert whole["flops"] == w["flops"] + 100 * 2 * 8 + 5 * 2 * (64 / 3 + 32)


def test_score_batch_work_by_hand():
    # 8 rows, fixed 6 + random 2 columns: 2*8*8 flops; in 8*8 floats, the
    # gathered rows 8*2, the fixed vector 6, out 8.
    w = work.score_batch(8, {"global": 6, "per_user": 2}, ("per_user",))
    assert w == dict(flops=128.0, bytes=(64 + 16 + 6 + 8) * 4)


def test_roofline_says_which_bound():
    peak = work.peaks("TPU v5 lite")
    t, bound = work.least_seconds(1e6, 819e9, peak)
    assert bound == "bytes" and t == pytest.approx(1.0)
    t, bound = work.least_seconds(197e12 * 2, 8, peak)
    assert bound == "flops" and t == pytest.approx(2.0)


def test_peaks_table_raises_on_an_unknown_device_kind():
    with pytest.raises(KeyError, match="no peaks for device kind 'cpu'"):
        work.peaks("cpu")
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_fit_counts_from_facts():
    facts = dict(rows=1000, dims={"global": 10, "per_user": 4}, counts={
        "global": dict(type="fixed", passes=2, evals=6, eval_unit="x_passes"),
        "per_user": dict(type="random", passes=2, entities=5,
                         newton_iterations=10.0)})
    assert work.fit_fe_solve(facts)["flops"] == 120000.0
    assert work.fit_re_newton_system(facts) == work.re_newton_system(1000, 4, 2.0)
    total = work.fit_total(facts)
    assert total["flops"] == (120000.0 + 2 * 2 * 1000 * 10 + 2 * 2 * 1000 * 4
                              + work.re_solve(1000, 5, 4, 2.0)["flops"])


def test_solver_counts_are_read_per_fit():
    """The counts that tell a flipped iteration from a gain: summed over the
    coordinates of one type; silent when the run reported none."""
    counts = {
        "global": dict(type="fixed", evals=24, iterations=10),
        "per_user": dict(type="random", entities=8, newton_iterations=80.0,
                         max_iterations=14),
        "per_item": dict(type="random", entities=2, newton_iterations=30.0,
                         max_iterations=17)}
    facts = dict(counts=counts)
    assert layers.read_metric("fe_evals_per_fit", facts) == 24.0
    assert layers.read_metric("re_newton_iters_per_fit", facts) == 10.0 + 15.0
    assert layers.read_metric("re_newton_max_iters_per_fit", facts) == 31.0
    assert layers.read_metric("fe_evals_per_fit", dict(counts=None)) is None
    assert layers.read_metric("re_newton_max_iters_per_fit",
                              dict(counts={"global": counts["global"]})) is None


# ---- the open loop ----------------------------------------------------------


def test_schedule_same_gaps_for_every_seed_in_another_order():
    a, b = ols.schedule(1000.0, 2.0, 1), ols.schedule(1000.0, 2.0, 2**31 + 5)
    assert len(a) == len(b) == 2000
    # the gap before the first request is the time left after the last one
    ga = np.sort(np.append(np.diff(a), 2.0 - a[-1]))
    gb = np.sort(np.append(np.diff(b), 2.0 - b[-1]))
    np.testing.assert_allclose(ga, gb, rtol=0, atol=1e-9)
    assert not np.allclose(a, b)
    assert a[0] == 0 and a[-1] < 2.0 and np.all(np.diff(a) > 0)
    # exponential gaps: the mean is 1/rate, the coefficient of variation ~1
    gaps = np.diff(a)
    assert gaps.mean() == pytest.approx(1e-3, rel=0.02)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.1)


def test_percentile_counts_failed_requests_as_slowest():
    lat = np.array([1.0] * 94 + [np.inf] * 6)
    assert ols.percentile(lat, 0.5) == 1.0
    assert ols.percentile(lat, 0.95) == np.inf      # 6 % failed: p95 is a failure
    lat = np.array([1.0] * 96 + [np.inf] * 4)
    assert ols.percentile(lat, 0.95) == 1.0


def test_latency_is_timed_from_the_due_time(monkeypatch):
    """A generator that runs late must not read as a fast server: with the
    send held back, latency grows by the delay and gen_late reports it."""
    real_sleep = ols.time.sleep
    calls = {"n": 0}

    def slow_sleep(d):
        calls["n"] += 1
        real_sleep(d + (0.2 if calls["n"] == 150 else 0.0))   # one 200 ms stall

    monkeypatch.setattr(ols.time, "sleep", slow_sleep)
    from benchmark import run

    result = run.run_cell(tiny.bench(), "serve.glmix2", seed=3, seconds=1.0,
                          trace=True, device_block=tiny.CPU,
                          overrides=dict(config=tiny.shrink_config("glmix2-logistic"),
                                         traffic=dict(tiny.SERVE, warm_requests=64)))
    # ~80 requests (20 %) were due during the stall: they were sent late,
    # and their latency counts the wait.
    assert result["metrics"]["gen_late_ms"]["value"] > 50.0
    calls["n"] = 0
    plain = run.run_cell(tiny.bench(), "serve.glmix2", seed=3, seconds=1.0,
                         trace=False, device_block=tiny.CPU,
                         overrides=dict(config=tiny.shrink_config("glmix2-logistic"),
                                        traffic=dict(tiny.SERVE, warm_requests=64)))
    assert plain["metrics"]["score_p95_ms"]["value"] > 50.0


def test_zipf_ids_stay_in_range_and_are_skewed():
    rng = np.random.default_rng(0)
    ids = ols.zipf_ids(rng, 20000, 3 << 25, 1.1)
    assert ids.min() >= 0 and ids.max() < 3 << 25
    _, counts = np.unique(ids, return_counts=True)
    assert counts.max() > 0.05 * len(ids)          # rank 1 takes ~1/zeta share


# ---- the reducer --------------------------------------------------------------


def test_reducer_on_synthetic_events():
    dev = [dict(name="/device:TPU:0",
                modules=[("jit_a(1)", 1.0, 0.5), ("jit_b(2)", 2.0, 0.25)],
                ops=[("fusion.1", 1.0, 0.2), ("kern", 1.3, 0.2), ("fusion.2", 2.0, 0.25)])]
    t = reduce.reduce_events(dev, [("bench/fit", 0.9, 1.6), ("bench/reference", 5.0, 1.0)])
    assert t.window == (0.9, 2.5)
    assert t.busy_s == pytest.approx(0.65)
    assert t.modules == {"jit_a": (1, 0.5), "jit_b": (1, 0.25)}
    assert [l.ops for l in t.launches] == [("fusion.1", "kern"), ("fusion.2",)]
    gaps = dict((n, s) for n, s in t.top_gaps(10))
    assert gaps["bench/fit: after jit_a before jit_b"] == pytest.approx(0.5)
    assert gaps["bench/fit: inside jit_a"] == pytest.approx(0.1)
    assert t.top_ops(1) == [["fusion.2", 0.25]]


def test_reducer_on_the_recorded_trace():
    """One GameEstimator.fit (glmix3 at 2^17 rows, 256 users, 16 items) as a
    TPU v5 lite recorded it (PR 26's probe), cut to the device's module and
    op lines and the host's ``bench/fit`` annotation. The busy time was
    checked by an independent sweep over the op events when it was recorded."""
    t = reduce.reduce(os.path.join(HERE, "data", "fit_small.xplane.pb"))
    assert t.annotations == [("bench/fit", pytest.approx(0.477781498),
                              pytest.approx(0.30272874))]
    assert t.window == (pytest.approx(0.477781498), pytest.approx(0.780510238))
    assert t.busy_s == pytest.approx(0.043165967, rel=1e-9)
    assert len(t.launches) == 741
    count, seconds = t.modules["jit_traced"]
    assert count == 18 and seconds == pytest.approx(0.034776127, rel=1e-6)
    facts = dict(trace=t, traced_fits=1)
    assert layers.read_metric("dispatches_per_fit", facts) == 741.0
    assert layers.read_metric("fe_solve_ms", facts) == pytest.approx(4.508624)
    assert layers.read_metric("re_solve_ms", facts) == pytest.approx(30.267503)
    assert layers.read_metric("re_newton_kernel_ms", facts) == pytest.approx(6.783159)
    assert layers.read_metric("device_idle.fit", facts) == pytest.approx(85.741041)
    name, seconds = t.top_gaps(1)[0]
    assert name.startswith("bench/fit: after jit_") and 0.002 < seconds < 0.003
    ops = t.top_ops(10)
    assert len(ops) == 10 and all(len(n) <= 80 for n, _ in ops)
    assert any("tpu_custom_call" in n or "Cholesky" in n for n, _ in ops)
    # 16 solver launches hold the Mosaic kernel (RE), 2 do not (FE): 2 passes
    # x (1 FE + 4 blocks of users + 4 blocks of items).
    held = [l for l in t.launches if l.name == "jit_traced"
            and any("tpu_custom_call" in op for op in l.ops)]
    assert len(held) == 16


def test_short_op_names():
    assert reduce.short_op(
        '%body.9 = (f32[96,128,128]{2,1,0}) custom-call(f32[96,512,128]{2,1,0} %pad.17), '
        'custom_call_target="tpu_custom_call", frontend_attributes={}'
    ) == "body.9 custom-call tpu_custom_call"
    assert reduce.short_op("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop") \
        == "fusion.3 fusion"
    assert reduce.short_op("plain-name") == "plain-name"


def test_module_readers_tell_launches_apart_by_the_kernel_they_hold():
    dev = [dict(name="/device:TPU:0",
                modules=[("jit_traced(1)", 0.0, 1.0), ("jit_traced(2)", 2.0, 3.0),
                         ("jit_other(3)", 6.0, 1.0)],
                ops=[("fusion", 0.0, 1.0), ('%body.9 = custom-call(), custom_call_target="tpu_custom_call"', 2.0, 2.0),
                     ("fusion", 4.0, 1.0), ("fusion", 6.0, 1.0)])]
    facts = dict(trace=reduce.reduce_events(dev, []), traced_fits=2)
    assert layers.read_metric("fe_solve_ms", facts) == pytest.approx(500.0)
    assert layers.read_metric("re_solve_ms", facts) == pytest.approx(1500.0)
    assert layers.read_metric("re_newton_kernel_ms", facts) == pytest.approx(1000.0)
    assert layers.read_metric("dispatches_per_fit", facts) == pytest.approx(1.5)
    assert layers.read_metric("device_idle.fit", facts) == pytest.approx(100 * 2 / 7)
    # With no launch holding the kernel, FE cannot be told from RE: silence.
    dev[0]["ops"] = [("fusion", 0.0, 1.0)]
    facts = dict(trace=reduce.reduce_events(dev, []), traced_fits=2)
    assert layers.read_metric("fe_solve_ms", facts) is None
    assert layers.read_metric("re_solve_ms", facts) is None


# ---- BENCHMARK.json and the files it names -----------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_benchmark_json_names_files_that_exist_and_keeps_to_the_contract():
    bench = tiny.bench()
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert os.path.exists(os.path.join(tiny.ROOT, c["file"]))
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and len(w["why"]) <= 200 and w["chips"] == 1
        path = os.path.join(tiny.ROOT, "benchmark", "workloads", w["traffic"] + ".json")
        with open(path) as f:
            traffic = json.load(f)
        assert os.path.exists(os.path.join(tiny.ROOT, "benchmark", "traffic",
                                           traffic["kind"] + ".py"))
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert NAME.match(m["name"]) and m["moves"] in e2e
        spec = layers.metric_file(m["name"])
        assert os.path.exists(os.path.join(tiny.ROOT, "benchmark", "readers",
                                           spec["reader"] + ".py"))
        for cell in m.get("workloads", []):
            assert cell in cells
            assert cell in e2e[m["moves"]].get("workloads", cells)
    assert len(json.dumps(bench)) < 64 * 1024


# ---- the data ------------------------------------------------------------------


def test_every_seed_gets_the_same_rows_with_the_entities_renamed():
    from benchmark import data

    a = data.make_glmix(1, 2048, 8, {"per_user": (4, 8)})
    b = data.make_glmix(2**31 + 5, 2048, 8, {"per_user": (4, 8)})
    again = data.make_glmix(1, 2048, 8, {"per_user": (4, 8)})
    ia, ib = np.asarray(a[2]["per_user"]), np.asarray(b[2]["per_user"])
    assert np.array_equal(ia, np.asarray(again[2]["per_user"]))   # same seed, same inputs
    assert not np.array_equal(ia, ib)                              # another seed, other names
    assert np.array_equal(np.asarray(a[0]), np.asarray(b[0]))      # the same rows, same order
    assert np.array_equal(np.asarray(a[3]), np.asarray(b[3]))
    assert np.all(np.asarray(a[0])[:, 0] == 1.0)
    # a renaming: rows that shared an entity still do
    pairs = set(zip(ia.tolist(), ib.tolist()))
    assert len(pairs) == len(set(ia.tolist())) == len(set(ib.tolist())) == 8
    # control.py --fresh-rows: rows from the seed, the same for the same seed
    fresh = data.make_glmix(1, 2048, 8, {"per_user": (4, 8)}, fresh_rows=True)
    fresh2 = data.make_glmix(1, 2048, 8, {"per_user": (4, 8)}, fresh_rows=True)
    assert not np.array_equal(np.asarray(fresh[0]), np.asarray(a[0]))
    assert np.array_equal(np.asarray(fresh[0]), np.asarray(fresh2[0]))
