"""The readers of the program's own spans: ``trace_spans`` (the profile's
``photon/*`` events against the device's operations) on synthetic events and
on a profile a TPU v5 lite recorded, and ``span_ring`` (the in-memory ring)."""

import os
import types

import pytest

from benchmark import layers, reduce
from benchmark.readers import span_ring, trace_spans

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "fit_small_spans.xplane.pb")

# One device: busy 1.0–1.2 and 1.5–2.0; launches start at 1.0, 1.5 and 2.5.
DEVICES = [dict(busy=[(1.0, 1.2), (1.5, 2.0)], launch_starts=[1.0, 1.5, 2.5])]
TRAIN = "game-estimator/train[x]/"
SPANS = [
    (TRAIN + "cd/iter0/global", 0.9, 1.6),
    (TRAIN + "cd/iter0/global/solve", 0.95, 1.3),
    (TRAIN + "cd/iter0/per_user", 1.6, 2.2),
    (TRAIN + "cd/iter0/per_item", 2.2, 2.4),
    ("serve/batch", 2.45, 2.55),
    ("serve/batch", 2.6, 2.9),
]
COUNTS = {"global": dict(type="fixed"), "per_user": dict(type="random"),
          "per_item": dict(type="random")}


def facts_for(window, **more):
    trace = types.SimpleNamespace(window=window, window_s=window[1] - window[0])
    return dict(trace=trace, trace_path="synthetic", counts=COUNTS,
                traced_fits=2, **more)


@pytest.fixture()
def synthetic(monkeypatch):
    monkeypatch.setattr(trace_spans, "load", lambda path: (DEVICES, SPANS))
    return facts_for((0.0, 3.0))


@pytest.mark.parametrize("params,want", [
    (dict(coordinate_type="fixed", value="busy_ms"), 300.0),       # 0.2 + 0.1
    (dict(coordinate_type="fixed", value="idle_ms"), 400.0),       # 0.7 - 0.3
    (dict(coordinate_type="fixed", value="wall_ms"), 700.0),
    (dict(coordinate_type="fixed", value="launches"), 2.0),
    (dict(coordinate_type="random", value="busy_ms"), 400.0),      # 1.6–2.0
    (dict(coordinate_type="random", value="busy_ms", per="traced_fits"), 200.0),
    (dict(coordinate_type="random", value="launches"), 0.0),
    (dict(span=r"(^|/)cd/iter\d+/[^/]+$", value="idle_ms"), 800.0),  # 1.5 - 0.7
    (dict(span="/batch$", value="wall_ms", per="span"), 200.0),    # (0.1 + 0.3) / 2
    (dict(span="/batch$", value="share"), 100.0 * 0.4 / 3.0),
    (dict(span="/batch$", value="launches"), 1.0),                 # the one at 2.5
])
def test_trace_spans_on_synthetic_events(synthetic, params, want):
    assert trace_spans.read(params, synthetic) == pytest.approx(want)


def test_a_span_cut_by_the_windows_edge_counts_only_inside(monkeypatch):
    monkeypatch.setattr(trace_spans, "load", lambda path: (DEVICES, SPANS))
    facts = facts_for((1.1, 1.9))
    read = trace_spans.read
    # the fixed update 0.9–1.6 is cut to 1.1–1.6: busy 1.1–1.2 and 1.5–1.6
    assert read(dict(coordinate_type="fixed", value="busy_ms"), facts) == pytest.approx(200.0)
    assert read(dict(coordinate_type="fixed", value="wall_ms"), facts) == pytest.approx(500.0)
    assert read(dict(coordinate_type="fixed", value="launches"), facts) == 1.0
    # per_user 1.6–2.2 is cut to 1.6–1.9; per_item lies outside
    assert read(dict(coordinate_type="random", value="wall_ms", per="span"),
                facts) == pytest.approx(300.0)
    assert read(dict(span="/batch$", value="wall_ms"), facts) is None


@pytest.mark.parametrize("params", [
    dict(span="/no/such/span$", value="busy_ms"),
    dict(span="/no/such/span$", value="share"),
    dict(coordinate_type="fixed", value="busy_ms"),     # no counts: no ids
])
def test_no_match_reads_nothing_never_zero(monkeypatch, params):
    monkeypatch.setattr(trace_spans, "load", lambda path: (DEVICES, SPANS))
    facts = facts_for((0.0, 3.0))
    if "coordinate_type" in params:
        facts["counts"] = None
    assert trace_spans.read(params, facts) is None


def test_without_a_device_plane_device_numbers_stay_silent(monkeypatch):
    monkeypatch.setattr(trace_spans, "load", lambda path: ([], SPANS))
    facts = facts_for((0.0, 3.0))
    assert trace_spans.read(dict(coordinate_type="fixed", value="busy_ms"), facts) is None
    assert trace_spans.read(dict(coordinate_type="fixed", value="launches"), facts) is None
    assert trace_spans.read(dict(span="/batch$", value="wall_ms"), facts) == pytest.approx(400.0)
    # and without a trace at all
    assert trace_spans.read(dict(span="/batch$", value="wall_ms"),
                            dict(trace=None, trace_path=None)) is None


def test_overlap_of_two_interval_lists():
    assert trace_spans.overlap([(0, 1), (2, 3)], [(0.5, 2.5)]) == pytest.approx(1.0)
    assert trace_spans.overlap([(0, 1)], [(1, 2)]) == 0.0
    assert trace_spans.overlap([], [(1, 2)]) == 0.0


@pytest.mark.parametrize("metric,want", [
    ("fe_update_ms", 150.0), ("re_update_ms", 200.0),
    ("fe_update_dispatches_per_fit", 1.0), ("re_update_dispatches_per_fit", 0.0),
    ("update_idle_ms", 400.0), ("serve_batch_ms", 200.0),
    ("flush_thread_busy", 100.0 * 0.4 / 3.0),
])
def test_the_metric_files_read_through_the_reader(synthetic, metric, want):
    assert layers.read_metric(metric, synthetic) == pytest.approx(want)


# ---- a profile recorded on the chip ------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    """One GameEstimator.fit (glmix3 at 2^17 rows, 256 users, 16 items) with
    PR 27's spans, as a TPU v5 lite recorded it, cut to the device's module
    and op lines and the host's ``bench/fit`` and ``photon/*`` events."""
    trace = reduce.reduce(RECORDED)
    counts = {"global": dict(type="fixed"), "per_user": dict(type="random"),
              "per_item": dict(type="random")}
    return dict(trace=trace, trace_path=RECORDED, counts=counts, traced_fits=1)


def test_recorded_profile_splits_device_time_by_coordinate(recorded):
    trace = recorded["trace"]
    fe = layers.read_metric("fe_update_ms", recorded)
    re_ = layers.read_metric("re_update_ms", recorded)
    busy_ms = trace.busy_s * 1e3
    assert fe == pytest.approx(5.621182) and re_ == pytest.approx(33.561698)
    # the updates hold the fit's device time: nothing of weight runs outside
    assert 0.97 * busy_ms <= fe + re_ <= busy_ms * (1 + 1e-9)
    # by interval, so at least what the launch-name split sees
    assert fe >= layers.read_metric("fe_solve_ms", recorded)
    assert re_ >= layers.read_metric("re_solve_ms", recorded)
    inside = (layers.read_metric("fe_update_dispatches_per_fit", recorded)
              + layers.read_metric("re_update_dispatches_per_fit", recorded))
    assert inside == 15.0 + 591.0
    assert layers.read_metric("dispatches_per_fit", recorded) == 743.0
    idle = layers.read_metric("update_idle_ms", recorded)
    assert 0 < idle <= (trace.window_s - trace.busy_s) * 1e3
    assert layers.read_metric("re_gather_ms", recorded) > 0


def test_recorded_profile_has_each_update_once_a_pass_with_its_children(recorded):
    _, spans = trace_spans.load(RECORDED)
    lo, hi = recorded["trace"].window
    # the profile also holds the fit before (``bench-warm/fit``), outside the window
    assert len([p for p, _, _ in spans if p.endswith("/cd/iter0/global")]) == 2
    paths = [p for p, a, b in spans if lo <= a and b <= hi]
    for it in (0, 1):
        for cid in ("global", "per_user", "per_item"):
            (update,) = [p for p in paths if p.endswith(f"/cd/iter{it}/{cid}")]
            kids = [p[len(update) + 1:] for p in paths
                    if p.startswith(update + "/") and "/" not in p[len(update) + 1:]]
            assert kids == ["exchange", "solve", "score", "exchange"]
    assert len([p for p in paths if p.endswith("/prepare")]) == 0   # a warm fit


# ---- the ring -----------------------------------------------------------------


def test_span_ring_sums_matching_spans_and_is_silent_after_a_drop(monkeypatch):
    from photon_tpu.obs import trace as obs_trace

    ring = obs_trace.Tracer(max_spans=4)
    monkeypatch.setattr(obs_trace, "_TRACER", ring)
    ring.record("serve/warm_up", 40.0, parent="")
    ring.record("store_build", 30.0, parent="serve/warm_up")
    ring.record("prepare", 2.0, parent="game-estimator/prepare-datasets")
    read = span_ring.read
    assert read(dict(span="(^|/)serve/warm_up$"), {}) == pytest.approx(40.0)
    assert read(dict(span="(^|/)prepare$"), {}) == pytest.approx(2.0)
    assert layers.read_metric("engine_build_s", {}) == pytest.approx(40.0)
    assert layers.read_metric("prepare_s", {}) == pytest.approx(2.0)
    assert read(dict(span="/no/such$"), {}) is None
    ring.record("batch", 0.003, parent="serve")
    ring.record("batch", 0.003, parent="serve")   # the fifth: the ring sheds one
    assert ring.dropped_spans == 1
    assert read(dict(span="(^|/)prepare$"), {}) is None
