"""The tracing seam: every ``Tracer.span`` holds a
``jax.profiler.TraceAnnotation("photon/<path>")`` open for its life, so the
program's spans land on the profiler's clock. Here the annotation class is
replaced by a recorder; what a real profile shows is the benchmark's side
(``benchmark/tests/test_spans.py``)."""

import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.data.game_data import GameBatch
from photon_tpu.data.random_effect import bucket_dim
from photon_tpu.estimators.config import (
    FixedEffectCoordinateConfig,
    GameOptimizationConfig,
    RandomEffectCoordinateConfig,
    RegularizationConfig,
)
from photon_tpu.estimators.game_estimator import GameEstimator
from photon_tpu.models.coefficients import Coefficients
from photon_tpu.models.game import (
    FixedEffectModel,
    GameModel,
    RandomEffectModel,
)
from photon_tpu.models.glm import GeneralizedLinearModel
from photon_tpu.obs import TELEMETRY_SCHEMA, registry
from photon_tpu.obs import trace as obs_trace
from photon_tpu.obs.report import validate_record
from photon_tpu.serve import ScoreRequest, ServeConfig, ServingEngine
from photon_tpu.types import TaskType

COORDINATES = ("global", "per_user")
D_FIX, D_RE, N_ENTITIES = 6, 4, 32


class Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs every enter and
    exit with the thread it happened on."""

    log = []
    # Reentrant: the collection hook (obs/host.py) enters an annotation from
    # inside whatever code starts a collection, this class's own included.
    lock = threading.RLock()

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        with Recorder.lock:
            Recorder.log.append(("enter", self.name, threading.get_ident(), None))
        return self

    def __exit__(self, exc_type, exc, tb):
        with Recorder.lock:
            Recorder.log.append(("exit", self.name, threading.get_ident(), exc_type))
        return False


@pytest.fixture()
def recorder(monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    Recorder.log = []
    return Recorder


def check_nesting(log):
    """Every enter has its exit, innermost first, per thread. Returns the
    finished annotations as ``(name, depth, exc_type)`` in order of entry."""
    stacks, entered, closed = {}, [], {}
    for kind, name, tid, exc_type in log:
        stack = stacks.setdefault(tid, [])
        if kind == "enter":
            entered.append((len(entered), name, len(stack)))
            stack.append(entered[-1][0])
        else:
            assert stack, f"exit of {name} with nothing open"
            i = stack.pop()
            assert entered[i][1] == name, f"{name} closed over {entered[i][1]}"
            closed[i] = exc_type
    assert all(not s for s in stacks.values()), "an annotation was left open"
    return [(name, depth, closed[i]) for i, name, depth in entered]


def tiny_fit_inputs():
    rng = np.random.default_rng(5)
    n = 256
    xf = rng.normal(size=(n, D_FIX)).astype(np.float32)
    xr = rng.normal(size=(n, D_RE)).astype(np.float32)
    xf[:, 0] = xr[:, 0] = 1.0
    users = rng.integers(0, 8, size=n).astype(np.int32)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    batch = GameBatch(
        label=jnp.asarray(y), offset=jnp.zeros(n, jnp.float32),
        weight=jnp.ones(n, jnp.float32),
        features={"global": jnp.asarray(xf), "per_user": jnp.asarray(xr)},
        entity_ids={"userId": jnp.asarray(users)},
    )
    est = GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_configs=[
            FixedEffectCoordinateConfig("global", "global"),
            RandomEffectCoordinateConfig("per_user", "userId", "per_user"),
        ],
        num_iterations=1,
        intercept_indices={"global": 0, "per_user": 0},
        num_entities={"userId": 8},
    )
    cfg = GameOptimizationConfig(reg={
        cid: RegularizationConfig(weight=1.0) for cid in COORDINATES})
    return est, batch, cfg


@pytest.fixture()
def fit_log(recorder):
    est, batch, cfg = tiny_fit_inputs()
    est.fit(batch, optimization_configs=[cfg])
    return check_nesting(recorder.log)


@pytest.mark.parametrize("cid", COORDINATES)
def test_fit_opens_the_coordinate_update_with_its_children_in_order(fit_log, cid):
    names = [n for n, _, _ in fit_log]
    (update,) = [n for n in names if n.endswith(f"/cd/iter0/{cid}")]
    assert update.startswith("photon/")
    depth = {n: d for n, d, _ in fit_log}
    children = [n for n in names
                if n.startswith(update + "/") and depth[n] == depth[update] + 1]
    assert [c[len(update) + 1:] for c in children] == [
        "exchange", "solve", "score", "exchange"]


def test_fit_opens_prepare_with_host_copy_and_group(fit_log):
    names = [n for n, _, _ in fit_log]
    (prepare,) = [n for n in names if n.endswith("/prepare")]
    # Every coordinate is planned before any is filled: the fill needs the
    # batch's layout, which the plans decide.
    assert [n[len(prepare):] for n in names if n.startswith(prepare + "/")] == [
        "/host_copy", "/group", "/group/per_user", "/group/per_user/plan",
        "/group/per_user", "/group/per_user/fill"]
    # the solver's spans reach the profile through the same seam
    assert any(n.endswith("/solve/fe_solve") for n in names)
    assert any(n.endswith("/solve/re_dispatch_blocks") for n in names)


def test_fit_closes_every_annotation_on_an_exception(recorder, monkeypatch):
    from photon_tpu.algorithm.random_effect import RandomEffectCoordinate

    class Boom(RuntimeError):
        pass

    def train(self, *args, **kwargs):
        raise Boom("solver failed")

    monkeypatch.setattr(RandomEffectCoordinate, "train", train)
    est, batch, cfg = tiny_fit_inputs()
    with pytest.raises(Boom):
        est.fit(batch, optimization_configs=[cfg])
    finished = check_nesting(recorder.log)      # nothing left open
    failed = {n for n, _, exc_type in finished if exc_type is Boom}
    assert any(n.endswith("/cd/iter0/per_user/solve") for n in failed)
    assert any(n.endswith("/cd/iter0/per_user") for n in failed)
    # the span records are written all the same
    assert any(s.name.endswith("cd/iter0/per_user/solve")
               for s in obs_trace.get_spans())


def make_engine(**cfg):
    rng = np.random.default_rng(3)
    model = GameModel({
        "global": FixedEffectModel(
            GeneralizedLinearModel(
                Coefficients(np.linspace(-1, 1, D_FIX).astype(np.float32)),
                TaskType.LOGISTIC_REGRESSION),
            "shardA"),
        "per_user": RandomEffectModel(
            rng.normal(size=(N_ENTITIES, D_RE)).astype(np.float32),
            "userId", "shardB", TaskType.LOGISTIC_REGRESSION),
    })
    defaults = dict(max_batch_size=8, max_delay_ms=1.0, hot_bytes=1 << 20)
    defaults.update(cfg)
    return ServingEngine(model, config=ServeConfig(**defaults))


def requests(n):
    rng = np.random.default_rng(9)
    return [ScoreRequest(
        {"shardA": rng.normal(size=D_FIX).astype(np.float32),
         "shardB": rng.normal(size=D_RE).astype(np.float32)},
        {"userId": int(i % N_ENTITIES)}) for i in range(n)]


def test_engine_build_opens_warm_up_with_its_children(recorder):
    make_engine().close()
    names = [n for n, _, _ in check_nesting(recorder.log)]
    assert [n for n in names if n.startswith("photon/serve/warm_up")] == [
        "photon/serve/warm_up", "photon/serve/warm_up/store_build",
        # the table is pinned (hot_bytes covers it): its upload, fenced
        "photon/serve/warm_up/store_build/table_upload",
        "photon/serve/warm_up/warm_uploads",
        "photon/serve/warm_up/transformer_warm_up"]


@pytest.mark.parametrize("child", ["score/assemble", "score/h2d", "score/launch",
                                   "score/d2h", "respond"])
def test_a_served_batch_opens_the_host_path_spans(recorder, child):
    eng = make_engine()
    recorder.log = []
    futures = [eng.submit(r) for r in requests(3)]
    for f in futures:
        f.result(timeout=30)
    eng.close()
    names = [n for n, _, _ in check_nesting(recorder.log)]
    batches = [n for n in names if n == "photon/serve/batch"]
    assert batches
    assert names.count(f"photon/serve/batch/{child}") == len(batches)
    order = [n[len("photon/serve/batch/"):] for n in names
             if n.startswith("photon/serve/batch/")]
    per_batch = ["score", "score/assemble", "score/h2d", "score/launch",
                 "score/d2h", "respond"]
    assert order == per_batch * len(batches)


def test_serve_h2d_bytes_is_the_padded_batch(recorder):
    eng = make_engine()
    hist = registry().histogram("serve_h2d_bytes")
    count0, sum0 = hist.count, hist.sum
    n = 3
    eng._score_batch(requests(n))
    eng.close()
    rows = bucket_dim(n)
    # features of both shards, label, offset, weight (float32), one int32 id
    padded = rows * 4 * (D_FIX + D_RE + 3 + 1)
    assert hist.count - count0 == 1
    assert hist.sum - sum0 == padded


@pytest.mark.parametrize("name", ["serve_batch_fill", "serve_batches_total",
                                  "serve_warmup_traces"])
def test_instruments_nothing_read_are_gone(name):
    eng = make_engine()
    for f in [eng.submit(r) for r in requests(2)]:
        f.result(timeout=30)
    eng.close()
    assert name not in {rec["metric"] for rec in registry().snapshot()}
    assert "serve_batch_rows" in {rec["metric"] for rec in registry().snapshot()}


def test_record_stays_host_only(recorder):
    obs_trace.record_span("external", 0.25)
    # A collection meanwhile holds its own annotation and leaves its own
    # root span (obs/host.py); the recorded span opened none.
    assert [e for e in recorder.log if not e[1].startswith("photon/host/")] == []
    own = [s for s in obs_trace.get_spans() if not s.name.startswith("host/")]
    assert own[-1].name.endswith("external")


def test_without_jax_spans_are_recorded_and_nothing_is_imported():
    code = (
        "import sys\n"
        "from photon_tpu.obs.trace import span, get_spans\n"
        "before = set(sys.modules)\n"
        "with span('a') as outer:\n"
        "    with span('b') as inner:\n"
        "        pass\n"
        "assert (outer, inner) == ('a', 'a/b'), (outer, inner)\n"
        "assert [s.name for s in get_spans()] == ['a/b', 'a']\n"
        "assert set(sys.modules) == before, sorted(set(sys.modules) - before)\n"
        "assert 'jax' not in sys.modules\n"
        "import types\n"
        "log = []\n"
        "class A:\n"
        "    def __init__(self, name): log.append(name)\n"
        "    def __enter__(self): return self\n"
        "    def __exit__(self, *exc): return False\n"
        "fake = types.ModuleType('jax.profiler'); fake.TraceAnnotation = A\n"
        "sys.modules['jax.profiler'] = fake\n"
        "with span('c'):\n"
        "    pass\n"
        "assert log == ['photon/c'], log\n"
        "print('ok')\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "ok"


def test_span_records_and_schema_are_unchanged():
    assert TELEMETRY_SCHEMA["span"] == {
        "record": (str,), "name": (str,), "parent": (str, type(None)),
        "start_s": (int, float), "duration_s": (int, float), "thread": (str,),
    }
    with obs_trace.span("outer"):
        with obs_trace.span("inner") as path:
            pass
    rec = next(s for s in reversed(obs_trace.get_spans()) if s.name == path)
    as_dict = rec.as_dict()
    assert list(as_dict) == ["record", "name", "parent", "start_s", "duration_s",
                             "thread"]
    assert as_dict["name"] == "outer/inner" and as_dict["parent"] == "outer"
    validate_record(as_dict)
