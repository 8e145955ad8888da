"""A warm fit makes no device round trip outside its coordinate updates.

Three contracts, each counted on the CPU (launches and reads are counts, not
times): ``CoordinateDescent.run`` builds its optimisation summary only for a
log that shows it; reading a tracker (``summary()`` / ``diagnostics_dict()``)
is ONE ``jax.device_get`` and no eager ``jnp`` launch, with the text and the
numbers of the op-by-op form it replaced; a second ``GameEstimator.fit`` on
the same batch reads no block back from the device.
"""

import contextlib
import logging
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.algorithm import (
    CoordinateDescent,
    FixedEffectCoordinate,
    RandomEffectCoordinate,
)
from photon_tpu.algorithm.coordinate_descent import CoordinateDescentResult
from photon_tpu.algorithm.random_effect import RandomEffectTrackerStats
from photon_tpu.data.game_data import GameBatch
from photon_tpu.data.random_effect import (
    RandomEffectDataConfig,
    build_random_effect_dataset,
)
from photon_tpu.ops import GLMObjective, LogisticLoss
from photon_tpu.optim.common import (
    REASON_DIVERGED,
    REASON_FUNCTION_VALUES_CONVERGED,
    REASON_GRADIENT_CONVERGED,
    REASON_MAX_ITERATIONS,
    OptimizeResult,
)
from photon_tpu.optim.factory import OptimizerSpec
from photon_tpu.types import TaskType

N, D_FIX, D_RE, E = 1024, 6, 3, 12
CD_LOGGER = "photon_tpu.algorithm.coordinate_descent"


# --- what is watched ---------------------------------------------------------


class _Compiles(logging.Handler):
    """Names of the programs JAX compiles while open (``jit(<name>)``), in
    order, with the marks the test drops between them. The caches are cleared
    on entry, so every eager primitive applied inside shows up here."""

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.names = []

    def emit(self, record):
        m = re.match(r"Compiling (jit\([^)]*\))", record.getMessage())
        if m:
            self.names.append(m.group(1))

    def mark(self, name):
        self.names.append(name)


@contextlib.contextmanager
def compiles():
    watcher = _Compiles()
    jax_logger = logging.getLogger("jax")
    jax.clear_caches()
    jax_logger.addHandler(watcher)
    try:
        with jax.log_compiles():
            yield watcher
    finally:
        jax_logger.removeHandler(watcher)


@contextlib.contextmanager
def device_gets(monkeypatch):
    """Counts the calls of ``jax.device_get`` while open."""
    calls = []
    real = jax.device_get

    def counted(x):
        calls.append(x)
        return real(x)

    with monkeypatch.context() as m:
        m.setattr(jax, "device_get", counted)
        yield calls


class _NumpyWatch:
    """``numpy`` as a module of the program sees it, with ``asarray`` and
    ``array`` noting the device arrays they are given: on the CPU those two
    copy through the buffer protocol, past every Python method of the array."""

    def __init__(self, read):
        self._read = read

    def __getattr__(self, name):
        return getattr(np, name)

    def _noting(self, convert):
        def noted(x, *args, **kwargs):
            if isinstance(x, jax.Array):
                self._read.append(x)
            return convert(x, *args, **kwargs)

        return noted

    @property
    def asarray(self):
        return self._noting(np.asarray)

    @property
    def array(self):
        return self._noting(np.array)


@contextlib.contextmanager
def host_reads(monkeypatch):
    """Every device array whose value the host reads while open: ``int()``,
    ``float()`` and ``jax.device_get`` go through the array type's
    ``__array__`` or ``_value``, ``np.asarray`` through the estimator
    layer's ``np``."""
    import photon_tpu.algorithm.fixed_effect
    import photon_tpu.algorithm.random_effect
    import photon_tpu.data.random_effect
    import photon_tpu.estimators.game_estimator

    array_type = type(jnp.zeros(()))
    read = []
    real_array, real_value = array_type.__array__, array_type._value

    def spy_array(self, *args, **kwargs):
        read.append(self)
        return real_array(self, *args, **kwargs)

    def spy_value(self):
        read.append(self)
        return real_value.fget(self)

    with monkeypatch.context() as m:
        m.setattr(array_type, "__array__", spy_array)
        m.setattr(array_type, "_value", property(spy_value))
        for module in (photon_tpu.algorithm.fixed_effect,
                       photon_tpu.algorithm.random_effect,
                       photon_tpu.data.random_effect,
                       photon_tpu.estimators.game_estimator):
            if getattr(module, "np", None) is np:
                m.setattr(module, "np", _NumpyWatch(read))
        yield read


# --- the data ----------------------------------------------------------------


@pytest.fixture(scope="module")
def glmix():
    rng = np.random.default_rng(34)
    Xf = rng.normal(size=(N, D_FIX)).astype(np.float32)
    Xf[:, 0] = 1.0
    Xr = rng.normal(size=(N, D_RE)).astype(np.float32)
    Xr[:, 0] = 1.0
    users = rng.integers(0, E, size=N).astype(np.int32)
    w_fix = rng.normal(size=D_FIX).astype(np.float32)
    w_users = rng.normal(scale=2.0, size=(E, D_RE)).astype(np.float32)
    logits = Xf @ w_fix + np.sum(Xr * w_users[users], axis=1)
    y = (rng.uniform(size=N) < 1 / (1 + np.exp(-logits))).astype(np.float32)
    batch = GameBatch(
        label=jnp.asarray(y),
        offset=jnp.zeros(N, jnp.float32),
        weight=jnp.ones(N, jnp.float32),
        features={"global": jnp.asarray(Xf), "per_user": jnp.asarray(Xr)},
        entity_ids={"userId": jnp.asarray(users)},
    )
    return batch, Xr, users, y


def coordinate_descent(glmix):
    batch, Xr, users, y = glmix
    fixed = FixedEffectCoordinate(
        "global", "global", TaskType.LOGISTIC_REGRESSION,
        GLMObjective(loss=LogisticLoss, l2_weight=1.0, intercept_index=0),
        OptimizerSpec(),
    )
    ds = build_random_effect_dataset(
        users, Xr, y, np.ones(N, np.float32), E,
        RandomEffectDataConfig(re_type="userId", feature_shard="per_user"),
    )
    rand = RandomEffectCoordinate(
        "per_user", ds, TaskType.LOGISTIC_REGRESSION,
        GLMObjective(loss=LogisticLoss, l2_weight=0.5, intercept_index=0),
    )
    return CoordinateDescent(
        {"global": fixed, "per_user": rand}, ["global", "per_user"],
        num_iterations=2,
    ), rand


def tracked_result():
    loss = np.full((11,), 2.5, np.float32)
    loss[:4] = [10.0, 5.0, 3.0, 2.5]
    gnorm = np.full((11,), 1e-3, np.float32)
    gnorm[:4] = [4.0, 1.0, 0.125, 1e-3]
    return OptimizeResult(
        w=jnp.zeros((3,)), value=jnp.float32(2.5), grad_norm=jnp.float32(1e-3),
        iterations=jnp.int32(3), reason_code=jnp.int32(2),
        loss_history=jnp.asarray(loss), grad_norm_history=jnp.asarray(gnorm),
        evals=jnp.int32(9), eval_unit="x_passes",
    )


def untracked_result():
    return OptimizeResult(
        w=jnp.zeros((3,)), value=jnp.float32(0.75), grad_norm=jnp.float32(2e-4),
        iterations=jnp.int32(7), reason_code=jnp.int32(1),
        loss_history=jnp.asarray([0.75], jnp.float32),
        grad_norm_history=jnp.asarray([2e-4], jnp.float32),
    )


def padded_tracker(samples=False):
    """Four entities and two padding lanes: two converged (one by function
    values, one by gradient), one at max iterations, one quarantined; the
    padding lanes carry codes and counts that must not be counted."""
    return RandomEffectTrackerStats(
        iterations=jnp.asarray([3, 5, 100, 2, 9, 7], jnp.int32),
        reasons=jnp.asarray(
            [REASON_FUNCTION_VALUES_CONVERGED, REASON_GRADIENT_CONVERGED,
             REASON_MAX_ITERATIONS, REASON_DIVERGED, 0,
             REASON_GRADIENT_CONVERGED], jnp.int32),
        valid=jnp.asarray([True, True, True, True, False, False]),
        samples=jnp.asarray([10, 20, 400, 5, 0, 0], jnp.int32) if samples else None,
    )


# The text and the numbers of the op-by-op readers these replaced (PR 33's
# tree, the same five objects).
_RE_DICT = dict(
    type="random_effect", entities=4, converged=2, hit_max_iter=1,
    quarantined=1, mean_iterations=27.5, max_iterations=100,
)
_RE_LINE = "entities=4 converged=2 hit_max_iter=1 quarantined=1 iters(mean=27.5, max=100)"
READERS = {
    "tracked_history": (
        tracked_result,
        "iter    loss           |grad|\n"
        "   0    1.000000e+01   4.000000e+00\n"
        "   1    5.000000e+00   1.000000e+00\n"
        "   2    3.000000e+00   1.250000e-01\n"
        "   3    2.500000e+00   1.000000e-03\n"
        "reason: FUNCTION_VALUES_CONVERGED",
        dict(type="fixed_effect", iterations=3, value=2.5,
             grad_norm=0.0010000000474974513,
             reason="FUNCTION_VALUES_CONVERGED", converged=True, evals=9,
             eval_unit="x_passes"),
    ),
    "history_not_tracked": (
        untracked_result,
        "iterations=7 value=7.500000e-01 |grad|=2.000000e-04 "
        "reason: MAX_ITERATIONS (history not tracked)",
        dict(type="fixed_effect", iterations=7, value=0.75,
             grad_norm=0.00019999999494757503, reason="MAX_ITERATIONS",
             converged=False, evals=0, eval_unit="objective_evals"),
    ),
    "empty_tracker": (
        RandomEffectTrackerStats.empty,
        "entities=0 converged=0 hit_max_iter=0 quarantined=0 iters(mean=0.0, max=0)",
        dict(type="random_effect", entities=0, converged=0, hit_max_iter=0,
             quarantined=0, mean_iterations=0.0, max_iterations=0,
             row_weighted_iterations=None),
    ),
    "tracker_without_samples": (
        padded_tracker, _RE_LINE, dict(_RE_DICT, row_weighted_iterations=None),
    ),
    "tracker_with_samples": (
        lambda: padded_tracker(samples=True), _RE_LINE,
        dict(_RE_DICT, row_weighted_iterations=92.27586364746094),
    ),
}


# --- (c) one transfer a read, the same text and numbers ------------------------


@pytest.mark.parametrize("method", ["summary", "diagnostics_dict"])
@pytest.mark.parametrize("case", sorted(READERS))
def test_a_read_is_one_device_get_and_no_launch(case, method, monkeypatch):
    build, text, numbers = READERS[case]
    diag = build()
    jax.block_until_ready(jax.tree_util.tree_leaves(diag))
    with compiles() as compiled, device_gets(monkeypatch) as gets:
        got = getattr(diag, method)()
    assert len(gets) == 1
    assert compiled.names == []
    assert got == (text if method == "summary" else numbers)
    if method == "diagnostics_dict":
        assert list(got) == list(numbers)  # the report's key order


def _jnp_aggregates(t):
    """The seven aggregates as ``jnp`` computed them, launch by launch."""
    conv = (t.reasons == REASON_FUNCTION_VALUES_CONVERGED) | (
        t.reasons == REASON_GRADIENT_CONVERGED
    )
    masked = jnp.where(t.valid, t.iterations, 0)
    weighted = None
    if t.samples is not None:
        rows = jnp.where(t.valid, t.samples, 0).astype(jnp.float32)
        weighted = float(
            jnp.sum(rows * t.iterations.astype(jnp.float32))
            / jnp.maximum(jnp.sum(rows), 1.0)
        )
    return dict(
        num_entities=int(jnp.sum(t.valid)),
        num_converged=int(jnp.sum(conv & t.valid)),
        num_max_iter=int(jnp.sum((t.reasons == REASON_MAX_ITERATIONS) & t.valid)),
        num_quarantined=int(jnp.sum((t.reasons == REASON_DIVERGED) & t.valid)),
        mean_iterations=float(
            jnp.sum(masked.astype(jnp.float32))
            / jnp.maximum(jnp.sum(t.valid), 1)
        ),
        max_iterations=int(jnp.max(masked)) if t.iterations.shape[0] else 0,
        row_weighted_iterations=weighted,
    )


@pytest.mark.parametrize("samples", [False, True], ids=["no_samples", "samples"])
def test_tracker_properties_equal_their_jnp_forms(samples, monkeypatch):
    tracker = padded_tracker(samples=samples)
    want = _jnp_aggregates(tracker)
    assert want["num_quarantined"] == 1 and want["num_max_iter"] == 1
    for name, value in want.items():
        with device_gets(monkeypatch) as gets:
            got = getattr(tracker, name)
        assert len(gets) == 1, name
        assert got == value and type(got) is type(value), name


def test_a_read_leaves_the_pytree_alone_and_takes_numpy_rows():
    tracker = padded_tracker(samples=True)
    tracker.summary()
    leaves = jax.tree_util.tree_leaves(tracker)
    assert len(leaves) == 4 and all(isinstance(x, jax.Array) for x in leaves)
    # A checkpoint round trip hands the readers numpy rows: same answers.
    on_host = jax.tree_util.tree_map(np.asarray, tracker)
    assert on_host.diagnostics_dict() == tracker.diagnostics_dict()


# --- (a) no summary, and no launch after the last update, for a quiet log -----


def test_quiet_log_builds_no_summary_and_launches_nothing_after_the_last_update(
    glmix, monkeypatch, caplog
):
    cd, rand = coordinate_descent(glmix)

    def never(self):
        raise AssertionError("summary() built for a log that will not show it")

    monkeypatch.setattr(CoordinateDescentResult, "summary", never)
    updates = cd.num_iterations * len(cd.update_sequence)
    with caplog.at_level(logging.WARNING, logger="photon_tpu"), compiles() as seen:
        scored = []
        real_score = rand.score

        def score(model, batch):
            out = real_score(model, batch)
            scored.append(1)
            if 2 * len(scored) == updates:  # the last update's score
                seen.mark("LAST UPDATE")
            return out

        monkeypatch.setattr(rand, "score", score)
        result = cd.run(glmix[0])
    assert seen.names.count("LAST UPDATE") == 1
    after = seen.names[seen.names.index("LAST UPDATE") + 1:]
    # The closing exchange's subtract and add may compile here; a reader's
    # index, slice, reduction or comparison may not.
    forbidden = {"jit(dynamic_slice)", "jit(squeeze)", "jit(_reduce_max)",
                 "jit(_reduce_sum)", "jit(true_divide)", "jit(equal)",
                 "jit(convert_element_type)", "jit(_where)"}
    assert not forbidden & set(after), after
    assert not [r for r in caplog.records if "optimization summary" in r.getMessage()]
    assert len(result.tracker["global"]) == len(result.tracker["per_user"]) == 2


# --- (b) at INFO the log's text is what it was ----------------------------------

# This file's data (seed 34), the wall times masked. The layout is PR 33's.
# The numbers after the first fixed-effect solve are PR 37's: since then the
# fixed effect's scores are the margins its solver carried, which differ from
# ``compute_score``'s in the last bits, and at this size that moves a user's
# Newton loop by an iteration (a fresh ``X @ w`` in their place moves it too).
# The last line is PR 38's: a user whose rejected step sat one ulp above its
# objective ended two iterations later, once the damping had flattened the
# step (mean 3.6, max 6); the Newton loop now ends such a user at the reject.
SUMMARY_AT_PR33 = """\
-- coordinate 'global', CD pass 0 (wall W)
   iter    loss           |grad|
      0    7.097831e+02   2.303395e+02
      1    6.031868e+02   6.696330e+00
      2    6.030665e+02   1.544837e+00
      3    6.030588e+02   1.794025e-01
      4    6.030588e+02   4.637405e-02
   reason: FUNCTION_VALUES_CONVERGED
-- coordinate 'global', CD pass 1 (wall W)
   iter    loss           |grad|
      0    3.225063e+02   7.274036e+01
      1    3.065534e+02   4.209774e+01
      2    2.992480e+02   5.105512e+00
      3    2.991182e+02   5.402415e-01
      4    2.991170e+02   5.354795e-02
      5    2.991169e+02   6.353940e-03
      6    2.991169e+02   1.054558e-03
      7    2.991169e+02   1.054558e-03
   reason: FUNCTION_VALUES_CONVERGED
-- coordinate 'per_user', CD pass 0 (wall W)
   entities=12 converged=12 hit_max_iter=0 quarantined=0 iters(mean=5.8, max=16)
-- coordinate 'per_user', CD pass 1 (wall W)
   entities=12 converged=12 hit_max_iter=0 quarantined=0 iters(mean=3.4, max=4)"""

_FLOAT = re.compile(r"\d\.\d{6}e[+-]\d\d")


def test_info_log_carries_the_summary_text_of_pr33(glmix, caplog):
    cd, _rand = coordinate_descent(glmix)
    with caplog.at_level(logging.INFO, logger="photon_tpu"):
        result = cd.run(glmix[0])
    (record,) = [r for r in caplog.records
                 if r.name == CD_LOGGER and "optimization summary" in r.getMessage()]
    head, text = record.getMessage().split("\n", 1)
    assert head == "optimization summary:"
    assert text == result.summary()
    text = re.sub(r"\(wall \d+\.\d{3}s\)", "(wall W)", text)
    # Layout, counts and reasons to the letter; the table's floats to 1e-4
    # (an L-BFGS on another CPU may round its last digits otherwise).
    assert _FLOAT.sub("F", text) == _FLOAT.sub("F", SUMMARY_AT_PR33)
    np.testing.assert_allclose(
        [float(x) for x in _FLOAT.findall(text)],
        [float(x) for x in _FLOAT.findall(SUMMARY_AT_PR33)],
        rtol=1e-4,
    )


def test_profile_event_payload_is_the_tracker_summary(glmix):
    """The per-update event under ``profile`` carries the same text as the
    tracker reads later."""
    from photon_tpu.utils.events import EventEmitter

    cd, _rand = coordinate_descent(glmix)
    events = []
    emitter = EventEmitter()
    emitter.register(events.append)
    result = cd.run(glmix[0], emitter=emitter)
    logs = [e.payload for e in events if e.name == "PhotonOptimizationLogEvent"]
    assert len(logs) == 4
    for payload in logs:
        diag = result.tracker[payload["coordinate"]][payload["cd_iteration"]]
        assert payload["summary"] == diag.summary()


# --- (d) the second fit reads no block back --------------------------------------


# The fixed effect's solver and its penalty by configuration: L2 alone routes
# to margin-space L-BFGS; an elastic net (benchmark/configs/
# glmix2-poisson-enet.json's shape: a Poisson loss, weight and alpha) to
# margin-space OWL-QN, whose counters and gauge are published when a tracker
# is READ. Both carry margins across the solve's boundary. TRON on a squared
# loss (benchmark/configs/glmix2-linear-tron.json's shape) carries none: its
# program ends in one score pass, and its CG and rejected steps are
# published when a tracker is read.
FIT_CASES = {
    "logistic_l2": (TaskType.LOGISTIC_REGRESSION, (1.0, 0.0), "lbfgs_margin"),
    "poisson_elastic_net": (TaskType.POISSON_REGRESSION, (16.0, 0.5), "owlqn_margin"),
    "linear_tron": (TaskType.LINEAR_REGRESSION, (1.0, 0.0), "tron"),
}


def estimator_of(case):
    """``(estimator, optimization config, the fixed effect's solver)``."""
    from photon_tpu.estimators.config import (
        FixedEffectCoordinateConfig,
        GameOptimizationConfig,
        RandomEffectCoordinateConfig,
        RegularizationConfig,
    )
    from photon_tpu.estimators.game_estimator import GameEstimator
    from photon_tpu.types import OptimizerType

    task, (weight, alpha), optimizer = FIT_CASES[case]
    configured = OptimizerType.TRON if optimizer == "tron" else OptimizerType.LBFGS
    estimator = GameEstimator(
        task=task,
        coordinate_configs=[
            FixedEffectCoordinateConfig("global", "global", optimizer=configured),
            RandomEffectCoordinateConfig("per_user", "userId", "per_user"),
        ],
        num_iterations=2,
        intercept_indices={"global": 0, "per_user": 0},
        num_entities={"userId": E},
    )
    opt = GameOptimizationConfig(reg={
        "global": RegularizationConfig(weight=weight, alpha=alpha),
        "per_user": RegularizationConfig(weight=0.5),
    })
    return estimator, opt, optimizer


@pytest.mark.parametrize("case", sorted(FIT_CASES))
def test_second_fit_reads_nothing_back_from_the_device(
    case, glmix, monkeypatch, caplog
):
    from photon_tpu.obs.metrics import registry

    estimator, opt, optimizer = estimator_of(case)
    batch = glmix[0]
    registry().reset()
    with caplog.at_level(logging.WARNING, logger="photon_tpu"):
        (first,) = estimator.fit(batch, optimization_configs=[opt])
        dataset = estimator._re_datasets["per_user"]
        for block, valid in zip(dataset.blocks, dataset.lane_valid, strict=True):
            assert isinstance(valid, np.ndarray) and valid.dtype == bool
            np.testing.assert_array_equal(valid, np.asarray(block.entity_idx) >= 0)
        with host_reads(monkeypatch) as read:
            (second,) = estimator.fit(batch, optimization_configs=[opt])
            jax.block_until_ready(jax.tree_util.tree_leaves(second.model))
    assert estimator._re_datasets["per_user"] is dataset  # the grouping was kept
    entity_idx = {id(b.entity_idx) for b in dataset.blocks}
    assert not [x for x in read if id(x) in entity_idx]
    assert read == []  # nor anything else: a warm fit only dispatches
    # Nothing was published either: the solver's counters and the gauge come
    # with the tracker's one transfer, when somebody reads it.
    labels = dict(coordinate="global", optimizer=optimizer)
    assert registry().find("fe_solver_iterations_total", **labels) is None
    assert registry().find("fe_tron_cg_steps_total", coordinate="global") is None
    assert registry().find("fe_tron_products_total", coordinate="global") is None
    with device_gets(monkeypatch) as gets:
        diags = [d.diagnostics_dict() for d in second.tracker["global"]]
        [d.summary() for d in second.tracker["global"]]  # the copy is kept
    assert len(gets) == len(diags) == 2
    assert registry().find("fe_solver_iterations_total", **labels).value == sum(
        d["iterations"] for d in diags
    )
    cg = registry().find("fe_tron_cg_steps_total", coordinate="global")
    rejected = registry().find("fe_tron_rejected_steps_total", coordinate="global")
    products = registry().find("fe_tron_products_total", coordinate="global")
    one_read = registry().find("fe_tron_one_read_products_total",
                               coordinate="global")
    if optimizer == "tron":
        assert cg.value == sum(d["cg_steps"] for d in diags) > 0
        assert rejected.value == sum(d["rejected_steps"] for d in diags)
        # A product a CG step and ρ's an outer iteration; off a TPU every
        # one takes the two-pass form.
        assert products.value == cg.value + sum(d["iterations"] for d in diags)
        assert one_read.value == 0
    else:
        assert cg is None and rejected is None
        assert products is None and one_read is None
    for a, b in zip(jax.tree_util.tree_leaves(first.model),
                    jax.tree_util.tree_leaves(second.model), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --- (e) the fixed effect's update launches its solve and no score --------------


@pytest.mark.parametrize("case", sorted(FIT_CASES))
def test_warm_fit_fe_update_launches_no_eager_score(case, glmix, monkeypatch, caplog):
    """The fixed effect's scores come out of its solve program: between the
    entry and the return of its update a warm fit launches no eager multiply
    and no reduction (``compute_score``'s two), and the two counters of the
    seam say, from static facts and without a device read, where each solve's
    starting margins and each new score came from."""
    from photon_tpu.obs.metrics import registry

    estimator, opt, _optimizer = estimator_of(case)
    batch = glmix[0]
    with caplog.at_level(logging.WARNING, logger="photon_tpu"):
        estimator.fit(batch, optimization_configs=[opt])
        registry().reset()
        with compiles() as seen, host_reads(monkeypatch) as read:
            real_update = FixedEffectCoordinate.update

            def update(self, *args, **kwargs):
                seen.mark("FE UPDATE")
                try:
                    return real_update(self, *args, **kwargs)
                finally:
                    seen.mark("FE DONE")

            monkeypatch.setattr(FixedEffectCoordinate, "update", update)
            (second,) = estimator.fit(batch, optimization_configs=[opt])
            jax.block_until_ready(jax.tree_util.tree_leaves(second.model))
    assert read == []
    assert seen.names.count("FE UPDATE") == seen.names.count("FE DONE") == 2
    inside, is_inside = [], False
    for name in seen.names:
        if name in ("FE UPDATE", "FE DONE"):
            is_inside = name == "FE UPDATE"
        elif is_inside:
            inside.append(name)
    # One solve program a pass (compiled once: the second pass hits it), the
    # residual's add, the zeros of the start; no multiply, no reduction.
    assert inside.count("jit(traced)") == 1, inside
    assert not [n for n in inside if "multiply" in n or "reduce" in n], inside

    def counted(name, source):
        found = registry().find(name, coordinate="global", source=source)
        return 0 if found is None else found.value

    if FIT_CASES[case][2] == "tron":
        want = dict(solver_margins=0, fused_pass=2, zero=0, prior_score=0,
                    recomputed=2)
    else:
        want = dict(solver_margins=2, fused_pass=0, zero=1, prior_score=1,
                    recomputed=0)
    got = {s: counted("fe_score_source_total", s)
           for s in ("solver_margins", "fused_pass")}
    got.update({s: counted("fe_start_margins_total", s)
                for s in ("zero", "prior_score", "recomputed")})
    assert got == want
