"""Compile-once executable cache for the GLMix solver hot paths.

The random-effect coordinate dispatches one vmapped ``_solve_block`` per
EntityBlock per coordinate-descent pass — the paper's hot loop of millions
of per-entity GLM solves (reference RandomEffectCoordinate.scala:228-283)
collapsed into a handful of SPMD programs. Before this cache, every one of
those dispatches re-traced the solver eagerly: K CD passes × B blocks ×
S λ-sweep configs paid K·B·S traces for what is at most a few distinct
(shape, objective, optimizer) combinations.

This module keys ONE jitted executable per

    (block shape bucket, dtype, static objective config, optimizer spec,
     has feature mask)

so repeated CD passes and repeated same-shape blocks reuse a single
executable. Paired with shape bucketing (data/random_effect.py rounds
``(E, n_max, d)`` up to a geometric grid), heterogeneous entity populations
collapse onto a handful of cache entries. The warm-start coefficient buffer
is donated (``donate_argnums``): the (E, d) warm start is dead after the
solve, so XLA reuses its HBM for the output instead of allocating a second
coefficient block.

Key construction notes:

- ``GLMObjective`` / ``OptimizerSpec`` / ``OptimizerConfig`` are keyed by
  their static scalar fields. Normalization vectors and box-constraint
  arrays are keyed by ``id()`` (they are built once per coordinate and
  reused across passes); the cache pins a strong reference to every keyed
  object so an id is never recycled while its entry is alive.
- Trace counting is done INSIDE the traced function (the standard
  trace-counter trick): the Python side effect runs only when JAX actually
  traces, so ``stats.traces`` counts real retraces — including any the
  jit-level cache would hide — and the retrace-regression test in
  tests/test_solve_cache.py asserts on it directly.
- Keys are deliberately DEVICE-POLYMORPHIC: no device or sharding
  component. One traced executable serves every device of a backend, so
  the entity-sharded coordinate (algorithm/sharded_random_effect.py) can
  run S shards across N devices through one shared cache — warming it at
  one device count leaves every other count with zero compiles
  (tests/test_entity_sharded.py asserts this), and the multichip ladder's
  zero-retrace bar needs no per-device keying.

The same cache serves the fixed-effect objective (``fe_solver``): the full
optimizer run over the sharded batch becomes one cached jitted program per
(objective, spec) instead of an eager re-trace of the ``lax.while_loop``
nest on every ``train()`` call.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import threading
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array

# Bounded-cache opt-in: entry cap for every SolveCache constructed without an
# explicit ``max_entries`` (default unbounded — today a λ-sweep is one entry
# per λ, which is fine; the env knob exists for per-λ-objective sweeps that
# blow up the entry count).
MAX_ENTRIES_ENV = "PHOTON_TPU_SOLVE_CACHE_MAX_ENTRIES"


@dataclasses.dataclass
class SolveCacheStats:
    """Counters for cache effectiveness, reported by bench.py.

    traces:  executions of the tracing path (one per distinct executable;
             a retrace of an existing key also counts — that is the point).
    calls:   solver dispatches routed through the cache.
    hits:    dispatches that reused an already-traced executable.
    trace_keys: shape/kind descriptor recorded at each trace, for the
             bench's retrace breakdown.
    """

    traces: int = 0
    calls: int = 0
    hits: int = 0
    evictions: int = 0
    trace_keys: List[Tuple] = dataclasses.field(default_factory=list)

    def as_dict(self) -> Dict[str, Any]:
        return dict(
            traces=self.traces,
            calls=self.calls,
            hits=self.hits,
            evictions=self.evictions,
            trace_keys=[list(k) for k in self.trace_keys],
        )


def _scalar(x):
    """Coerce a numeric config field to a hashable Python scalar; arrays and
    other unhashables fall back to identity (pinned by the cache entry)."""
    if x is None or isinstance(x, (bool, int, float, str)):
        return x
    try:
        hash(x)
        return x
    except TypeError:
        return ("id", id(x))


class SolveCache:
    """Executable cache for block (random-effect) and fixed-effect solves.

    One instance may be shared across coordinates — the module-level
    :func:`default_cache` is shared by every coordinate that is not given an
    explicit cache, so a λ-sweep over the same dataset hits one executable
    set. ``donate=False`` disables warm-start donation (callers that need to
    reuse the w0 buffer after the solve).
    """

    def __init__(self, donate: bool = True, max_entries: Optional[int] = None):
        self.donate = donate
        if max_entries is None:
            env = os.environ.get(MAX_ENTRIES_ENV, "").strip()
            max_entries = int(env) if env else None
        if max_entries is not None and max_entries < 1:
            raise ValueError(f"max_entries must be >= 1, got {max_entries}")
        # LRU bound on ENTRIES (per-key executables). Evicting an entry only
        # drops the cache's reference + pins — a solver callable a caller
        # already holds keeps working (jax.jit owns its own executables); a
        # later dispatch of the same key rebuilds (and re-traces) it.
        self.max_entries = max_entries
        self.stats = SolveCacheStats()
        self._fns: "OrderedDict[Tuple, Callable]" = OrderedDict()
        self._pins: Dict[Tuple, Tuple] = {}  # keep id()-keyed objects alive
        self._lock = threading.Lock()

    # ---- static keys -----------------------------------------------------

    @staticmethod
    def _norm_key(norm) -> Optional[Tuple]:
        if norm is None:
            return None
        return (
            bool(norm.is_identity),
            None if norm.factors is None else ("id", id(norm.factors)),
            None if norm.shifts is None else ("id", id(norm.shifts)),
            _scalar(getattr(norm, "intercept_index", None)),
        )

    @classmethod
    def _objective_key(cls, objective) -> Tuple:
        return (
            objective.loss,
            _scalar(objective.l2_weight),
            _scalar(objective.l1_weight),
            _scalar(objective.intercept_index),
            bool(objective.use_pallas),
            cls._norm_key(objective.normalization),
        )

    @staticmethod
    def _spec_key(spec) -> Tuple:
        return (
            spec.optimizer,
            _scalar(spec.max_iter),
            _scalar(spec.tol),
            _scalar(spec.memory),
            _scalar(spec.max_cg_iter),
            None
            if spec.box is None
            else (("id", id(spec.box[0])), ("id", id(spec.box[1]))),
            bool(spec.track_history),
        )

    @staticmethod
    def _config_key(config) -> Tuple:
        return (
            _scalar(config.max_iter),
            _scalar(config.tol),
            _scalar(config.memory),
            _scalar(config.max_line_search_evals),
            bool(config.track_history),
        )

    # ---- builders --------------------------------------------------------

    def _get_or_build(self, key: Tuple, build: Callable[[], Callable], pins: Tuple):
        with self._lock:
            fn = self._fns.get(key)
            if fn is None:
                fn = build()
                self._fns[key] = fn
                self._pins[key] = pins
                if self.max_entries is not None:
                    while len(self._fns) > self.max_entries:
                        old_key, _old_fn = self._fns.popitem(last=False)
                        self._pins.pop(old_key, None)
                        self.stats.evictions += 1
                        from photon_tpu.obs.metrics import registry

                        registry().counter("solve_cache_evictions_total").inc()
            else:
                self._fns.move_to_end(key)  # LRU touch
        return fn

    def _counted(self, fn: Callable) -> Callable:
        """Wrap a jitted fn with hit/call accounting (trace accounting lives
        inside the traced body, so it also catches shape-driven retraces)."""

        def call(*args):
            before = self.stats.traces
            out = fn(*args)
            self.stats.calls += 1
            if self.stats.traces == before:
                self.stats.hits += 1
            return out

        return call

    def block_solver(
        self, objective, spec, config, has_mask: bool,
        convergence_tol: Optional[float] = None,
    ) -> Callable[..., Tuple[Array, Array, Array]]:
        """Jitted ``_solve_block`` executable for one static configuration.

        Returns ``solve(block, offsets, w0[, feature_mask])``. The warm
        start ``w0`` is DONATED (when ``self.donate``): callers must pass a
        buffer that is dead after the call — a fresh gather, or an explicit
        copy of any model-owned array.

        With ``convergence_tol`` set (the active-set gate of
        algorithm/random_effect.py), the traced program ALSO returns a
        per-entity bool ``active`` mask computed in-graph: an entity stays
        active while its coefficient delta exceeds ``tol`` relative to the
        warm start, and shape-bucket padding rows (entity_idx == -1) are
        never active. The tol is part of the cache key, so gated and
        ungated dispatches never share (or invalidate) an executable;
        ``trace_keys`` keeps the same shape-only format either way so trace
        breakdowns of gated and ungated runs stay comparable.

        Every dispatch carries an in-trace divergence quarantine: entity rows
        whose solve produced non-finite coefficients keep their warm start
        and are flagged ``REASON_DIVERGED``; with ``convergence_tol`` the
        program additionally returns a per-entity ``quarantined`` bool mask
        (fifth output) that the coordinate reads at the existing
        pass-boundary mask fetch — no extra host syncs.
        """
        has_mask = bool(has_mask)
        tol = None if convergence_tol is None else float(convergence_tol)
        key = (
            "block",
            self._objective_key(objective),
            self._spec_key(spec),
            self._config_key(config),
            has_mask,
            tol,
        )

        def build():
            from photon_tpu.algorithm.random_effect import _solve_block
            from photon_tpu.optim.common import REASON_DIVERGED

            stats = self.stats

            def solve(block, offsets, w0, feature_mask=None):
                stats.traces += 1
                stats.trace_keys.append(
                    ("block",) + tuple(block.features.shape) + (has_mask,)
                )
                w, iterations, reasons = _solve_block(
                    block, offsets, w0, objective, spec, config, feature_mask
                )
                # Per-entity divergence quarantine, fully in-trace: a row
                # whose solve went non-finite keeps its warm start and is
                # flagged REASON_DIVERGED. The reasons array is only read on
                # the host at the pass-boundary mask fetch / report finalize,
                # so the guard adds no syncs.
                row_finite = jnp.all(jnp.isfinite(w), axis=-1)
                w = jnp.where(row_finite[:, None], w, w0)
                reasons = jnp.where(row_finite, reasons, REASON_DIVERGED)
                if tol is None:
                    return w, iterations, reasons
                # Relative coefficient movement in MODEL space; the floor of
                # 1.0 on the reference norm makes near-zero models behave
                # like an absolute tolerance. Quarantined rows have w == w0,
                # hence delta == 0: they retire from the active set.
                delta = jnp.linalg.norm((w - w0).astype(jnp.float32), axis=-1)
                ref = jnp.maximum(
                    jnp.linalg.norm(w0.astype(jnp.float32), axis=-1), 1.0
                )
                valid = block.entity_idx >= 0
                active = (delta > tol * ref) & valid
                # Quarantine keys on the DIVERGED reason, not row_finite:
                # the in-loop guards (Newton's non-finite-objective stop,
                # L-BFGS's iterate rollback) already return a finite w while
                # flagging the row — those entities must still be counted.
                quarantined = (reasons == REASON_DIVERGED) & valid
                return w, iterations, reasons, active, quarantined

            # The closures stay named ``traced`` (the launch is the module
            # ``jit_traced``, which the benchmark's metrics select on); the
            # scope names the device work for a profile.
            if has_mask:

                def traced(block, offsets, w0, feature_mask):
                    with jax.named_scope("re_solve"):
                        return solve(block, offsets, w0, feature_mask)

            else:

                def traced(block, offsets, w0):
                    with jax.named_scope("re_solve"):
                        return solve(block, offsets, w0)

            donate = (2,) if self.donate else ()
            return jax.jit(traced, donate_argnums=donate)

        fn = self._get_or_build(key, build, (objective, spec, config))
        counted = self._counted(fn)
        if has_mask:
            return counted

        def call(block, offsets, w0, feature_mask=None):
            assert feature_mask is None
            return counted(block, offsets, w0)

        return call

    def fe_solver(self, objective, spec, batch=None) -> Callable:
        """Jitted fixed-effect solve ``(w0, labeled_batch, start_score=None)
        -> (OptimizeResult, score)`` for one (objective, spec). ``score`` is
        x·w at the result for every sample (what the model made of it scores
        on the batch), from the solver's own margins where it carries them
        and from one pass over X inside the same program where it does not
        (``make_optimizer(..., with_score=True)``); ``start_score`` is x·w0
        where the caller holds it, which a margin-carrying solver starts
        from. The batch is a traced argument, so the one cache entry serves
        every batch of the same structure; w0 is NOT donated here
        (fixed-effect warm starts alias live model buffers).

        ``batch``, the concrete batch the solve will run on, decides before
        any trace whether TRON's Hessian-vector products read X once
        (``GLMObjective.one_read_hvp``: inside the trace a sharded batch
        cannot be told from one on a single device); the decision is part
        of the key. Without it every product reads X twice."""
        from photon_tpu.optim.factory import make_optimizer, routed_solver

        one_read = (
            batch is not None
            and routed_solver(objective, spec) == "tron"
            and objective.one_read_hvp(batch)
        )
        key = ("fe", self._objective_key(objective), self._spec_key(spec),
               one_read)

        def build():
            from photon_tpu.optim.common import REASON_DIVERGED

            solve = make_optimizer(objective, spec, with_score=True,
                                   one_read_hvp=one_read)
            stats = self.stats

            def traced(w0, lb, start_score=None):
                stats.traces += 1
                stats.trace_keys.append(("fe", int(w0.shape[0])))
                with jax.named_scope("fe_solve"):
                    res, score = solve(w0, lb, start_score)
                # Divergence backstop covering every optimizer type: a
                # non-finite final point falls back to the warm start, its
                # score with it, and is flagged DIVERGED (L-BFGS additionally
                # rolls back to the last finite iterate inside its own loop).
                ok = jnp.all(jnp.isfinite(res.w))
                w = jnp.where(ok, res.w, w0)
                score = jax.lax.cond(
                    ok, lambda: score, lambda: objective.scores(w0, lb)
                )
                res = dataclasses.replace(
                    res,
                    w=w,
                    reason_code=jnp.where(
                        ok, res.reason_code, jnp.int32(REASON_DIVERGED)
                    ),
                    nonzeros=jnp.count_nonzero(w).astype(jnp.int32),
                )
                return res, score

            return jax.jit(traced)

        fn = self._get_or_build(key, build, (objective, spec))
        return self._counted(fn)

    # ---- introspection ---------------------------------------------------

    @contextlib.contextmanager
    def expect_cached(self, what: str = "dispatch"):
        """Assert no NEW executable is traced inside the context.

        The active-set path wraps every compacted dispatch in this: compacted
        blocks are packed exclusively onto entity allocations that the first
        full pass already compiled, so a retrace here is a bug (a shape that
        escaped the allowed-size plan), not a performance wobble. Tracing
        happens synchronously at dispatch time, so the counter check is
        exact even though execution is async.
        """
        traces0, nkeys = self.stats.traces, len(self.stats.trace_keys)
        yield
        if self.stats.traces != traces0:
            raise AssertionError(
                f"{what}: expected a cache hit but traced "
                f"{self.stats.traces - traces0} new executable(s): "
                f"{self.stats.trace_keys[nkeys:]}"
            )

    def trace_mark(self) -> int:
        """Snapshot of the cumulative trace count, for retrace-delta
        assertions across a window (the out-of-core bench and ci stages
        assert ``traces_since(mark) == 0`` after warm-up: residency changes
        where a block lives, never its aval, so evictions must not
        recompile)."""
        return int(self.stats.traces)

    def traces_since(self, mark: int) -> int:
        """New executables traced since :meth:`trace_mark`."""
        return int(self.stats.traces) - int(mark)

    @property
    def num_entries(self) -> int:
        return len(self._fns)

    def clear(self) -> None:
        with self._lock:
            self._fns.clear()
            self._pins.clear()
            self.stats = SolveCacheStats()

    def reset_stats(self) -> None:
        """Zero the counters while KEEPING compiled executables.

        Mutates in place: already-built traced closures captured this stats
        object, so replacing it would route their retrace increments to a
        dead object. Used by ``obs.begin_run`` so a run report counts this
        run's dispatches, not the process's lifetime."""
        with self._lock:
            s = self.stats
            s.traces = 0
            s.calls = 0
            s.hits = 0
            s.evictions = 0
            s.trace_keys.clear()


_default_cache = SolveCache()


def default_cache() -> SolveCache:
    """The process-wide cache shared by coordinates without an explicit one."""
    return _default_cache


def reset_default_cache(
    donate: bool = True, max_entries: Optional[int] = None
) -> SolveCache:
    """Replace the shared cache (tests / benchmark A-B sections)."""
    global _default_cache
    _default_cache = SolveCache(donate=donate, max_entries=max_entries)
    return _default_cache


def cache_stats() -> Dict[str, Any]:
    """Shared-cache counters, in the shape bench.py reports."""
    return _default_cache.stats.as_dict()
