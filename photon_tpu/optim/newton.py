"""Damped Newton (Levenberg) solver for small-dimension GLMs.

Role parity: the per-entity random-effect solves — the reference runs one
Breeze L-BFGS per entity inside ``mapValues``
(photon-api algorithm/RandomEffectCoordinate.scala:228-283), and offers TRON
(truncated Newton, photon-lib optimization/TRON.scala:148-246) as the
second-order option.

TPU-first design: for the random-effect shape (d ≲ 64, thousands of
entities solved as ONE vmapped program) the right second-order method is
exact Newton — H = XᵀDX + λI is a tiny (d, d) matrix whose assembly is an
MXU einsum, while L-BFGS's nested line-search loops dominate wall time on
deep ``lax.while_loop`` nests (each vmapped while iteration costs fixed
overhead regardless of lane width). Newton converges in 3-5 iterations
where L-BFGS needs 10+, and each iteration is exactly TWO passes over X
(one gradient + Hessian assembly, one trial-point margin refresh) with no
inner loops.

The damped system is solved by Cholesky, and how is chosen by two static
sizes (``spd_solve``). Up to ``SPD_UNROLL_MAX_DIM`` wide and from
``SPD_UNROLL_MIN_LANES`` entities in the ``vmap``, the factorisation and
both triangular solves are ``d`` column steps unrolled in Python, plain
float32 multiply / subtract / divide / sqrt with the ENTITY axis as the
minor (lane) axis of every intermediate; else
``jax.scipy.linalg.cho_factor`` / ``cho_solve``. Under ``vmap`` the library
call becomes one batched ``Cholesky`` custom call that factorises the
matrices one after another, each 16 × 16 on a tile of its own: on a TPU v5e
at ``f32[3072,16,16]`` it took 5.16 ms inside a loop and was 42–50 % of a
GLMix fit, where the unrolled steps take 0.016 ms; both land 4e-7 to 6e-7
from a float64 solve (chip runs of PR 32, PERF.md §6).

Two more static sizes shape the loop's body (PR 38, a block of 163,840
users of up to 4 rows): an entity with fewer than ``ROWS_ON_MXU_MIN`` rows
takes its three products over the rows as multiply-and-reduce, which XLA
lays out with the entities minor, and from ``SPD_LANE_CHUNK`` lanes the
column steps run a chunk of lanes at a time, so that their intermediates
stay in the chip's fast memory.

Damping follows the Levenberg accept/reject pattern (the scalar analogue of
TRON's trust-region radius update, TRON.scala:93-94): a rejected step keeps
the iterate and multiplies the damping by 10; an accepted step shrinks it.
A failed factorisation (sqrt of a pivot that is not positive: NaN in that
entity's step, on either lowering) lands in the reject branch by
construction.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from photon_tpu.data.batch import LabeledBatch, SparseFeatures
from photon_tpu.ops.objective import GLMObjective
from photon_tpu.optim.common import (
    OptimizeResult,
    OptimizerConfig,
    REASON_DIVERGED,
    REASON_FUNCTION_VALUES_CONVERGED,
    REASON_MAX_ITERATIONS,
    REASON_NOT_CONVERGED,
    check_convergence,
)

Array = jax.Array

_MU_INIT = 0.0  # start with pure Newton; L2'd GLM Hessians are PD
_MU_BOOST = 10.0
_MU_SHRINK = 0.25
_MU_MIN_ON_REJECT = 1e-4  # first reject jumps 0 → 1e-3 (×10 applied after)

# Widest system solved by the unrolled column steps; wider ones take the
# library's ``cho_factor`` / ``cho_solve``. The steps' program grows with d
# and every block geometry compiles its own: for the v5e at 3072 lanes the
# compiler takes 0.9 s at d = 16, 2.6 s at 32 and 12.5 s at 64 (the library
# call 0.3 s at each), and the gain over the library call shrinks as the
# rank-one updates outgrow VMEM: 326× at d = 16, 31× at 32, 8.9× at 64
# (chip runs of PR 32). Every benchmark cell has d = 16.
SPD_UNROLL_MAX_DIM = 32

# Fewest systems in one ``vmap`` for which the column steps are worth their
# set-up. Every block geometry's solver program traces and lowers its own
# copy of them, 0.4 s each on the v5e's host (its Python runs four to five
# times slower than this repository's sandbox), and set-up has a 10 % bound:
# the heavy-tailed cell has 19 geometries, ten of them under 128 lanes, and
# the first fit of its set-up read 27.0–27.5 s without this bound, 22.3–23.4
# with it and 20.0 at the parent (`setup_s` +20 % and +9 %; one call, warm,
# PR 32). The library call costs 1.4 µs a matrix: 6 to 110 µs an iteration
# up to 72 lanes against the steps' 6, 4 ms of a 0.37 s fit (PERF.md §6).
SPD_UNROLL_MIN_LANES = 128

# Fewest rows of one entity for which the three products over its rows (the
# margins X·w, the gradient Xᵀ·dz and the Hessian Xᵀ·diag(d2)·X) are matrix
# products. Under it the contraction is a fraction of one pass of the matrix
# unit, which gains nothing, and the layout costs: under the entity ``vmap``
# XLA keeps a ``dot_general``'s batch axis major, so a block of 163,840
# users of up to 4 rows wrote its Hessians as f32[163840,16,16] with the 16
# columns padded to a 128-lane tile, 1.34 GB an iteration where 168 MB are
# data, and carried its (163840, 4) and (163840, 16) vectors 32 and 8 times
# padded. Written as multiply and reduce the same sums are laid out with the
# entities minor (compiled for the v5e: 5.0 GB of outputs a Newton iteration
# of that block before, 2.7 GB after; PERF.md §6, PR 38). Exact float32,
# where the matrix unit rounds its operands. No block of the cells that
# existed before has fewer than 96 rows a lane.
ROWS_ON_MXU_MIN = 64

# Lanes the unrolled column steps take at a time. Each step rewrites what is
# left of every system, ~1,500 floats a lane over the 16 steps at d = 16:
# for all 163,840 lanes of a block at once every intermediate is 20–160 MB
# and goes through HBM (1.4 GB of outputs a solve); 4,096 lanes at a time
# they are 0.5–4 MB and the compiler keeps every one of them in fast memory
# (compiled for the v5e: each ``slice_subtract_fusion`` output in memory
# space 1). The same operations on every lane, so the same bits. A lane
# count it does not divide (the plan's are multiples of 1/16 of a power of
# two, so from 65,536 lanes all are divided) is solved whole.
SPD_LANE_CHUNK = 4096


@jax.jit
def _solve_columns(A: Array, b: Array) -> Array:
    """Cholesky factorisation and both substitutions of ``A x = b`` as
    ``d`` unrolled column steps of elementwise float32 arithmetic.

    ``A`` is ``(d, d, lanes)`` and ``b`` ``(d, lanes)``: the last axis holds
    independent systems and is the minor axis of every intermediate. A
    pivot that is not positive gives NaN (``sqrt``) in that system's ``x``
    and in no other. What set-up pays for it, once a solver program (one a
    block geometry of a plan), is why it is written so: on ``lax`` calls,
    which trace twice as fast as ``jnp`` operators, and jitted, because
    ``vmap`` of the Newton loop asks the batching rule below for these
    ~17·d equations three times a program: the jit traces them once and
    binds one equation each time (PERF.md §6: 19 programs in the
    heavy-tailed cell, and set-up has a bound).
    """
    d, lanes = A.shape[0], A.shape[2]
    M = lax.concatenate([A, lax.expand_dims(b, (1,))], 1)  # (d, d + 1, lanes)
    one = lax.full((1, 1, lanes), 1.0, A.dtype)
    cols, ys, rinvs = [], [], []
    for j in range(d):
        # Right-looking, as LAPACK's potf2. What is left of the system is
        # symmetric, so its first ROW scaled by 1/sqrt(pivot) is column j
        # of L below the diagonal, and its last entry is y_j of y = L⁻¹b.
        m = d - j
        rinv = lax.div(one, lax.sqrt(lax.slice(M, (0, 0, 0), (1, 1, lanes))))
        row = lax.mul(lax.slice(M, (0, 1, 0), (1, m + 1, lanes)), rinv)
        rinvs.append(rinv)
        ys.append(lax.slice(row, (0, m - 1, 0), (1, m, lanes)))
        if m > 1:
            col = lax.slice(row, (0, 0, 0), (1, m - 1, lanes))  # L[j+1:, j]
            cols.append(col)
            rank_one = lax.mul(lax.reshape(col, (m - 1, 1, lanes)), row)
            M = lax.sub(lax.slice(M, (1, 1, 0), (m, m + 1, lanes)), rank_one)
    x = lax.mul(ys[-1], rinvs[-1])  # entries j.. of the solution of Lᵀx = y
    for j in reversed(range(d - 1)):
        dot = lax.expand_dims(
            lax.reduce_sum(lax.mul(cols[j], x), axes=(1,)), (1,)
        )
        x = lax.concatenate([lax.mul(lax.sub(ys[j], dot), rinvs[j]), x], 1)
    return lax.squeeze(x, (0,))


def _solve_library(A: Array, b: Array) -> Array:
    chol, _ = jax.scipy.linalg.cho_factor(A, lower=True)
    return jax.scipy.linalg.cho_solve((chol, True), b)


@jax.custom_batching.custom_vmap
def _solve_by_lanes(A: Array, b: Array) -> Array:
    return _solve_library(A, b)  # one system: no lanes to lay it along


@_solve_by_lanes.def_vmap
def _solve_by_lanes_vmap(axis_size, in_batched, A, b):
    """``vmap`` of enough systems puts the batch axis LAST: a TPU tiles an
    array's two minor axes, so ``(E, d, d)`` leaves a 16 × 16 matrix on a
    tile of its own and the entities one after another, where ``(d, d, E)``
    lays the entities along the lanes. An outer ``vmap`` of this
    (``batched_tuning`` maps λ over the entity map) puts its axis in front
    of every step, so the lanes stay minor. Over ``SPD_LANE_CHUNK`` lanes the
    steps take that many at a time, sliced along the lane axis."""
    if spd_solve_lowering(A.shape[-1], axis_size) == "library":
        in_axes = tuple(0 if batched else None for batched in in_batched)
        return jax.vmap(_solve_library, in_axes)(A, b), True

    def last(x, batched):
        if batched:
            return jnp.moveaxis(x, 0, -1)
        return jnp.broadcast_to(x[..., None], x.shape + (axis_size,))

    A, b = last(A, in_batched[0]), last(b, in_batched[1])
    if axis_size > SPD_LANE_CHUNK and axis_size % SPD_LANE_CHUNK == 0:
        def chunk(i, x):
            at = i * SPD_LANE_CHUNK
            part = _solve_columns(
                lax.dynamic_slice_in_dim(A, at, SPD_LANE_CHUNK, axis=2),
                lax.dynamic_slice_in_dim(b, at, SPD_LANE_CHUNK, axis=1),
            )
            return lax.dynamic_update_slice_in_dim(x, part, at, axis=1)

        x = lax.fori_loop(0, axis_size // SPD_LANE_CHUNK, chunk, jnp.zeros_like(b))
    else:
        x = _solve_columns(A, b)
    return jnp.moveaxis(x, -1, 0), True


def spd_solve(A: Array, b: Array) -> Array:
    """Solve the symmetric positive definite ``(d, d)`` system ``A x = b``
    by Cholesky, on the lowering the static ``d`` and the ``vmap``'s size
    choose (``spd_solve_lowering``). Not positive definite: NaN in ``x``."""
    if A.shape[0] > SPD_UNROLL_MAX_DIM:
        return _solve_library(A, b)
    return _solve_by_lanes(A, b)


def spd_solve_lowering(d: int, lanes: int) -> str:
    """``"unrolled"`` for ``lanes`` systems of width ``d`` under one
    ``vmap`` up to ``SPD_UNROLL_MAX_DIM`` and from ``SPD_UNROLL_MIN_LANES``,
    else ``"library"``."""
    unrolled = d <= SPD_UNROLL_MAX_DIM and lanes >= SPD_UNROLL_MIN_LANES
    return "unrolled" if unrolled else "library"


def minimize_newton(
    objective: GLMObjective,
    batch: LabeledBatch,
    w0: Array,
    config: OptimizerConfig = OptimizerConfig(),
    l2_override: Optional[Array] = None,
) -> OptimizeResult:
    """Levenberg-damped exact Newton over a dense-feature GLM batch.

    ``result.evals`` counts X passes (2 per iteration), the same cost unit
    as ``minimize_lbfgs_margin``. Dense features only (the per-entity blocks
    are dense by construction); scale-type normalization is folded, shift
    normalization is not supported (the random-effect path never uses it).
    """
    if isinstance(batch.features, SparseFeatures):
        raise ValueError("minimize_newton requires dense features")
    if objective.l1_weight > 0.0:
        raise ValueError("Newton solves smooth objectives; use OWL-QN for L1")
    norm = objective.normalization
    if norm is not None and not norm.is_identity and norm.shifts is not None:
        raise ValueError("minimize_newton supports scale normalization only")

    loss = objective.loss
    l2 = objective.l2_weight if l2_override is None else l2_override
    has_l2 = l2_override is not None or objective.l2_weight != 0.0
    label, weight, offset = batch.label, batch.weight, batch.offset
    X = batch.features
    if norm is not None and not norm.is_identity and norm.factors is not None:
        X = X * norm.factors[None, :]  # margins/H/grad all use X·diag(f)

    d = w0.shape[0]
    dtype = w0.dtype
    m_iter, tol = config.max_iter, config.tol

    # The three products over the rows. From ROWS_ON_MXU_MIN rows they are
    # matrix products; under it, the same sums written as multiply and
    # reduce (exact float32, no matrix unit).
    if X.shape[0] >= ROWS_ON_MXU_MIN:
        def margins(w):
            return X @ w + offset

        def gradient(dz):
            return X.T @ dz

        def hessian(d2):
            return jnp.einsum("nd,n,ne->de", X, d2, X)

        def hessian_diagonal(d2, H):
            return jnp.diagonal(H)
    else:
        def margins(w):
            return jnp.sum(X * w[None, :], axis=1) + offset

        def gradient(dz):
            return jnp.sum(X * dz[:, None], axis=0)

        def hessian(d2):
            return jnp.sum((X * d2[:, None])[:, :, None] * X[:, None, :], axis=0)

        def hessian_diagonal(d2, H):
            # The same products in the same order as H's own diagonal, with
            # no gather out of the (entities, d, d) array under the vmap.
            return jnp.sum((X * d2[:, None]) * X, axis=0)

    def _l2_mask(w: Array) -> Array:
        if objective.intercept_index is None:
            return w
        return w.at[objective.intercept_index].set(0.0)

    def l2_value(w: Array) -> Array:
        if not has_l2:
            return jnp.zeros((), dtype)
        wm = _l2_mask(w)
        return 0.5 * l2 * jnp.dot(wm, wm)

    def data_value(z: Array) -> Array:
        return jnp.sum(weight * loss.value(z, label))

    lam_diag = jnp.zeros((d,), dtype)
    if has_l2:
        lam_diag = jnp.full((d,), l2, dtype)
        if objective.intercept_index is not None:
            lam_diag = lam_diag.at[objective.intercept_index].set(0.0)

    z0 = margins(w0)
    f0 = data_value(z0) + l2_value(w0)

    hist_len = config.history_len
    state0 = dict(
        w=w0,
        z=z0,
        f=f0,
        mu=jnp.asarray(_MU_INIT, dtype),
        gnorm=jnp.asarray(jnp.inf, dtype),
        it=jnp.int32(0),
        reason=jnp.int32(REASON_NOT_CONVERGED),
        evals=jnp.int32(1),  # initial margin pass
        g0_norm=jnp.asarray(0.0, dtype),
        loss_hist=jnp.full((hist_len,), f0, dtype),
        gnorm_hist=jnp.full((hist_len,), jnp.inf, dtype),
    )

    def cond(st):
        return (st["reason"] == REASON_NOT_CONVERGED) & (st["it"] < m_iter)

    def body(st):
        w, z, f = st["w"], st["z"], st["f"]
        # --- pass 1: gradient + Hessian from the carried margins ---
        dz = weight * loss.dz(z, label)
        d2 = weight * loss.dzz(z, label)
        g = gradient(dz) + (l2 * _l2_mask(w) if has_l2 else 0.0)
        H0 = hessian(d2)
        H = H0 + jnp.diag(lam_diag)
        gnorm = jnp.linalg.norm(g)
        g0_norm = jnp.where(st["it"] == 0, gnorm, st["g0_norm"])

        # Levenberg system: (H + μ·diag(H)) p = -g. Scaling the damping by
        # diag(H) keeps μ unit-free across entities of very different sizes.
        # The diagonal is floored at a tiny fraction of its largest entry so
        # a feature column with no active samples (H_jj = 0, arises when
        # l2 = 0) still becomes positive-definite under damping instead of
        # failing the factorisation forever — the dead direction then gets
        # step p_j = −g_j/(μ·floor) = 0 since g_j = 0 too.
        diag_h = hessian_diagonal(d2, H0) + lam_diag
        floor = 1e-7 * jnp.maximum(jnp.max(diag_h), 1.0)
        Hd = H + st["mu"] * jnp.diag(jnp.maximum(diag_h, floor))
        p = -spd_solve(Hd, g)

        # --- pass 2: trial margins, then FREE backtracking on margins ---
        # Margins are affine in the step: z(w + t·p) = z + t·u with
        # u = z_try − z already in hand, so step-halving trials are O(n)
        # elementwise evaluations with no further X pass (the same margin
        # affinity minimize_lbfgs_margin's line search exploits). This is
        # what globalizes pure Newton on exp-like losses (Poisson) without
        # burning a full iteration per rejected step.
        w_try = w + p
        z_try = margins(w_try)
        u = z_try - z
        ts = jnp.asarray([1.0, 0.5, 0.25, 0.125, 1 / 16, 1 / 32, 1 / 64], dtype)

        def f_at(t):
            return data_value(z + t * u) + l2_value(w + t * p)

        fs = jax.vmap(f_at)(ts)
        fs = jnp.where(jnp.isnan(fs), jnp.inf, fs)  # failed solve → reject
        # The best trial's value and step with no index into ``fs`` or
        # ``ts``: under the entity vmap each index is a gather of one element
        # a lane, and the one out of ``fs`` took 1.35 of the 4.65 ms of an
        # iteration over 163,840 lanes (chip run, PR 38). The same numbers:
        # the minimum is the element argmin points at, the sum has one term.
        ib = jnp.argmin(fs)
        f_best = jnp.min(fs)
        t_best = jnp.sum(jnp.where(jnp.arange(ts.shape[0]) == ib, ts, 0.0))
        # <= so ties at f32 resolution near the optimum still step (the
        # gradient keeps contracting).
        accept = f_best <= f

        w_new = jnp.where(accept, w + t_best * p, w)
        z_new = jnp.where(accept, z + t_best * u, z)
        f_new = jnp.where(accept, f_best, f)
        mu_new = jnp.where(
            accept & (t_best == 1.0),
            st["mu"] * _MU_SHRINK,
            jnp.where(
                accept,
                st["mu"],  # partial step: keep current damping
                jnp.maximum(st["mu"], _MU_MIN_ON_REJECT) * _MU_BOOST,
            ),
        )

        it = st["it"] + 1
        # Convergence: gradient test on the CURRENT iterate's exact gradient
        # (no lag); value test on the best-trial-vs-current change — at the
        # optimum even a rejected Newton step has |f_best − f| ≈ 0, which is
        # precisely "can't improve" (a genuinely bad rejected step has a
        # large |f_best − f| and keeps iterating with boosted damping).
        reason = check_convergence(f_best, f, gnorm, g0_norm, tol, it, m_iter)
        # A REJECTED step whose best trial is above f by no more than the
        # dtype resolves f cannot be improved on either. The value test
        # misses it wherever one ulp of f is over tol·f (float32, tol 1e-7:
        # every f whose mantissa is under 1.19, a quarter of all values),
        # and no later step can end it: the trials are judged from fresh
        # margins X·w against the CARRIED f, whose margins were summed step
        # by step, so even p = 0 under any damping reads that one ulp above,
        # and the loop ran to max_iter (one 16-row user in 4,096 held its
        # whole block for 100 iterations; PERF.md §6, PR 38).
        stalled = ~accept & (f_best - f <= 2.0 * jnp.finfo(dtype).eps * jnp.abs(f))
        reason = jnp.where(
            stalled & (reason == REASON_NOT_CONVERGED),
            jnp.int32(REASON_FUNCTION_VALUES_CONVERGED),
            reason,
        )
        # Divergence guard: a non-finite carried objective can never be
        # improved (every trial compares False against NaN, so the reject
        # branch keeps the iterate forever). Flag DIVERGED and stop; the
        # iterate itself is still the last finite point (w0 when f0 was
        # already non-finite — e.g. corrupted offsets).
        reason = jnp.where(
            jnp.isfinite(f_new), reason, jnp.int32(REASON_DIVERGED)
        )
        return dict(
            w=w_new,
            z=z_new,
            f=f_new,
            mu=mu_new,
            gnorm=gnorm,
            it=it,
            reason=reason,
            evals=st["evals"] + 2,
            g0_norm=g0_norm,
            loss_hist=st["loss_hist"].at[jnp.minimum(it, hist_len - 1)].set(f_new),
            gnorm_hist=st["gnorm_hist"].at[jnp.minimum(it, hist_len - 1)].set(gnorm),
        )

    st = jax.lax.while_loop(cond, body, state0)
    idx = jnp.arange(hist_len)
    loss_hist = jnp.where(idx <= st["it"], st["loss_hist"], st["f"])
    gnorm_hist = jnp.where(idx <= st["it"], st["gnorm_hist"], st["gnorm"])
    # Entry 0 = |g| at the initial point (computed inside the first body
    # iteration; inf only if the loop never ran).
    gnorm_hist = gnorm_hist.at[0].set(
        jnp.where(st["it"] > 0, st["g0_norm"], st["gnorm"])
    )
    reason = jnp.where(
        st["reason"] == REASON_NOT_CONVERGED, REASON_MAX_ITERATIONS, st["reason"]
    )
    return OptimizeResult(
        w=st["w"],
        value=st["f"],
        grad_norm=st["gnorm"],
        iterations=st["it"],
        reason_code=reason,
        loss_history=loss_hist,
        grad_norm_history=gnorm_hist,
        evals=st["evals"],
        eval_unit="x_passes",
    )
