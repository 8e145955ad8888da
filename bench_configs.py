"""BASELINE.md benchmark configs 1, 2, 3, 5 (config 4 = bench.py headline).

Each config prints the same JSON shape as the headline: {"metric", "value",
"unit", "vs_baseline", ...}. Work accounting follows bench.py exactly: one
"visit" = one sample's feature vector processed in ONE pass over the feature
matrix, counted from the solvers' OptimizeResult.evals (x_passes unit) on the
TPU side and from scipy's nfev (×2 passes: forward + transpose) on the CPU
side. CPU baselines are measured on this image via

    python bench.py --measure-cpu-baseline-all

and pinned below (same protocol as bench.BASELINE_SAMPLES_PER_SEC);
re-measure when a workload changes.

Configs (BASELINE.md "Benchmark configs to stand up"):
  1. a1a-family LIBSVM logistic λ-sweep — the reference's own README demo
     workload (/root/reference/README.md:240-304: a1a, 50 iterations,
     λ ∈ {0.1, 1, 10, 100}). Data: the a9a fixture shipped with the
     reference's integration tests (same Adult/a1a family, 32561×123,
     binary features); synthesized with matching shape/sparsity if absent.
     The four λ fits run as ONE vmapped margin-LBFGS program
     (sweep_l2_lbfgs_margin) — the TPU answer to the reference's four
     sequential warm-started fits (ModelTraining.scala:162-200).
  2. Linear regression + L2 via TRON (trust-region Newton, ≤20 CG H·v per
     outer iteration; reference optimization/TRON.scala:148-329). evals
     counts f/g evaluations AND CG H·v products (each ≈ 2 X passes, the
     same unit), from the solver's iterations and CG steps — trial traffic
     is in the model, per VERDICT r2.
  3. Poisson elastic-net via OWL-QN (reference OWLQN.scala:39-70), L1+L2.
     CPU baseline: scipy L-BFGS-B on the split-variable (w⁺, w⁻)
     formulation — the standard smooth reformulation of the L1 term.
  5. Full GAME with Bayesian auto-tune: fixed + per-user GLMix, 8 rounds of
     GP/EI candidate evaluation through the real GameEstimator →
     CoordinateDescent → margin-LBFGS/Newton stack. Metric is wall-clock
     (the unit the reference's sequential tuner loop is judged by,
     GameEstimator.scala:364-382); baseline = the identical pipeline on
     this image's CPU (JAX CPU backend, same code, measured).
"""

from __future__ import annotations

import hashlib
import os
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

# Pinned CPU baselines (samples/sec for 1-3, wall seconds for 5), measured
# 2026-07-29 on the build image via `python bench.py --measure-cpu-baseline-all`.
CPU_BASELINES: Dict[str, float] = {
    "glmix_headline_sps": 1.302e7,  # bench.BASELINE_SAMPLES_PER_SEC
    "libsvm_sweep_sps": 2.393e7,
    "tron_linear_sps": 1.173e7,
    "poisson_owlqn_sps": 1.069e7,
    "game_tune_wall_s": 206.2,
    # scipy L-BFGS-B on CSR (2^20×2^20, 64 nnz/row): 23.23s, 38 evals.
    "sparse_wide_sps": 3.431e6,
}


def workload_fp(*parts) -> str:
    """Fingerprint of the workload-defining constants. Pinned next to each
    CPU baseline; a mismatch means the workload changed after the baseline
    was measured, so ``vs_baseline`` would silently lie (VERDICT r3 weak #7).
    """
    return hashlib.sha1(repr(parts).encode()).hexdigest()[:12]


# Fingerprints captured when the CPU baselines above were measured. If a
# workload constant changes, re-run `python bench.py --measure-cpu-baseline-all`
# and re-pin BOTH the baseline and its fingerprint.
PINNED_FPS: Dict[str, str] = {
    "glmix_headline_sps": "a89930dacf11",
    "libsvm_sweep_sps": "79c950d0e9a4",
    "tron_linear_sps": "672690cf2d1b",
    "poisson_owlqn_sps": "aecb962224bd",
    "sparse_wide_sps": "63836e95844b",
    "game_tune_wall_s": "68d65b80e022",
}


def baseline_ratio(
    key: str, fp: str, measured: Optional[float], *, lower_is_better: bool = False
) -> dict:
    """vs_baseline fields for a measured value, guarded by the workload
    fingerprint (division and the no-baseline guard live HERE, once)."""
    pinned = PINNED_FPS.get(key)
    base = CPU_BASELINES.get(key)
    if pinned != fp or not base or not measured:
        return {
            "vs_baseline": None,
            "baseline_stale": True,
            "workload_fp": fp,
            "pinned_fp": pinned,
        }
    ratio = (base / measured) if lower_is_better else (measured / base)
    return {"vs_baseline": round(ratio, 3), "workload_fp": fp}

_A9A_PATH = (
    "/root/reference/photon-client/src/integTest/resources/DriverIntegTest/input/a9a"
)
_SWEEP_LAMBDAS = (0.1, 1.0, 10.0, 100.0)  # README.md:240-304 demo grid
_SWEEP_ITERS = 50


def _progress(msg: str) -> None:
    import sys

    print(f"# {time.strftime('%H:%M:%S')} {msg}", file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# Config 1: a1a-family LIBSVM logistic regression, λ sweep
# --------------------------------------------------------------------------


def _load_libsvm_data() -> Tuple[np.ndarray, np.ndarray, str]:
    if os.path.exists(_A9A_PATH):
        from photon_tpu.io.libsvm import read_libsvm

        X, y = read_libsvm(_A9A_PATH, dim=123)
        return X, y, "a9a (reference demo fixture)"
    # Fallback: Adult-like synthetic — 123 binary indicator features,
    # ~14 active per row.
    rng = np.random.default_rng(0)
    n, d = 32561, 123
    X = (rng.uniform(size=(n, d)) < 14.0 / d).astype(np.float32)
    w = rng.normal(size=d).astype(np.float32)
    z = X @ w
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-(z - z.mean())))).astype(np.float32)
    return X, y, "synthetic a1a-like"


def run_libsvm_sweep() -> dict:
    import jax
    import jax.numpy as jnp

    from photon_tpu.data.batch import LabeledBatch
    from photon_tpu.ops.losses import LogisticLoss
    from photon_tpu.ops.objective import GLMObjective
    from photon_tpu.optim.common import OptimizerConfig
    from photon_tpu.optim.margin_lbfgs import sweep_l2_lbfgs_margin

    _progress("config 1: loading LIBSVM data")
    X, y, source = _load_libsvm_data()
    n, d = X.shape
    # Intercept column (the reference reader adds one, GLMSuite.scala role).
    X = np.concatenate([np.ones((n, 1), np.float32), X], axis=1)
    d += 1
    batch = LabeledBatch(jnp.asarray(y), jnp.asarray(X))
    obj = GLMObjective(loss=LogisticLoss, intercept_index=0)
    cfg = OptimizerConfig(max_iter=_SWEEP_ITERS, track_history=False)
    lams = jnp.asarray(_SWEEP_LAMBDAS, jnp.float32)
    k = len(_SWEEP_LAMBDAS)

    @jax.jit
    def sweep(w0s):
        res = sweep_l2_lbfgs_margin(obj, batch, w0s, lams, cfg)
        return res.w, jnp.sum(res.evals)

    _progress("config 1: compiling + warm-up")
    w, ev = sweep(jnp.zeros((k, d), jnp.float32))
    float(jnp.sum(w))
    times = []
    for rep in range(3):
        w0s = jnp.full((k, d), 1e-5 * (rep + 1), jnp.float32)
        t0 = time.perf_counter()
        w, ev = sweep(w0s)
        float(jnp.sum(w))
        times.append(time.perf_counter() - t0)
    dt = min(times)
    visits = int(ev) * n  # evals are x_passes summed over the k lanes
    sps = visits / dt
    fp = workload_fp("libsvm_sweep", source, n, d, _SWEEP_LAMBDAS, _SWEEP_ITERS)
    return dict(
        metric="libsvm_logistic_sweep_samples_per_sec_per_chip",
        value=round(sps, 1),
        unit="samples/s",
        **baseline_ratio("libsvm_sweep_sps", fp, sps),
        data=source,
        n=n,
        d=d,
        lambdas=list(_SWEEP_LAMBDAS),
        x_passes=int(ev),
        wall_s=round(dt, 4),
        baseline="scipy L-BFGS-B per λ, measured on this image",
    )


def measure_cpu_libsvm_sweep() -> float:
    import scipy.optimize

    X, y, _ = _load_libsvm_data()
    n, d = X.shape
    X = np.concatenate([np.ones((n, 1), np.float32), X], axis=1)
    d += 1
    t0 = time.perf_counter()
    visits = 0
    for lam in _SWEEP_LAMBDAS:
        def f_g(w):
            z = X @ w.astype(np.float32)
            p = 1.0 / (1.0 + np.exp(-z))
            reg_w = w.copy()
            reg_w[0] = 0.0
            val = np.sum(np.logaddexp(0, z) - y * z) + 0.5 * lam * np.dot(reg_w, reg_w)
            grad = X.T @ (p - y) + lam * reg_w.astype(np.float32)
            return float(val), grad.astype(np.float64)

        r = scipy.optimize.minimize(
            f_g, np.zeros(d), jac=True, method="L-BFGS-B",
            options=dict(maxiter=_SWEEP_ITERS),
        )
        visits += 2 * n * r.nfev
    dt = time.perf_counter() - t0
    sps = visits / dt
    print(f"# CPU libsvm sweep baseline: {sps:.4g} samples/s ({dt:.2f}s)")
    return sps


# --------------------------------------------------------------------------
# Config 2: linear regression + L2, TRON
# --------------------------------------------------------------------------

_TRON_N, _TRON_D = 1 << 21, 256


def _linear_data(seed=1):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(_TRON_N, _TRON_D)).astype(np.float32)
    X[:, 0] = 1.0
    w = (rng.normal(size=_TRON_D) / np.sqrt(_TRON_D)).astype(np.float32)
    y = (X @ w + 0.1 * rng.normal(size=_TRON_N)).astype(np.float32)
    return X, y


def run_tron_linear() -> dict:
    import jax
    import jax.numpy as jnp

    from photon_tpu.data.batch import LabeledBatch
    from photon_tpu.ops.losses import SquaredLoss
    from photon_tpu.ops.objective import GLMObjective
    from photon_tpu.optim.common import OptimizerConfig
    from photon_tpu.optim.tron import minimize_tron

    _progress("config 2: generating linear data")
    X, y = _linear_data()
    batch = LabeledBatch(jnp.asarray(y), jnp.asarray(X))
    jax.block_until_ready(batch.features)
    # use_pallas: value/grad rides the fused one-pass kernel; each CG
    # product reads X once where the batch qualifies (one_read_hvp, asked
    # of the concrete batch before the trace).
    obj = GLMObjective(
        loss=SquaredLoss, l2_weight=1.0, intercept_index=0, use_pallas=True
    )
    one_read = obj.one_read_hvp(batch)
    cfg = OptimizerConfig(max_iter=15, tol=1e-5, track_history=False)

    # ``b`` rides as a jit argument: closing over it would bake the ~2 GB
    # design matrix into the HLO as a literal (slow lowering + transfer).
    @jax.jit
    def solve(w0, b):
        res = minimize_tron(
            lambda w: obj.value_and_grad(w, b),
            None,
            w0,
            cfg,
            hvp_factory=lambda w: obj.linearized_hvp(w, b, one_read=one_read),
        )
        # f/g or H·v evaluations: the start, then per outer iteration the
        # trial, ρ's product and one product a CG step.
        return res.w, 1 + 2 * res.iterations + res.cg_steps

    _progress("config 2: compiling + warm-up")
    w, ev = solve(jnp.zeros(_TRON_D, jnp.float32), batch)
    float(jnp.sum(w))
    times = []
    for rep in range(3):
        t0 = time.perf_counter()
        w, ev = solve(jnp.full((_TRON_D,), 1e-6 * (rep + 1), jnp.float32), batch)
        float(jnp.sum(w))
        times.append(time.perf_counter() - t0)
    dt = min(times)
    # NOMINAL algorithmic visits — each f/g or H·v eval = 2 visits/sample
    # (value+grad, forward+transpose), the same accounting the scipy
    # trust-ncg baseline uses; the fused kernels serve each pair in one
    # physical X pass, which is the win vs_baseline measures.
    visits = 2 * _TRON_N * int(ev)
    sps = visits / dt
    fp = workload_fp("tron_linear", _TRON_N, _TRON_D, 15, 1e-5, 1)
    return dict(
        metric="tron_linear_l2_samples_per_sec_per_chip",
        value=round(sps, 1),
        unit="samples/s",
        **baseline_ratio("tron_linear_sps", fp, sps),
        n=_TRON_N,
        d=_TRON_D,
        evals=int(ev),
        wall_s=round(dt, 4),
        baseline="scipy trust-ncg (hessp), measured on this image",
    )


def measure_cpu_tron_linear() -> float:
    import scipy.optimize

    X, y = _linear_data()
    n = _TRON_N
    evals = 0

    def f_g(w):
        nonlocal evals
        evals += 1
        w32 = w.astype(np.float32)
        r = X @ w32 - y
        reg_w = w32.copy()
        reg_w[0] = 0.0
        val = 0.5 * float(r @ r) + 0.5 * float(reg_w @ reg_w)
        g = X.T @ r + reg_w
        return val, g.astype(np.float64)

    def hessp(w, v):
        nonlocal evals
        evals += 1
        v32 = v.astype(np.float32)
        hv = X.T @ (X @ v32) + v32
        hv[0] -= v32[0]
        return hv.astype(np.float64)

    t0 = time.perf_counter()
    scipy.optimize.minimize(
        f_g, np.zeros(_TRON_D), jac=True, hessp=hessp, method="trust-ncg",
        options=dict(maxiter=15),
    )
    dt = time.perf_counter() - t0
    sps = 2 * n * evals / dt
    print(f"# CPU TRON-linear baseline: {sps:.4g} samples/s ({dt:.2f}s, {evals} evals)")
    return sps


# --------------------------------------------------------------------------
# Config 3: Poisson elastic-net, OWL-QN
# --------------------------------------------------------------------------

_PO_N, _PO_D = 1 << 21, 256
_PO_L1, _PO_L2 = 0.1, 1.0


def _poisson_data(seed=2):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(_PO_N, _PO_D)).astype(np.float32)
    X[:, 0] = 1.0
    w = (rng.normal(size=_PO_D) / np.sqrt(_PO_D)).astype(np.float32)
    z = np.clip(X @ w, None, 3.0)
    y = rng.poisson(np.exp(z)).astype(np.float32)
    return X, y


def run_poisson_owlqn() -> dict:
    import jax
    import jax.numpy as jnp

    from photon_tpu.data.batch import LabeledBatch
    from photon_tpu.ops.losses import PoissonLoss
    from photon_tpu.ops.objective import GLMObjective
    from photon_tpu.optim.common import OptimizerConfig
    from photon_tpu.optim.owlqn import minimize_owlqn

    _progress("config 3: generating Poisson data")
    X, y = _poisson_data()
    batch = LabeledBatch(jnp.asarray(y), jnp.asarray(X))
    jax.block_until_ready(batch.features)
    # Smooth part = loss + L2; the L1 term lives in OWL-QN itself
    # (reference RegularizationContext elastic-net split). use_pallas: each
    # OWL-QN f/g evaluation is one fused X pass instead of XLA's two.
    obj = GLMObjective(
        loss=PoissonLoss, l2_weight=_PO_L2, intercept_index=0, use_pallas=True
    )
    cfg = OptimizerConfig(max_iter=60, track_history=False)
    l1_mask = jnp.ones(_PO_D, jnp.float32).at[0].set(0.0)

    # ``b`` as a jit argument, not a closure capture (see run_tron_linear).
    @jax.jit
    def solve(w0, b):
        res = minimize_owlqn(
            lambda w: obj.value_and_grad(w, b), w0, _PO_L1, cfg, l1_mask=l1_mask
        )
        return res.w, res.evals

    _progress("config 3: compiling + warm-up")
    w, ev = solve(jnp.zeros(_PO_D, jnp.float32), batch)
    float(jnp.sum(w))
    times = []
    for rep in range(3):
        t0 = time.perf_counter()
        w, ev = solve(jnp.full((_PO_D,), 1e-6 * (rep + 1), jnp.float32), batch)
        float(jnp.sum(w))
        times.append(time.perf_counter() - t0)
    dt = min(times)
    # NOMINAL algorithmic visits — value+grad = 2 visits/sample per eval,
    # the same accounting the scipy CPU baseline uses. The fused kernel
    # serves both in ONE physical X pass; that implementation win is what
    # vs_baseline measures, so the work normalization must not change.
    visits = 2 * _PO_N * int(ev)
    sps = visits / dt
    nnz = int(jnp.sum(jnp.abs(w) > 1e-8))
    fp = workload_fp("poisson_owlqn", _PO_N, _PO_D, _PO_L1, _PO_L2, 60, 2)
    return dict(
        metric="poisson_elastic_net_samples_per_sec_per_chip",
        value=round(sps, 1),
        unit="samples/s",
        **baseline_ratio("poisson_owlqn_sps", fp, sps),
        n=_PO_N,
        d=_PO_D,
        l1=_PO_L1,
        l2=_PO_L2,
        nnz_coefficients=nnz,
        evals=int(ev),
        wall_s=round(dt, 4),
        baseline="scipy L-BFGS-B on split (w+,w-) variables, measured on this image",
    )


def measure_cpu_poisson_owlqn() -> float:
    import scipy.optimize

    X, y = _poisson_data()
    n, d = _PO_N, _PO_D

    # Split-variable elastic net: w = u − v, u,v ≥ 0;
    # penalty λ₁·Σ(u+v) + λ₂/2‖u−v‖² (intercept unpenalized).
    def f_g(uv):
        u, v = uv[:d].astype(np.float32), uv[d:].astype(np.float32)
        w = u - v
        z = np.clip(X @ w, None, 30.0)
        ez = np.exp(z)
        reg_w = w.copy()
        reg_w[0] = 0.0
        l1_vec = np.full(d, _PO_L1, np.float32)
        l1_vec[0] = 0.0
        val = (
            float(np.sum(ez - y * z))
            + 0.5 * _PO_L2 * float(reg_w @ reg_w)
            + float(l1_vec @ (u + v))
        )
        dz = ez - y
        gw = X.T @ dz + _PO_L2 * reg_w
        gu = gw + l1_vec
        gv = -gw + l1_vec
        return val, np.concatenate([gu, gv]).astype(np.float64)

    bounds = [(0, None)] * (2 * d)
    t0 = time.perf_counter()
    r = scipy.optimize.minimize(
        f_g, np.zeros(2 * d), jac=True, method="L-BFGS-B", bounds=bounds,
        options=dict(maxiter=60),
    )
    dt = time.perf_counter() - t0
    sps = 2 * n * r.nfev / dt
    print(f"# CPU Poisson-OWLQN baseline: {sps:.4g} samples/s ({dt:.2f}s, {r.nfev} evals)")
    return sps


# --------------------------------------------------------------------------
# Config 6 (VERDICT r3 #4): sparse WIDE fixed effect — the path that carries
# the reference's "hundreds of billions of coefficients" story
# (/root/reference/README.md:56) scaled to one chip: n=2^20 rows, d=2^20
# coefficients, 64 nnz/row in the padded-sparse SparseFeatures layout
# (gather matvec + scatter-add rmatvec). Baseline: scipy L-BFGS-B over a
# CSR matrix with the identical objective and visit accounting.
# --------------------------------------------------------------------------

_SP_N, _SP_D, _SP_K = 1 << 20, 1 << 20, 64
_SP_ITERS = 30
_SP_SEED = 3


def _sparse_wide_data(seed=_SP_SEED):
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, _SP_D, size=(_SP_N, _SP_K)).astype(np.int32)
    vals = rng.normal(size=(_SP_N, _SP_K)).astype(np.float32)
    idx[:, 0] = 0  # intercept slot: feature 0, value 1
    vals[:, 0] = 1.0
    w_true = (rng.normal(size=_SP_D) / 8.0).astype(np.float32)
    z = np.sum(vals * w_true[idx], axis=1)
    y = (rng.uniform(size=_SP_N) < 1.0 / (1.0 + np.exp(-z))).astype(np.float32)
    return idx, vals, y


def run_sparse_wide() -> dict:
    import jax
    import jax.numpy as jnp

    from photon_tpu.data.batch import LabeledBatch, SparseFeatures
    from photon_tpu.ops.losses import LogisticLoss
    from photon_tpu.ops.objective import GLMObjective
    from photon_tpu.optim.common import OptimizerConfig
    from photon_tpu.optim.margin_lbfgs import minimize_lbfgs_margin

    _progress("config 6: generating sparse wide data (2^20 × 2^20, 64 nnz/row)")
    idx, vals, y = _sparse_wide_data()
    obj = GLMObjective(loss=LogisticLoss, l2_weight=1.0, intercept_index=0)
    cfg = OptimizerConfig(max_iter=_SP_ITERS, track_history=False)

    # Two gradient lowerings, measured head-to-head on the real chip: the
    # duplicate-index scatter-add vs the precomputed column-sorted
    # segment-sum (with_transpose_plan). XLA TPU serializes colliding
    # scatter updates, so which wins is a hardware question — the bench
    # answers it and reports the best.
    variant_walls = {}
    best = None
    import ml_dtypes

    idx_dev = jnp.asarray(idx)
    vals_f32 = jnp.asarray(vals)
    # bf16 value storage: 6B/nnz instead of 8B (margins/gradients still
    # accumulate in f32 via dtype promotion) — a bandwidth-vs-precision
    # trade the chip gets to judge alongside the scatter/segsum split.
    vals_bf16 = jnp.asarray(vals.astype(ml_dtypes.bfloat16))
    y_dev = jnp.asarray(y)
    # Plan derived from the HOST index array (no device round-trip).
    flat = idx.reshape(-1)
    order = np.argsort(flat, kind="stable")
    csc_order = jnp.asarray(order.astype(np.int32))
    csc_segments = jnp.asarray(flat[order].astype(np.int32))
    variants = {
        "scatter": SparseFeatures(idx_dev, vals_f32, _SP_D),
        "segsum": SparseFeatures(idx_dev, vals_f32, _SP_D, csc_order, csc_segments),
        "scatter_bf16": SparseFeatures(idx_dev, vals_bf16, _SP_D),
        "segsum_bf16": SparseFeatures(
            idx_dev, vals_bf16, _SP_D, csc_order, csc_segments
        ),
    }
    # One jitted solve shared by all variants, with the batch as a traced
    # argument — a per-variant closure would bake ~0.5 GB of indices/values
    # into each variant's HLO as literals.
    @jax.jit
    def solve(w0, b):
        res = minimize_lbfgs_margin(obj, b, w0, cfg)
        return res.w, res.evals

    for variant, feats in variants.items():
        batch = LabeledBatch(y_dev, feats)
        jax.block_until_ready(batch.features.values)

        _progress(f"config 6: compiling + warm-up ({variant})")
        w, ev = solve(jnp.zeros(_SP_D, jnp.float32), batch)
        float(jnp.sum(w))
        times = []
        for rep in range(3):
            t0 = time.perf_counter()
            w, ev = solve(jnp.full((_SP_D,), 1e-6 * (rep + 1), jnp.float32), batch)
            float(jnp.sum(w))
            times.append(time.perf_counter() - t0)
        variant_walls[f"rmatvec_{variant}_wall_s"] = round(min(times), 4)
        if best is None or min(times) < best[0]:
            best = (min(times), variant, int(ev))
    dt, best_variant, ev = best
    visits = _SP_N * ev  # evals count X passes directly (margin solver)
    sps = visits / dt
    # Modeled sparse traffic: one pass reads (idx int32 + vals f32) once;
    # the gradient pass additionally scatters into a (d,) f32 accumulator.
    nnz_bytes = _SP_N * _SP_K * 8
    gbps = ev * nnz_bytes / dt / 1e9
    fp = workload_fp("sparse_wide", _SP_N, _SP_D, _SP_K, _SP_ITERS, _SP_SEED)
    return dict(
        metric="sparse_wide_logistic_samples_per_sec_per_chip",
        value=round(sps, 1),
        unit="samples/s",
        **baseline_ratio("sparse_wide_sps", fp, sps),
        n=_SP_N,
        d=_SP_D,
        nnz_per_row=_SP_K,
        x_passes=ev,
        wall_s=round(dt, 4),
        rmatvec_variant=best_variant,
        **variant_walls,
        nnz_traffic_gbps=round(gbps, 1),
        baseline="scipy L-BFGS-B on CSR, measured on this image",
    )


def measure_cpu_sparse_wide() -> float:
    import scipy.optimize
    import scipy.sparse

    idx, vals, y = _sparse_wide_data()
    indptr = np.arange(_SP_N + 1, dtype=np.int64) * _SP_K
    X = scipy.sparse.csr_matrix(
        (vals.ravel(), idx.ravel().astype(np.int64), indptr), shape=(_SP_N, _SP_D)
    )

    def f_g(w):
        w32 = w.astype(np.float32)
        z = X @ w32
        p = 1.0 / (1.0 + np.exp(-z))
        reg_w = w32.copy()
        reg_w[0] = 0.0
        val = float(np.sum(np.logaddexp(0, z) - y * z)) + 0.5 * float(reg_w @ reg_w)
        grad = X.T @ (p - y).astype(np.float32) + reg_w
        return val, grad.astype(np.float64)

    t0 = time.perf_counter()
    r = scipy.optimize.minimize(
        f_g, np.zeros(_SP_D), jac=True, method="L-BFGS-B",
        options=dict(maxiter=_SP_ITERS),
    )
    dt = time.perf_counter() - t0
    sps = 2 * _SP_N * r.nfev / dt
    print(f"# CPU sparse-wide baseline: {sps:.4g} samples/s ({dt:.2f}s, {r.nfev} evals)")
    return sps


# Config 6 at CPU-mesh scale (VERDICT r5 #4): the SAME four rmatvec
# lowerings as run_sparse_wide, shrunk so the head-to-head completes on a
# 1-core CPU host in minutes, not hours. The winner sets
# data/batch.py::DEFAULT_TRANSPOSE_PLAN for the current backend; the full
# 2^20 config answers the question again on real TPU hardware.
_RM_N, _RM_D, _RM_K = 1 << 16, 1 << 16, 32
_RM_ITERS = 6


def run_rmatvec_cpu_ab() -> dict:
    import jax
    import jax.numpy as jnp
    import ml_dtypes

    from photon_tpu.data.batch import LabeledBatch, SparseFeatures
    from photon_tpu.io.columnar import _available_cores
    from photon_tpu.ops.losses import LogisticLoss
    from photon_tpu.ops.objective import GLMObjective
    from photon_tpu.optim.common import OptimizerConfig
    from photon_tpu.optim.margin_lbfgs import minimize_lbfgs_margin

    _progress(
        f"rmatvec CPU A/B: generating data (2^16 × 2^16, {_RM_K} nnz/row)"
    )
    rng = np.random.default_rng(_SP_SEED)
    idx = rng.integers(0, _RM_D, size=(_RM_N, _RM_K)).astype(np.int32)
    vals = rng.normal(size=(_RM_N, _RM_K)).astype(np.float32)
    idx[:, 0] = 0
    vals[:, 0] = 1.0
    w_true = (rng.normal(size=_RM_D) / 8.0).astype(np.float32)
    z = np.sum(vals * w_true[idx], axis=1)
    y = (rng.uniform(size=_RM_N) < 1.0 / (1.0 + np.exp(-z))).astype(np.float32)

    obj = GLMObjective(loss=LogisticLoss, l2_weight=1.0, intercept_index=0)
    cfg = OptimizerConfig(max_iter=_RM_ITERS, track_history=False)
    idx_dev = jnp.asarray(idx)
    vals_f32 = jnp.asarray(vals)
    vals_bf16 = jnp.asarray(vals.astype(ml_dtypes.bfloat16))
    y_dev = jnp.asarray(y)
    flat = idx.reshape(-1)
    order = np.argsort(flat, kind="stable")
    csc_order = jnp.asarray(order.astype(np.int32))
    csc_segments = jnp.asarray(flat[order].astype(np.int32))
    variants = {
        "scatter": SparseFeatures(idx_dev, vals_f32, _RM_D),
        "segsum": SparseFeatures(idx_dev, vals_f32, _RM_D, csc_order, csc_segments),
        "scatter_bf16": SparseFeatures(idx_dev, vals_bf16, _RM_D),
        "segsum_bf16": SparseFeatures(
            idx_dev, vals_bf16, _RM_D, csc_order, csc_segments
        ),
    }

    @jax.jit
    def solve(w0, b):
        res = minimize_lbfgs_margin(obj, b, w0, cfg)
        return res.w, res.evals

    walls = {}
    best = None
    for variant, feats in variants.items():
        batch = LabeledBatch(y_dev, feats)
        jax.block_until_ready(batch.features.values)
        _progress(f"rmatvec CPU A/B: compiling + warm-up ({variant})")
        w, ev = solve(jnp.zeros(_RM_D, jnp.float32), batch)
        float(jnp.sum(w))
        times = []
        for rep in range(3):
            t0 = time.perf_counter()
            w, ev = solve(jnp.full((_RM_D,), 1e-6 * (rep + 1), jnp.float32), batch)
            float(jnp.sum(w))
            times.append(time.perf_counter() - t0)
        walls[f"rmatvec_{variant}_wall_s"] = round(min(times), 4)
        if best is None or min(times) < best[0]:
            best = (min(times), variant)
    from photon_tpu.data.batch import default_transpose_plan

    return dict(
        metric="rmatvec_cpu_ab_best_wall_s",
        value=best[0],
        unit="s",
        winner=best[1],
        n=_RM_N,
        d=_RM_D,
        nnz_per_row=_RM_K,
        iters=_RM_ITERS,
        host_cores=_available_cores(),
        backend=jax.default_backend(),
        default_transpose_plan=default_transpose_plan(),
        **walls,
    )


def run_rmatvec_sharded_ab() -> dict:
    """Scatter-add vs column-sorted segment-sum rmatvec ON THE SHARDED
    PATH: the run_rmatvec_cpu_ab head-to-head re-run with the batch rows
    sharded over an 8-virtual-device mesh, so the gradient's transpose
    product lowers to per-device partial rmatvec + one cross-device
    reduction — the multichip FE step's actual program. The structural
    asymmetry this measures: the scatter-add partitions trivially on the
    sample axis (each device scatters ITS rows, psum merges), while the
    column-sorted plan's flat (n·k,) gather/segment arrays cut across the
    row partition, forcing SPMD to insert collectives (or replicate the
    nnz stream) before it can segment-sum.

    Must run in a process whose FIRST jax touch forced the 8-device mesh
    (``bench.py --rmatvec-sharded-ab`` does). Scaled down from the
    unsharded A/B (n=2^15, d=2^14) — the verdict wanted is the lowering
    ORDERING under sharding, not peak numbers."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from photon_tpu.data.batch import (
        LabeledBatch,
        SparseFeatures,
        default_transpose_plan,
    )
    from photon_tpu.ops.losses import LogisticLoss
    from photon_tpu.ops.objective import GLMObjective
    from photon_tpu.optim.common import OptimizerConfig
    from photon_tpu.optim.margin_lbfgs import minimize_lbfgs_margin
    from photon_tpu.parallel.mesh import make_mesh

    n, d, k, iters = 1 << 15, 1 << 14, _RM_K, _RM_ITERS
    mesh = make_mesh(n_data=8, devices=jax.devices()[:8])
    rows = NamedSharding(mesh, PartitionSpec("data"))
    repl = NamedSharding(mesh, PartitionSpec())

    rng = np.random.default_rng(_SP_SEED)
    idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
    vals = rng.normal(size=(n, k)).astype(np.float32)
    idx[:, 0] = 0
    vals[:, 0] = 1.0
    w_true = (rng.normal(size=d) / 8.0).astype(np.float32)
    z = np.sum(vals * w_true[idx], axis=1)
    y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-z))).astype(np.float32)
    flat = idx.reshape(-1)
    order = np.argsort(flat, kind="stable")

    def put(x, sh):
        return jax.device_put(jnp.asarray(x), sh)

    variants = {
        "scatter": SparseFeatures(put(idx, rows), put(vals, rows), d),
        "segsum": SparseFeatures(
            put(idx, rows), put(vals, rows), d,
            put(order.astype(np.int32), rows),
            put(flat[order].astype(np.int32), rows),
        ),
    }
    obj = GLMObjective(loss=LogisticLoss, l2_weight=1.0, intercept_index=0)
    cfg = OptimizerConfig(max_iter=iters, track_history=False)

    @jax.jit
    def solve(w0, b):
        res = minimize_lbfgs_margin(obj, b, w0, cfg)
        return res.w, res.evals

    walls, sols = {}, {}
    best = None
    for variant, feats in variants.items():
        batch = LabeledBatch(put(y, rows), feats)
        jax.block_until_ready(batch.features.values)
        _progress(f"rmatvec sharded A/B: compiling + warm-up ({variant})")
        w, _ = solve(put(np.zeros(d, np.float32), repl), batch)
        float(jnp.sum(w))
        times = []
        for rep in range(3):
            t0 = time.perf_counter()
            w, _ = solve(
                put(np.full(d, 1e-6 * (rep + 1), np.float32), repl), batch
            )
            float(jnp.sum(w))
            times.append(time.perf_counter() - t0)
        walls[f"rmatvec_{variant}_sharded_wall_s"] = round(min(times), 4)
        sols[variant] = np.asarray(w)
        if best is None or min(times) < best[0]:
            best = (min(times), variant)
    # Both lowerings compute the same transpose product; under sharding the
    # reduction grouping differs, so parity is allclose-level.
    max_dw = float(np.abs(sols["scatter"] - sols["segsum"]).max())
    return dict(
        metric="rmatvec_sharded_ab_best_wall_s",
        value=best[0],
        unit="s",
        winner=best[1],
        n=n,
        d=d,
        nnz_per_row=k,
        iters=iters,
        mesh_devices=int(np.prod(list(mesh.shape.values()))),
        backend=jax.default_backend(),
        max_abs_dw=max_dw,
        default_transpose_plan=default_transpose_plan(),
        **walls,
    )


# --------------------------------------------------------------------------
# Config 5: full GAME + Bayesian auto-tune (wall-clock)
# --------------------------------------------------------------------------

_G_N, _G_DFIX, _G_DRE, _G_E = 1 << 17, 64, 8, 1024
_G_ROUNDS = 8


def _game_tune_pipeline(batch_size: int = 1) -> Tuple[float, float]:
    """Run the full GAME + Bayesian tuning pipeline once on the current JAX
    default backend. Returns (wall seconds, best AUC). ``batch_size > 1``
    evaluates that many candidates per round through the vmapped
    one-program path (estimators/batched_tuning.py)."""
    import jax.numpy as jnp

    from photon_tpu.data.game_data import GameBatch
    from photon_tpu.estimators.config import (
        FixedEffectCoordinateConfig,
        GameOptimizationConfig,
        RandomEffectCoordinateConfig,
        RegularizationConfig,
    )
    from photon_tpu.estimators.evaluation_function import GameEstimatorEvaluationFunction
    from photon_tpu.estimators.game_estimator import GameEstimator
    from photon_tpu.evaluation import EvaluationSuite
    from photon_tpu.evaluation.suite import EvaluatorSpec
    from photon_tpu.hyperparameter.tuner import AtlasTuner, TuningMode
    from photon_tpu.types import TaskType

    rng = np.random.default_rng(5)
    n, d_fix, d_re, e = _G_N, _G_DFIX, _G_DRE, _G_E
    Xf = rng.normal(size=(n, d_fix)).astype(np.float32)
    Xf[:, 0] = 1.0
    Xr = rng.normal(size=(n, d_re)).astype(np.float32)
    Xr[:, 0] = 1.0
    users = rng.integers(0, e, size=n).astype(np.int32)
    w_fix = (rng.normal(size=d_fix) / np.sqrt(d_fix)).astype(np.float32)
    w_users = rng.normal(scale=1.0, size=(e, d_re)).astype(np.float32)
    logits = Xf @ w_fix + np.sum(Xr * w_users[users], axis=1)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-logits))).astype(np.float32)

    half = n // 2
    def mk_batch(sl):
        return GameBatch(
            label=jnp.asarray(y[sl]),
            offset=jnp.zeros(len(y[sl]), jnp.float32),
            weight=jnp.ones(len(y[sl]), jnp.float32),
            features={"global": jnp.asarray(Xf[sl]), "per_user": jnp.asarray(Xr[sl])},
            entity_ids={"userId": jnp.asarray(users[sl])},
        )

    train, valid = mk_batch(slice(0, half)), mk_batch(slice(half, n))

    base_config = GameOptimizationConfig(
        reg={
            "global": RegularizationConfig(weight=1.0),
            "per_user": RegularizationConfig(weight=1.0),
        }
    )
    estimator = GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_configs=[
            FixedEffectCoordinateConfig("global", "global"),
            RandomEffectCoordinateConfig("per_user", "userId", "per_user"),
        ],
        num_iterations=2,
        intercept_indices={"global": 0, "per_user": 0},
        num_entities={"userId": e},
    )
    suite = EvaluationSuite([EvaluatorSpec.parse("AUC")])

    eval_fn = GameEstimatorEvaluationFunction(
        estimator, base_config, train, valid, suite, is_opt_max=True
    )
    t0 = time.perf_counter()
    _x, best_signed, _obs = AtlasTuner().search(
        _G_ROUNDS, eval_fn.dim, TuningMode.BAYESIAN, eval_fn,
        search_range=eval_fn.search_range, seed=3, batch_size=batch_size,
    )
    dt = time.perf_counter() - t0
    return dt, -float(best_signed)  # signed = -AUC (search minimizes)


def run_game_tuning() -> dict:
    _progress("config 5: GAME + Bayesian auto-tune on TPU (sequential)")
    dt_seq, best = _game_tune_pipeline()
    _progress("config 5: batched rounds (8 candidates / program)")
    dt_batch, best_b = _game_tune_pipeline(batch_size=_G_ROUNDS)
    dt = min(dt_seq, dt_batch)
    fp = workload_fp("game_tune", _G_N, _G_DFIX, _G_DRE, _G_E, _G_ROUNDS)
    return dict(
        metric="game_bayes_tuning_wall_clock",
        value=round(dt, 2),
        unit="seconds",
        # >1 = faster than CPU
        **baseline_ratio("game_tune_wall_s", fp, dt, lower_is_better=True),
        rounds=_G_ROUNDS,
        n=_G_N,
        entities=_G_E,
        best_auc=round(max(best, best_b), 4),
        sequential_wall_s=round(dt_seq, 2),
        batched_wall_s=round(dt_batch, 2),
        baseline="identical sequential pipeline on this image's CPU (JAX CPU backend)",
    )


def measure_cpu_game_tuning() -> float:
    """Run the identical pipeline on the JAX CPU backend in a subprocess
    (a fresh process is the only clean way to force platform selection)."""
    import subprocess
    import sys

    code = (
        "from photon_tpu.utils.virtual_devices import force_virtual_cpu_devices;"
        "force_virtual_cpu_devices(1);"
        "import bench_configs as bc, json;"
        "dt, best = bc._game_tune_pipeline();"
        "print(json.dumps({'wall_s': dt, 'best_auc': best}))"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    import json as _json

    line = out.stdout.strip().splitlines()[-1]
    dt = _json.loads(line)["wall_s"]
    print(f"# CPU GAME-tuning baseline: {dt:.1f}s wall")
    return dt


# --------------------------------------------------------------------------


# (metric name as emitted on success — error lines reuse it so failures
# join the same metric series, per r4 review)
EXTRA_CONFIGS = [
    ("libsvm_logistic_sweep_samples_per_sec_per_chip", "run_libsvm_sweep"),
    ("tron_linear_l2_samples_per_sec_per_chip", "run_tron_linear"),
    ("poisson_elastic_net_samples_per_sec_per_chip", "run_poisson_owlqn"),
    ("sparse_wide_logistic_samples_per_sec_per_chip", "run_sparse_wide"),
    ("game_bayes_tuning_wall_clock", "run_game_tuning"),
]


def run_extra_configs() -> List[dict]:
    """Run configs 1/2/3/6/5. One config failing yields an {"error": ...}
    line instead of killing the whole evidence run (VERDICT r3 weak #2)."""
    results = []
    for name, fn_name in EXTRA_CONFIGS:
        try:
            results.append(globals()[fn_name]())
        except Exception as exc:  # noqa: BLE001 — evidence must survive
            results.append({
                "metric": name,
                "error": type(exc).__name__,
                "detail": str(exc)[:300],
            })
    return results


def measure_all_cpu_baselines() -> None:
    print("# measuring CPU baselines for configs 1, 2, 3, 6, 5 — pin these in "
          "bench_configs.CPU_BASELINES")
    print(f"#   libsvm_sweep_sps = {measure_cpu_libsvm_sweep():.4g}")
    print(f"#   tron_linear_sps = {measure_cpu_tron_linear():.4g}")
    print(f"#   poisson_owlqn_sps = {measure_cpu_poisson_owlqn():.4g}")
    print(f"#   sparse_wide_sps = {measure_cpu_sparse_wide():.4g}")
    print(f"#   game_tune_wall_s = {measure_cpu_game_tuning():.4g}")
