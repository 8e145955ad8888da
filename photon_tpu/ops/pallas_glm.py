"""Pallas TPU kernel: fused GLM objective value + gradient in one pass over X.

Role parity: the reference's aggregator hot loop — per-sample dot product +
axpy accumulated across the cluster (ValueAndGradientAggregator.add/merge,
photon-lib aggregators/ValueAndGradientAggregator.scala:242-285). On TPU the
same computation as XLA emits it is TWO passes over X in HBM per objective
evaluation: one for ``z = X @ w`` and one for ``grad = Xᵀ · dz`` (the
transpose blocks fusion). Since the fixed-effect solve is HBM-bandwidth
bound (SURVEY.md §6 cost model: one such evaluation per L-BFGS line-search
point), halving X traffic halves the step time.

This kernel streams row-tiles of X through VMEM once per evaluation:

    per tile:  z  = X_tile @ w + offset          (MXU)
               lv = weight · loss(z, y)          (VPU, fused)
               dz = weight · loss'(z, y)         (VPU, fused)
               loss_acc += Σ lv                  (SMEM scalar)
               grad_acc += X_tileᵀ @ dz          (MXU, VMEM accumulator)

Grid steps on TPU are sequential per core, so accumulating into the same
output block across steps is race-free (standard reduction pattern). The
feature dimension is kept whole per tile (w and one (TILE_N, d) tile must
fit VMEM) — beyond that, the replicated path or the feature-sharded
shard_map path (photon_tpu.parallel.feature_sharded) applies.

L2/normalization are folded by the wrapper (effective-coefficient algebra,
photon_tpu.data.normalization), keeping the kernel a pure data-loss pass.

One lowering per entry point: tall rebalanced tiles (``_tile_geometry``)
on a grid declared sequential (``_SEQUENTIAL_GRID``, a correctness
requirement on megacore parts, not a tunable), with no per-call ``tile_n``
override. The data-Hessian product (``data_hvp_operator``) is the one-read
form of the fixed effect's TRON products wherever the batch qualifies
(``GLMObjective.one_read_hvp``); the linearize/transpose product in
ops/objective.py serves every other caller. On a TPU v5e, at 2^22 × 256
float32, it takes 5.75 ms a product (5.70 inside TRON's CG loop), against
11.42 ms for XLA's forward and transpose passes and 5.24 ms for one read
of X at 819 GB/s, and reads 1.2e-7 from a float64 product. It multiplies
and adds in float32 on the VPU: the same products on the MXU at default
precision take as long but round X to bfloat16 (1.6e-3 from float64), and
at HIGHEST they take 7.49 ms. Taller tiles, a raised VMEM limit or an
unrolled slab loop move none of it.

VMEM layout (PR 21, the first compile for a real v5e): every per-sample
vector (label, offset, weight, the margins output; d2 in rows of 128)
crosses the kernel boundary as a LANE-DENSE row block, and the margins are
computed in that orientation (``w_row · X_tileᵀ``). A ``(tile_n, 1)``
column block is laid out 128 lanes wide in VMEM — 512 bytes per sample per
buffer — which at tall tiles cost ten times the X tile itself and made the
TPU compiler refuse every d=256 call. ``_tile_geometry`` budgets every
block the call holds against the default 16 MB scoped-VMEM limit; the
ahead-of-time compile in tests/test_tpu_aot_compile.py keeps that true.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from photon_tpu.ops.losses import PointwiseLoss

Array = jax.Array

# Both kernels ACCUMULATE into their output block across grid steps, which
# requires the row-tile grid to run sequentially. Mosaic infers that from
# the constant output index map, but megacore parts (v4/v5p) split
# "parallel" grid dims across cores — declare the semantics explicitly so
# the reduction stays correct everywhere, not just on single-core v5e.
_SEQUENTIAL_GRID = pltpu.CompilerParams(dimension_semantics=("arbitrary",))


def pallas_available() -> bool:
    """True when the fused kernels COMPILE (Mosaic) rather than interpret:
    the default backend is a TPU. Off-TPU the kernels still run in
    interpreter mode (orders slower) — production call sites gate on this;
    tests opt into ``interpret=True`` explicitly."""
    return jax.default_backend() == "tpu"


# Requested row-tile height; the VMEM budget below is the real constraint,
# so this just needs to be "large". Grid steps run sequentially and carry
# fixed per-step cost (DMA semaphores, loop bookkeeping), which short tiles
# multiply: the last chip run before PR 12 used 512-row tiles, 4096 steps
# on the n=2^21, d=256 headline, and moved 5% of HBM peak.
DEFAULT_TILE_N = 8192
# Feature dims above this exceed the VMEM tile budget; callers fall back.
MAX_FUSED_DIM = 4096

_LANE = 128
# What one pallas_call may hold in VMEM: the default scoped limit on every
# TPU generation this repo names is 16 MB; a quarter is left to Mosaic's
# own scratch. No vmem_limit_bytes override — the default limit is the one
# number that holds on a chip nobody has measured yet.
_VMEM_BUDGET = 12 * 1024 * 1024
# Resident blocks (w / grad / loss partials) and in-kernel row temporaries.
_VMEM_FIXED = 1024 * 1024
# A lane-dense (1, tile_n) float32 row block, double-buffered: bytes/sample.
ROW_VEC_BYTES = 2 * 4
# A (tile_n, 1) float32 column block is laid out 128 lanes wide in VMEM,
# double-buffered: bytes/sample.
COL_VEC_BYTES = 2 * 4 * _LANE


def x_row_bytes(d_pad: int, dtype) -> int:
    """VMEM bytes one row of the streamed X tile costs. Grid inputs are
    double-buffered; for a packed (sub-32-bit) tile Mosaic additionally
    holds one relayout copy for the contraction over the row axis
    (measured by bisecting ``vmem_limit_bytes`` in the ahead-of-time v5e
    compile, PR 21: bf16 8192×256 needs 12.3 MB = 3 tiles, f32 4096×256
    needs 8.1 MB = 2 tiles)."""
    itemsize = jnp.dtype(dtype).itemsize
    return d_pad * itemsize * (2 if itemsize >= 4 else 3)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _tile_geometry(
    n: int, tile_n: int, row_bytes: int, fixed_bytes: int = _VMEM_FIXED,
    align: int = _LANE,
) -> Tuple[int, int]:
    """Choose (tile_n, n_pad) for ``n`` sample rows.

    ``row_bytes`` is what ONE sample row costs in VMEM across EVERY block
    the call holds at the width VMEM really gives it (``x_row_bytes`` plus
    ``ROW_VEC_BYTES`` / ``COL_VEC_BYTES`` per per-sample vector), and
    ``fixed_bytes`` what it holds regardless of tile height; together they
    stay inside ``_VMEM_BUDGET``. Then, in order: the tile is never taller
    than the data; a height that divides ``n`` exactly is preferred (no
    padded copy of X in HBM); otherwise heights are REBALANCED across the
    grid so padding never exceeds ``align - 1`` rows per tile — a tall
    default must not round n=8200 up to two full 8192 tiles (that would
    nearly double the HBM traffic this kernel exists to minimize).
    ``align`` is the lane width for lane-dense row blocks and the dtype's
    sublane count for column blocks.
    """
    n = max(n, 1)
    cap = max(_VMEM_BUDGET - fixed_bytes, 0) // row_bytes
    cap = max(align, min(tile_n, cap, _round_up(n, align)) // align * align)
    for t in range(cap, cap // 2, -align):
        if n % t == 0:
            return t, n
    n_tiles = -(-n // cap)
    tile_n = _round_up(-(-n // n_tiles), align)
    return tile_n, n_tiles * tile_n


def _check_fused_width(d: int, fn_name: str) -> None:
    """Every in-tree caller is gated by GLMObjective._can_fuse; a direct
    caller above the width limit would get a tile clamped to one lane row,
    blow the VMEM budget, and die in Mosaic with an opaque compile error
    (ADVICE r4). Fail fast and descriptively instead."""
    if d > MAX_FUSED_DIM:
        raise ValueError(
            f"{fn_name} supports d <= {MAX_FUSED_DIM} (got d={d}); "
            "use the two-pass XLA path for wider problems"
        )


# z_row = w_row · X_tileᵀ (contract the feature axis of both operands) and
# out_row = t_row · X_tile (contract the sample axis): every per-sample
# value stays a lane-dense (1, tile_n) row from load to store.
_CONTRACT_FEATURES = (((1,), (1,)), ((), ()))
_CONTRACT_SAMPLES = (((1,), (0,)), ((), ()))


def _kernel(loss: PointwiseLoss, w_ref, x_ref, y_ref, off_ref, wt_ref,
            loss_ref, grad_ref, z_ref=None):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        grad_ref[...] = jnp.zeros_like(grad_ref)

    x = x_ref[...]
    z = jax.lax.dot_general(
        w_ref[...], x, _CONTRACT_FEATURES, preferred_element_type=jnp.float32
    ) + off_ref[...]
    if z_ref is not None:
        # Fresh margins out — lets margin-space solvers refresh their carried
        # margins exactly (no incremental z += α·u drift) at no extra X pass.
        z_ref[...] = z
    y = y_ref[...]
    wt = wt_ref[...]

    lv = wt * loss.value(z, y)
    dz = wt * loss.dz(z, y)

    # Per-tile loss partial, summed by the wrapper (pairwise-ish; no
    # cross-step scalar accumulation).
    loss_ref[...] = jnp.sum(lv, axis=1, keepdims=True)
    # dz stays float32 against a bfloat16 X (Mosaic lowers the mixed dot);
    # accumulated across sequential grid steps.
    grad_ref[...] += jax.lax.dot_general(
        dz, x, _CONTRACT_SAMPLES, preferred_element_type=jnp.float32
    )


# The one-read product's inner loop reads X in slabs of _SUB rows (one
# lane-dense row of d2) by at most _CHUNK features, all on the VPU in
# float32: every product is an exact float32 multiply and every sum a
# float32 add, as in XLA's own multiply-and-reduce fusions.
_SUB = 128
_CHUNK = 512


def _hvp_kernel(n_tiles, last_rows, v_ref, x_ref, d2_ref, out_ref, acc_ref):
    """One-read GLM data-Hessian product, per row tile of X: for each slab
    of _SUB rows, u = X_slab·v (multiply, then a lane reduction: one float
    a row), t = d2 ∘ u, acc += X_slabᵀ·t (multiply, then sums over the
    rows). The tile is read from HBM once for both products; the slab is
    read from VMEM twice. ``acc`` keeps eight partial rows across the grid
    and folds them into ``out`` at the last step.

    ``d2_ref`` holds the tile's d2 as lane-dense rows of _SUB, one a slab;
    their transpose puts slab s's d2 in column s, which a lane mask and a
    lane reduction turn into the slab's column. The last tile of a grid
    that does not divide n reads rows past the end of X, whose contents
    are undefined: there ``last_rows`` masks them to zero before any
    product, so no padded copy of X is ever needed."""
    i = pl.program_id(0)
    d = x_ref.shape[1]
    chunks = [(c, min(_CHUNK, d - c)) for c in range(0, d, _CHUNK)]

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    d2t = d2_ref[...].T  # (_SUB, slabs): column s holds slab s's d2
    lane = jax.lax.broadcasted_iota(jnp.int32, d2t.shape, 1)

    def run(n_slabs: int, limit: Optional[int]):
        def slab(s, carry):
            r0 = pl.multiple_of(s * _SUB, _SUB)
            if limit is not None:
                row = jax.lax.broadcasted_iota(jnp.int32, (_SUB, 1), 0) + r0
                keep = row < limit

            def load(c, w):
                x = x_ref[pl.ds(r0, _SUB), pl.ds(c, w)].astype(jnp.float32)
                return x if limit is None else jnp.where(keep, x, 0.0)

            u = sum(
                jnp.sum(load(c, w) * v_ref[:, pl.ds(c, w)], axis=1,
                        keepdims=True)
                for c, w in chunks
            )
            d2s = jnp.sum(jnp.where(lane == s, d2t, 0.0), axis=1, keepdims=True)
            t = u * d2s
            for c, w in chunks:
                acc_ref[:, pl.ds(c, w)] += jnp.sum(
                    (t * load(c, w)).reshape(_SUB // 8, 8, w), axis=0
                )
            return carry

        jax.lax.fori_loop(0, n_slabs, slab, 0)

    slabs = x_ref.shape[0] // _SUB
    if last_rows == x_ref.shape[0]:
        run(slabs, None)
    else:
        @pl.when(i < n_tiles - 1)
        def _full():
            run(slabs, None)

        @pl.when(i == n_tiles - 1)
        def _last():
            run(-(-last_rows // _SUB), last_rows)

    @pl.when(i == n_tiles - 1)
    def _fold():
        out_ref[...] = jnp.sum(acc_ref[...], axis=0, keepdims=True)


def _row_blocks(n_tiles: int, tile_n: int):
    """(reshape, BlockSpec) for a per-sample vector as lane-dense rows: the
    (n_pad,) vector becomes (n_tiles, 1, tile_n) — a free bitcast — and each
    grid step sees one (1, tile_n) row."""
    def as_rows(v: Array) -> Array:
        return v.astype(jnp.float32).reshape(n_tiles, 1, tile_n)

    return as_rows, pl.BlockSpec((None, 1, tile_n), lambda i: (i, 0, 0))


def data_hvp_operator(
    X: Array, d2: Array, interpret: Optional[bool] = None,
) -> Callable[[Array], Array]:
    """``v -> Xᵀ·diag(d2)·X·v`` in ONE read of ``X`` a product, where XLA
    pays two (the forward and the transpose matrix-vector products): the
    data term of a GLM Hessian-vector product at fixed margins, for
    TRON's conjugate-gradient steps (``GLMObjective.linearized_hvp(...,
    one_read=True)``). ``d2`` is laid out once here, so the operator's
    products share it.

    Arithmetic is float32 on the VPU (``_hvp_kernel``): a bfloat16 X is
    widened to float32 before any product, and no MXU pass rounds an
    operand. X is never padded or copied: the last tile of a grid that
    does not divide n masks the rows past the end; only d2 (n floats) is
    padded to whole tiles. Tile geometry follows ``DEFAULT_TILE_N`` (read
    at call time; tests vary it by monkeypatching), in heights of whole
    _SUB-row slabs.
    """
    n, d = X.shape
    _check_fused_width(d, "data_hvp_operator")
    if interpret is None:
        interpret = not pallas_available()
    d_pad = _round_up(max(d, 1), _LANE)
    fixed = _VMEM_FIXED + 8 * d_pad * 4 + 4 * _SUB * min(d_pad, _CHUNK) * 4
    tile_n, n_pad = _tile_geometry(
        n, DEFAULT_TILE_N, x_row_bytes(d_pad, X.dtype) + ROW_VEC_BYTES,
        fixed_bytes=fixed,
    )
    n_tiles = n_pad // tile_n
    slabs = tile_n // _SUB
    rows = _round_up(slabs, 8)
    # d2 as lane-dense rows of _SUB, ``rows`` of them a tile (zero past n).
    d2 = jnp.pad(d2.astype(jnp.float32), (0, n_pad - n))
    d2 = d2.reshape(n_tiles, slabs, _SUB)
    if rows != slabs:
        d2 = jnp.pad(d2, ((0, 0), (0, rows - slabs), (0, 0)))
    d2 = d2.reshape(n_tiles * rows, _SUB)
    resident = pl.BlockSpec((1, d), lambda i: (0, 0))
    call = pl.pallas_call(
        functools.partial(_hvp_kernel, n_tiles, n - (n_tiles - 1) * tile_n),
        grid=(n_tiles,),
        in_specs=[
            resident,                                     # v
            pl.BlockSpec((tile_n, d), lambda i: (i, 0)),  # X row tile
            pl.BlockSpec((rows, _SUB), lambda i: (i, 0)),  # d2 rows
        ],
        out_specs=resident,
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((8, d), jnp.float32)],
        compiler_params=None if interpret else _SEQUENTIAL_GRID,
        interpret=interpret,
    )

    def product(v: Array) -> Array:
        return call(v.astype(jnp.float32)[None, :], X, d2)[0]

    return product


def fused_data_hvp(
    v: Array,
    X: Array,
    d2: Array,
    interpret: Optional[bool] = None,
) -> Array:
    """Xᵀ·diag(d2)·X·v in ONE read of ``X``: ``data_hvp_operator``'s
    product, for a single vector."""
    return data_hvp_operator(X, d2, interpret)(v)


def fused_data_value_and_grad(
    loss: PointwiseLoss,
    w: Array,
    X: Array,
    label: Array,
    offset: Array,
    weight: Array,
    interpret: Optional[bool] = None,
    return_margins: bool = False,
) -> Tuple[Array, ...]:
    """Σᵢ wᵢ·loss(xᵢ·w + offsetᵢ, yᵢ) and its gradient w.r.t. ``w``, in one
    pass over ``X``. Pure data term — no regularization, no normalization.

    Pads rows to the tile height with weight-0 samples and features to the
    lane width; both paddings are exact (zero contribution).
    ``interpret=None`` auto-selects interpreter mode off-TPU (CPU tests).

    ``X`` may be bfloat16 (half the HBM traffic of the bandwidth-bound read);
    margins and all accumulation stay float32 via preferred_element_type.

    With ``return_margins=True`` also returns the fresh margins
    ``z = X·w + offset`` (float32, shape (n,)) computed in the same pass —
    the margin-space L-BFGS uses this to refresh its carried margins exactly
    every iteration instead of accumulating ``z += α·u`` rounding drift.

    Tile geometry is fixed by ``DEFAULT_TILE_N`` (module constant, read at
    call time): the round-4 FE bandwidth A/B (bench ``--fe-bandwidth-ab``,
    BENCH_FULL.md) settled on tall rebalanced tiles under a sequential
    grid as the single surviving lowering, so the per-call tile-height
    override was deleted with the losing candidates. Tests vary geometry
    by monkeypatching ``pallas_glm.DEFAULT_TILE_N``.
    """
    n, d = X.shape
    _check_fused_width(d, "fused_data_value_and_grad")
    if interpret is None:
        interpret = not pallas_available()

    d_pad = _round_up(max(d, 1), _LANE)
    n_vectors = 4 if return_margins else 3
    tile_n, n_pad = _tile_geometry(
        n, DEFAULT_TILE_N,
        x_row_bytes(d_pad, X.dtype) + n_vectors * ROW_VEC_BYTES,
    )
    if n_pad != n or d_pad != d:
        X = jnp.pad(X, ((0, n_pad - n), (0, d_pad - d)))
        label = jnp.pad(label, (0, n_pad - n))
        offset = jnp.pad(offset, (0, n_pad - n))
        weight = jnp.pad(weight, (0, n_pad - n))  # 0-weight padding rows
        w = jnp.pad(w, (0, d_pad - d))

    # w takes X's dtype: with bf16 X the margin dot runs bf16×bf16 → f32
    # (preferred_element_type); value/grad accumulation is f32 either way.
    w_row = w.astype(X.dtype)[None, :]
    n_tiles = n_pad // tile_n
    as_rows, row_spec = _row_blocks(n_tiles, tile_n)
    resident = pl.BlockSpec((1, d_pad), lambda i: (0, 0))
    out_specs = [
        pl.BlockSpec((None, 1, 1), lambda i: (i, 0, 0)),  # loss partial
        resident,                                         # grad accumulator
    ]
    out_shape = [
        jax.ShapeDtypeStruct((n_tiles, 1, 1), jnp.float32),
        jax.ShapeDtypeStruct((1, d_pad), jnp.float32),
    ]
    if return_margins:
        out_specs.append(row_spec)
        out_shape.append(jax.ShapeDtypeStruct((n_tiles, 1, tile_n), jnp.float32))

    outs = pl.pallas_call(
        functools.partial(_kernel, loss),
        grid=(n_tiles,),
        in_specs=[
            resident,                                         # w
            pl.BlockSpec((tile_n, d_pad), lambda i: (i, 0)),  # X row tile
            row_spec,                                         # y
            row_spec,                                         # offset
            row_spec,                                         # weight
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=None if interpret else _SEQUENTIAL_GRID,
        interpret=interpret,
    )(w_row, X, as_rows(label), as_rows(offset), as_rows(weight))

    value = jnp.sum(outs[0])
    grad = outs[1][0, :d]
    if return_margins:
        return value, grad, outs[2].reshape(n_pad)[:n]
    return value, grad
