"""Batched small-GLM Newton-system Pallas kernel for random effects.

Role parity: the reference solves thousands of tiny per-entity GLMs inside
``mapValues`` (photon-api algorithm/RandomEffectCoordinate.scala:228-283) —
one Breeze optimizer per entity on whatever executor holds the partition.
The TPU rebuild already collapses a bucket of entities into ONE vmapped
damped-Newton program (optim/newton.py); this module collapses that
program's X-touching work into a single Pallas kernel with **one grid
instance per bucketed block row**: each instance streams its entity's
(n_max, d) feature slab through VMEM once and assembles both Newton-system
reductions in that single read —

    per entity:  H = Xᵀ·diag(d2)·X     (MXU, d×d resident in VMEM)
                 g = Xᵀ·dz             (MXU, d resident in VMEM)

where the XLA lowering reads X twice (einsum Hessian + transpose matvec).
The Cholesky factorization, the Levenberg damping loop, and the trial-point
margin sweep stay in XLA — ``lax.linalg`` does not lower inside Mosaic, and
keeping the loop structure identical to the XLA path is what makes parity
bit-exact by construction (the kernel only replaces two reductions whose
per-entity values are reduction-order-identical to the vmapped einsum /
matmul; verified on CPU, pinned by tests/test_re_kernel.py).

The kernel is written UNBATCHED (one entity) and batched by ``jax.vmap``
inside ``_solve_block``'s ``vmap(solve_one)`` — pallas_call's batching rule
prepends the entity grid dimension, which is exactly the "one grid instance
per block row" shape, and it means every surrounding op (while_loop carry,
convergence select, quarantine) is shared verbatim with the XLA path.

bfloat16 X ("pallas_bf16x"): the kernel reads a bf16 copy of the slab
(halving the bandwidth-bound HBM read) and upcasts in VMEM; d2/dz and ALL
accumulation stay float32. Parity vs the f32 XLA path is then a pinned
tolerance, not bit-exact — see RE_KERNELS below and the BENCH_FULL.md
verdict table.

The kernel is OPT-IN (``re_kernel="pallas"`` / ``"pallas_bf16x"``): the XLA
lowering is the default on every backend. On the v5e a warm fit on it took
0.81 s against the kernel's 1.96 s (2^22 rows, 8192 users; chip runs of
PR 28, PERF.md §6) and came out nearer the float32 reference, because
Mosaic's float32 dot at default precision is one bfloat16 pass.

Lowerings: interpret mode (CPU tests) runs the exact unpadded whole-slab
kernel; compiling for a TPU takes the lane/sublane-padded row-tiled one
(``padded=None`` auto), whose ``(tile_n, 1)`` d2/dz column blocks are
counted at their real 128-lane VMEM width by ``_tile_geometry``.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

from photon_tpu.ops.pallas_glm import (
    _LANE,
    _SEQUENTIAL_GRID,
    COL_VEC_BYTES,
    _round_up,
    _tile_geometry,
    pallas_available,
    x_row_bytes,
)

Array = jax.Array

# Solver-kernel routing values for RandomEffectCoordinate.re_kernel /
# solve_cache.block_solver. "auto" resolves to "xla"; the other three are
# concrete lowerings:
#   xla          — vmapped einsum/matmul Newton system (2 X reads/iter)
#   pallas       — fused one-read Pallas Newton system, f32 X (bit-exact)
#   pallas_bf16x — same kernel over a bf16 X copy, f32 accumulate
#                  (pinned-tolerance parity; halves the slab's HBM read)
RE_KERNELS = ("auto", "xla", "pallas", "pallas_bf16x")


def resolve_re_kernel(re_kernel: str) -> str:
    """Concrete kernel for a requested routing value. ``auto`` is the XLA
    lowering on every backend: on a TPU it is the fastest and the most
    exact of the three (module docstring), and off a TPU interpret-mode
    Pallas is orders of magnitude slower. The kernel is opt-in by name."""
    if re_kernel not in RE_KERNELS:
        raise ValueError(
            f"re_kernel must be one of {RE_KERNELS}, got {re_kernel!r}"
        )
    return "xla" if re_kernel == "auto" else re_kernel


def _system_kernel(x_ref, d2_ref, dz_ref, h_ref, g_ref):
    """Whole-slab instance: both reductions from one read of x_ref.

    The einsum / matmul formulations are deliberately IDENTICAL to the XLA
    path in optim/newton.py — under vmap their per-entity values are
    bit-equal to the batched lowering (reduction-order parity verified on
    CPU), which is what lets the fused path claim bit-exact results."""
    x = x_ref[...]
    if x.dtype != jnp.float32:
        x = x.astype(jnp.float32)  # bf16 slab upcasts in VMEM; accum stays f32
    h_ref[...] = jnp.einsum("nd,n,ne->de", x, d2_ref[...], x)
    g_ref[...] = x.T @ dz_ref[...]


def _system_kernel_tiled(x_ref, d2_ref, dz_ref, h_ref, g_ref):
    """Row-tiled instance for slabs over the VMEM budget: sequential-grid
    accumulation (the pallas_glm reduction pattern), rank-2 operands for
    Mosaic layouts, preferred_element_type pins f32 accumulation."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        h_ref[:] = jnp.zeros_like(h_ref)
        g_ref[:] = jnp.zeros_like(g_ref)

    x = x_ref[:]
    if x.dtype != jnp.float32:
        x = x.astype(jnp.float32)
    xd = x * d2_ref[:]  # (tile_n, d_pad) ∘ (tile_n, 1)
    h_ref[:] += jax.lax.dot_general(
        xd, x,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    g_ref[:] += jax.lax.dot_general(
        x, dz_ref[:],
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def fused_newton_system(
    X: Array,
    d2: Array,
    dz: Array,
    interpret: Optional[bool] = None,
    padded: Optional[bool] = None,
) -> Tuple[Array, Array]:
    """``(Xᵀ·diag(d2)·X, Xᵀ·dz)`` in ONE pass over ``X`` ((n, d), one
    entity; vmap for the batched per-block-row kernel).

    ``padded=None`` auto-selects: the exact unpadded whole-slab kernel in
    interpret mode (CPU — bit-exact vs the XLA formulations), the
    lane/sublane-padded tiled lowering when compiling for TPU (zero padding
    rows/columns contribute exactly zero to both reductions, but tiling
    re-associates the n-reduction, so on-chip parity is pinned-tolerance
    like bf16 — see module docstring)."""
    n, d = X.shape
    if interpret is None:
        interpret = not pallas_available()
    if padded is None:
        padded = not interpret
    if not padded:
        return pl.pallas_call(
            _system_kernel,
            out_shape=[
                jax.ShapeDtypeStruct((d, d), jnp.float32),
                jax.ShapeDtypeStruct((d,), jnp.float32),
            ],
            interpret=interpret,
            name="re_newton_system",
        )(X, d2, dz)

    d_pad = _round_up(max(d, 1), _LANE)
    sublane = 16 if X.dtype == jnp.bfloat16 else 8
    # Per sample row: the X tile, its float32 working copies in the kernel
    # (the d2-scaled tile, and the upcast of a bf16 tile), and the d2 / dz
    # columns. Resident: the double-buffered (d_pad, d_pad) Hessian block
    # and the (d_pad, 1) gradient column.
    tile_n, n_pad = _tile_geometry(
        n, n,
        row_bytes=x_row_bytes(d_pad, X.dtype) + 2 * d_pad * 4
        + 2 * COL_VEC_BYTES,
        fixed_bytes=2 * d_pad * d_pad * 4 + d_pad * COL_VEC_BYTES,
        align=sublane,
    )
    if n_pad != n or d_pad != d:
        X = jnp.pad(X, ((0, n_pad - n), (0, d_pad - d)))
        d2 = jnp.pad(d2, (0, n_pad - n))
        dz = jnp.pad(dz, (0, n_pad - n))
    col = lambda v: v.astype(jnp.float32)[:, None]  # noqa: E731
    n_tiles = n_pad // tile_n
    h, g = pl.pallas_call(
        _system_kernel_tiled,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((tile_n, d_pad), lambda i: (i, 0)),  # X row tile
            pl.BlockSpec((tile_n, 1), lambda i: (i, 0)),      # d2
            pl.BlockSpec((tile_n, 1), lambda i: (i, 0)),      # dz
        ],
        out_specs=[
            pl.BlockSpec((d_pad, d_pad), lambda i: (0, 0)),
            pl.BlockSpec((d_pad, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d_pad, d_pad), jnp.float32),
            jax.ShapeDtypeStruct((d_pad, 1), jnp.float32),
        ],
        compiler_params=None if interpret else _SEQUENTIAL_GRID,
        interpret=interpret,
        name="re_newton_system_tiled",
    )(X, col(d2), col(dz))
    return h[:d, :d], g[:d, 0]
