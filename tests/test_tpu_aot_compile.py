"""Every ``pallas_call``, the random-effect block solve and its Newton loop's
SPD solve, compiled ahead of time for a TPU v5e — on the CPU.

``jax.experimental.topologies.get_topology_desc`` describes a v5e:2x2 host
with no chip attached, and lowering against a ``ShapeDtypeStruct`` placed
on one of its devices runs the real TPU compiler, Mosaic included, under
``JAX_PLATFORMS=cpu``. That turns "the compiler accepts the kernel at the
full-width shapes" — VMEM budget, HBM capacity, layouts — into a tier-1
check that costs seconds and no chip time. It is also the loop to fix a
kernel in: edit, compile here, and only then spend a chip run.

What this cannot show is that the compiled kernel computes the right thing
on the hardware; ``chip_smoke.py`` (phase kernels) does that on the chip.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from photon_tpu.algorithm.random_effect import _solve_block
from photon_tpu.data.random_effect import EntityBlock
from photon_tpu.ops.losses import LogisticLoss
from photon_tpu.ops.objective import GLMObjective
from photon_tpu.ops.pallas_glm import (
    MAX_FUSED_DIM,
    fused_data_hvp,
    fused_data_value_and_grad,
)
from photon_tpu.optim.factory import OptimizerSpec
from photon_tpu.optim.newton import spd_solve, spd_solve_lowering
from photon_tpu.types import OptimizerType

# bench.py's headline shapes: N = 2^21 rows, d = 256; d_re = 16, as in every
# benchmark cell.
N, D_FIX = 1 << 21, 256
D_RE = 16


@pytest.fixture(scope="module")
def v5e():
    """Sharding on one device of a described (not attached) v5e host.

    Loading libtpu normally takes its multi-process lock
    (/tmp/libtpu_lockfile) for the life of the process, which on a machine
    WITH a chip would shut every other process out of the TPU while this
    pytest process lives (seen on the v5e, PR 21). A compile-only load
    opens no device, so it is told not to take the lock."""
    with pytest.MonkeyPatch.context() as env:
        env.setenv("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
        try:
            import libtpu  # noqa: F401
            from jax.experimental import topologies

            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as exc:  # noqa: BLE001 — any failure: no compiler
            pytest.skip(
                f"no ahead-of-time TPU compiler in this install: {exc!r}"
            )
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    """Lower for the described device, check Mosaic is in the program (not
    the interpreter), and run the TPU compiler on it."""
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
        for shape, dtype in shapes
    ]
    lowered = jax.jit(fn).lower(*args)
    assert "tpu_custom_call" in lowered.as_text()
    return lowered.compile()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("n,d", [(N, D_FIX), (1 << 17, MAX_FUSED_DIM)])
def test_fixed_effect_kernels_compile_for_v5e(v5e, n, d, dtype):
    f32 = jnp.float32
    vec, w = ((n,), f32), ((d,), f32)
    for return_margins in (False, True):
        _compile(
            lambda w_, X, y, off, wt: fused_data_value_and_grad(
                LogisticLoss, w_, X, y, off, wt, interpret=False,
                return_margins=return_margins,
            ),
            v5e, w, ((n, d), dtype), vec, vec, vec,
        )
    _compile(
        lambda v, X, d2: fused_data_hvp(v, X, d2, interpret=False),
        v5e, w, ((n, d), dtype), vec,
    )


def test_one_read_hvp_compiles_without_a_copy_of_x(v5e):
    """Where no tile height divides n, the one-read product masks its last
    tile: the compiled program holds no temporary the size of X."""
    n, d = N * 2 + 37, D_FIX
    f32 = jnp.float32
    compiled = _compile(
        lambda v, X, d2: fused_data_hvp(v, X, d2, interpret=False),
        v5e, ((d,), f32), ((n, d), f32), ((n,), f32),
    )
    assert compiled.memory_analysis().temp_size_in_bytes < n * d * 4 // 100


@pytest.mark.parametrize(
    "lanes,n_max",
    [
        # The blocks the fit cells dispatch (PERF.md §4): fit.glmix2's two,
        # fit.glmix3's items, the ends of fit.glmix2-zipf's plan, and the
        # widest block of fit.glmix2-fewrows (users of up to 4 rows).
        (2304, 512), (2048, 768), (144, 8192), (128, 12288),
        (3840, 96), (1, 524288), (163840, 4),
    ],
)
def test_random_effect_block_solve_compiles_for_v5e(v5e, lanes, n_max):
    """The jitted ``_solve_block`` on the Newton route, the program a
    ``RandomEffectCoordinate`` dispatches once a block: it compiles for the
    v5e at the cells' block shapes, holds no Mosaic kernel, and the library
    ``Cholesky`` call exactly where ``spd_solve_lowering`` says so."""
    def on_chip(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    objective = GLMObjective(loss=LogisticLoss, l2_weight=1.0)
    spec = OptimizerSpec(optimizer=OptimizerType.NEWTON, max_iter=20, tol=1e-7)
    block = EntityBlock(
        entity_idx=on_chip((lanes,), jnp.int32),
        features=on_chip((lanes, n_max, D_RE)),
        label=on_chip((lanes, n_max)),
        weight=on_chip((lanes, n_max)),
        sample_index=on_chip((lanes, n_max), jnp.int32),
        train_mask=on_chip((lanes,), jnp.bool_),
    )
    text = jax.jit(
        lambda b, offsets, w0: _solve_block(
            b, offsets, w0, objective, spec, spec.config()
        )
    ).lower(block, on_chip((lanes, n_max)), on_chip((lanes, D_RE))).compile().as_text()
    assert "tpu_custom_call" not in text
    library = spd_solve_lowering(D_RE, lanes) == "library"
    assert ('custom_call_target="Cholesky"' in text) == library


@pytest.mark.parametrize("lanes", [3072, 128, 1])
def test_spd_solve_compiles_for_v5e_without_the_cholesky_call(v5e, lanes):
    """The RE Newton system at d_re = 16 under the entity ``vmap``: from 128
    lanes the compiled program is the unrolled column steps (no Mosaic, no
    library ``Cholesky`` custom call), the entities on the minor axis; a
    block of one lane keeps the library call."""
    f32 = jnp.float32
    args = [
        jax.ShapeDtypeStruct(shape, f32, sharding=v5e)
        for shape in ((lanes, D_RE, D_RE), (lanes, D_RE))
    ]
    text = jax.jit(jax.vmap(spd_solve)).lower(*args).compile().as_text()
    assert "tpu_custom_call" not in text  # no Mosaic kernel
    if spd_solve_lowering(D_RE, lanes) == "library":
        assert 'custom_call_target="Cholesky"' in text
        return
    assert 'custom_call_target="Cholesky"' not in text
    # The rank-one updates run at (d - j, d - j + 1, lanes), the entity
    # axis minor in the device layout.
    assert f"f32[{D_RE - 1},{D_RE},{lanes}]{{2,1,0" in text
