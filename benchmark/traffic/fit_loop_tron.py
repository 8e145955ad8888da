"""The fit window of ``fit_loop.py`` on a squared loss with a trust-region
Newton fixed effect: ``data_linear``'s AR(1) fixed shard and continuous
labels beside ``fit_uniform``'s per-user shard and ids, an estimator built
with the fixed effect's stopping rule (``program_tron``), compared with the
normal-equations reference (``reference/glmix_linear.py``).

The window, ``fit_s``, the one fit of set-up and the traced slice are
``fit_loop``'s, so the cell and its logistic control differ by the fixed
shard, the labels, the loss and the fixed effect's solver, and by nothing in
how they are timed. What is its own: the solvers' counts carry TRON's CG
and rejected steps (``program_tron.tracker_counts``), and the facts carry
the registry's snapshot taken after the trackers were read, which is when
the program publishes its solver counters.
"""

from __future__ import annotations

import time

from benchmark import compare_linear, data_linear, device, program, program_tron, tracing
from benchmark.traffic.fit_loop import sizes


def run(ctx) -> dict:
    import jax

    config, traffic = ctx.config, ctx.traffic
    entities, re = sizes(config, traffic)
    (fixed,) = program.coordinates(config, "fixed")
    rows = int(traffic["rows"])

    # -- set-up: data on the device from the seed, one fit -------------------
    xf, shards, ids, y = data_linear.make_glmix(
        ctx.seed, rows, fixed["dim"], re, traffic["truth"])
    jax.block_until_ready((xf, shards, ids, y))
    ctx.log(f"data on the device: {rows} rows, label sd {float(y.std()):.4f}")
    estimator, batch, opt = program_tron.build_fit(config, xf, shards, ids, y,
                                                   entities)
    t_fit = time.perf_counter()
    program.fit_once(estimator, batch, opt)   # groups entities, compiles or loads
    first_fit_s = time.perf_counter() - t_fit
    before = ctx.clock.snapshot()
    ctx.setup_done(first_fit_s=first_fit_s)

    # -- the window -----------------------------------------------------------
    models, ends, tracker = [], [], None
    tracer = tracing.Slice(ctx, first=1, count=int(traffic.get("trace_fits", 3)))
    failed = 0
    t0 = time.perf_counter()
    while True:
        tracer.before(len(ends))
        try:
            with tracing.annotate(tracer.name(len(ends), "fit")):
                model, tracker = program.fit_once(estimator, batch, opt)
        except Exception as exc:  # noqa: BLE001 — a failed fit is counted
            ctx.log(f"fit failed: {exc!r}")
            failed += 1
            if failed >= 3:
                break
            continue
        now = time.perf_counter()
        ends.append(now - t0)
        models.append(model)
        tracer.after(len(ends))
        if now - t0 >= ctx.seconds:
            break
    tracer.stop()
    window = ends[-1] if ends else float("nan")
    after = ctx.clock.snapshot()
    peak = device.peak_bytes()
    counts = program_tron.tracker_counts(config, tracker) if ends else None
    registry = program.registry_snapshot()
    ctx.log(f"window: {len(ends)} fits in {window:.3f}s, ends "
            f"{[round(e, 3) for e in ends]}")
    ctx.log(f"solver counts of the last fit: {counts}")

    # -- free the program's state, then the reference -------------------------
    del estimator, batch, tracker
    with tracing.annotate("bench/reference"):
        checks = compare_linear.fit_models(ctx, config, traffic, models, xf,
                                           shards, ids, y, entities)
    checks.append(("compiles_in_window",
                   after["backend_compiles"] - before["backend_compiles"], 0))
    fits = len(ends)
    traced = tracer.fits_wall()
    return dict(
        attempted=fits + failed, failed=failed, memory_peak_bytes=peak,
        checks=checks, counts=counts,
        end_to_end={"fit_s": window / fits if fits else None},
        facts=dict(
            rows=rows, counts=counts,
            dims={c["id"]: c["dim"] for c in config["coordinates"]},
            traced_fits=traced["fits"],
            traced_fit_s=(traced["wall_s"] / traced["fits"]
                          if traced["fits"] else None),
            trace_path=tracer.path, registry_after=registry),
    )
