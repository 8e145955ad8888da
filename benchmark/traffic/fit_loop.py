"""The fit window: ``GameEstimator.fit`` from zero coefficients, again and
again on one device-resident training batch, for ``--seconds``.

``fit_s`` is the whole window (first fit's start to the last completed fit's
fence) over the fits completed in it, so a stall anywhere moves it. It is the
WARM solve only: the estimator keeps its grouping of the batch between fits,
so what a trainer pays once per model (the copy of the shards to the host,
the grouping in Python, every program fetched) lands in the one fit of
set-up. That fit's seconds go out as ``first_fit_s`` on the set-up line.
"""

from __future__ import annotations

import time

from benchmark import compare, data, device, program, tracing


def sizes(config: dict, traffic: dict):
    """``(entities, re)`` by random-effect coordinate id: the population of
    each, and ``(width, population)`` as ``data.make_glmix`` takes it."""
    entities = {c["id"]: int(traffic["entities"][c["id"]])
                for c in program.coordinates(config, "random")}
    re = {c["id"]: (c["dim"], entities[c["id"]])
          for c in program.coordinates(config, "random")}
    return entities, re


def run(ctx) -> dict:
    import jax

    config, traffic = ctx.config, ctx.traffic
    entities, re = sizes(config, traffic)
    (fixed,) = program.coordinates(config, "fixed")
    rows = int(traffic["rows"])

    # -- set-up: data on the device from the seed, one fit -------------------
    xf, shards, ids, y = data.make_glmix(ctx.seed, rows, fixed["dim"], re)
    jax.block_until_ready((xf, shards, ids, y))
    ctx.log(f"data on the device: {rows} rows")
    estimator, batch, opt = program.build_fit(config, xf, shards, ids, y, entities)
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
    counts = program.tracker_counts(config, tracker) if ends else None
    ctx.log(f"window: {len(ends)} fits in {window:.3f}s, ends "
            f"{[round(e, 3) for e in ends]}")
    ctx.log(f"solver counts of the last fit: {counts}")

    # -- free the program's state, then the reference -------------------------
    del estimator, batch, tracker
    with tracing.annotate("bench/reference"):
        checks = compare.fit_models(ctx, config, traffic, models, xf, shards,
                                    ids, y, entities)
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
            trace_path=tracer.path),
    )
