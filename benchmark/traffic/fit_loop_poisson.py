"""The fit window of ``fit_loop.py`` on a count response under an elastic
net: ``data_poisson``'s labels on ``fit_uniform``'s rows, an estimator built
with the penalty's split (``program_enet``), compared with the
proximal-Newton reference (``reference/glmix_poisson_enet.py``).

The window, ``fit_s``, the one fit of set-up and the traced slice are
``fit_loop``'s, so the cell and its logistic control differ by the labels,
the loss, the penalty and the fixed effect's solver, and by nothing in how
they are timed. What is its own:

- ``correct`` also holds ``support_diff`` (the penalty's zeros) and
  ``diverged_users`` (no solve of the window's last fit ended DIVERGED: the
  program keeps such an entity at its start, which the configuration rules
  out);
- the registry's snapshot after the trackers were read, which is when the
  program publishes the fixed effect's solver counters and the count of its
  non-zero coefficients (``readers/registry_value.py``).
"""

from __future__ import annotations

import time

from benchmark import (compare_poisson, data_poisson, device, program,
                       program_enet, tracing)
from benchmark.traffic.fit_loop import sizes


def run(ctx) -> dict:
    import jax

    config, traffic = ctx.config, ctx.traffic
    entities, re = sizes(config, traffic)
    (fixed,) = program.coordinates(config, "fixed")
    rows = int(traffic["rows"])

    # -- set-up: data on the device from the seed, one fit -------------------
    xf, shards, ids, y = data_poisson.make_glmix(
        ctx.seed, rows, fixed["dim"], re, traffic["truth"])
    jax.block_until_ready((xf, shards, ids, y))
    ctx.log(f"data on the device: {rows} rows, mean count {float(y.mean()):.4f}")
    estimator, batch, opt = program_enet.build_fit(config, xf, shards, ids, y,
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
    counts, diverged = None, float("inf")
    if ends:
        counts = program.tracker_counts(config, tracker)
        diverged = program_enet.quarantined(config, tracker)
    registry = program.registry_snapshot()
    ctx.log(f"window: {len(ends)} fits in {window:.3f}s, ends "
            f"{[round(e, 3) for e in ends]}")
    ctx.log(f"solver counts of the last fit: {counts}")

    # -- free the program's state, then the reference -------------------------
    del estimator, batch, tracker
    with tracing.annotate("bench/reference"):
        checks = compare_poisson.fit_models(ctx, config, traffic, models, xf,
                                            shards, ids, y, entities)
    checks.append(("diverged_users", diverged, 0))
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
