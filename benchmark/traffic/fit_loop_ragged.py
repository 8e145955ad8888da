"""The fit window of ``fit_loop.py`` on entities whose row counts follow a
law (``data_ragged``), compared with the layout-free reference
(``reference/glmix_ragged.py``).

The window, ``fit_s``, the one fit of set-up and the traced slice are
``fit_loop``'s, so the cell and its uniform control differ by the id column
and by nothing in how they are timed. What is its own:

- the reference groups nothing into a padded grid, so it fits beside the
  data whatever the largest user holds, and every row of every user is in
  it (the configuration's guarantee);
- ``facts["counts"]`` carries the random effects' Newton iterations
  weighted by rows (Σ_e rows_e · its_e ÷ rows, as the program's tracker
  reports them), in the place ``work.fit_total`` reads: rows × the mean
  entity's iterations counts a 30-row user like a 400,000-row one. The
  result's ``counts`` keeps the mean entity's beside it;
- the registry's snapshot after the window, for the metrics of the block
  plan (``readers/registry_value.py``).
"""

from __future__ import annotations

import copy
import time
from typing import Dict, List

from benchmark import compare, data_ragged, device, program, tracing
from benchmark.traffic.fit_loop import sizes


def compare_models(ctx, config: dict, traffic: dict, models: List[Dict], xf,
                   shards, ids, y, entities) -> List[compare.Check]:
    """Every model the window's fits returned against the ragged
    reference's (``compare.model_gaps``), each number the worst over them."""
    from benchmark.reference import glmix_ragged

    t0 = time.perf_counter()
    ref = glmix_ragged.fit(config, xf, shards, ids, y, entities, log=ctx.log)
    gaps: Dict[str, float] = {}
    for model in models:
        for key, gap in compare.model_gaps(config, model, ref).items():
            gaps[key] = max(gaps.get(key, 0.0), gap)
    if not models:
        gaps = {"fixed_gap": float("inf")}
    ctx.log(f"reference and comparison of {len(models)} models took "
            f"{time.perf_counter() - t0:.1f}s")
    limits = traffic["limits"]
    return [(name, value, limits[name]) for name, value in gaps.items()]


def weigh_by_rows(config: dict, counts: dict, tracker) -> dict:
    """``counts`` with each random effect's ``newton_iterations`` replaced by
    entities × (Σ_e rows_e · its_e ÷ rows, summed over the passes), so that
    ``work``'s rows × iterations-per-entity is Σ_e rows_e · its_e. The mean
    entity's stays under ``newton_iterations_by_entity``. A program whose
    tracker does not weigh (the parent's) leaves the counts as they are."""
    out = copy.deepcopy(counts)
    for c in program.coordinates(config, "random"):
        weighed = [d.diagnostics_dict().get("row_weighted_iterations")
                   for d in tracker[c["id"]]]
        if any(w is None for w in weighed):
            continue
        mine = out[c["id"]]
        mine["newton_iterations_by_entity"] = mine["newton_iterations"]
        mine["newton_iterations"] = sum(weighed) * mine["entities"]
    return out


def run(ctx) -> dict:
    import jax

    config, traffic = ctx.config, ctx.traffic
    entities, re = sizes(config, traffic)
    (fixed,) = program.coordinates(config, "fixed")
    rows = int(traffic["rows"])

    # -- set-up: data on the device from the seed, one fit -------------------
    xf, shards, ids, y = data_ragged.make_glmix(
        ctx.seed, rows, fixed["dim"], re, traffic.get("law", {}))
    jax.block_until_ready((xf, shards, ids, y))
    ctx.log(f"data on the device: {rows} rows")
    estimator, batch, opt = program.build_fit(config, xf, shards, ids, y, entities)
    t_fit = time.perf_counter()
    program.fit_once(estimator, batch, opt)   # plans blocks, compiles or loads
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
    counts = weighed = None
    if ends:
        counts = program.tracker_counts(config, tracker)
        weighed = weigh_by_rows(config, counts, tracker)
    registry = program.registry_snapshot()
    ctx.log(f"window: {len(ends)} fits in {window:.3f}s, ends "
            f"{[round(e, 3) for e in ends]}")
    ctx.log(f"solver counts of the last fit: {weighed}")

    # -- free the program's state, then the reference -------------------------
    del estimator, batch, tracker
    with tracing.annotate("bench/reference"):
        checks = compare_models(ctx, config, traffic, models, xf, shards, ids,
                                y, entities)
    checks.append(("compiles_in_window",
                   after["backend_compiles"] - before["backend_compiles"], 0))
    fits = len(ends)
    traced = tracer.fits_wall()
    return dict(
        attempted=fits + failed, failed=failed, memory_peak_bytes=peak,
        checks=checks, counts=weighed,
        end_to_end={"fit_s": window / fits if fits else None},
        facts=dict(
            rows=rows, counts=weighed,
            dims={c["id"]: c["dim"] for c in config["coordinates"]},
            traced_fits=traced["fits"],
            traced_fit_s=(traced["wall_s"] / traced["fits"]
                          if traced["fits"] else None),
            trace_path=tracer.path, registry_after=registry),
    )
