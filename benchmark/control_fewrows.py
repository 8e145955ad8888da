#!/usr/bin/env python3
"""Readings for the limits of ``correct`` in ``fit.glmix2-fewrows``, on the
chip at the cell's own size, several seeds in one process
(``control_ragged.py``'s readings, and the two faults that hold THIS
deployment's guarantee):

    python3 benchmark/control_fewrows.py --workload fit.glmix2-fewrows \
        --seeds 1,2 --what program,control,unchanged,half,altered,lower_bound_16,ratio_capped

A JSON line a seed with the numbers ``compare`` would read. ``program``,
``control`` (the ragged reference at bfloat16 products), ``unchanged``,
``half`` and ``altered`` are ``control_ragged``'s. The guarantee is that
every user with a row gets a model trained on all of its rows over all of
its coefficients, so the two faults are the PROGRAM fitted with an option
of its own that breaks it, as upstream's ``RandomEffectDataConfiguration``
would: ``lower_bound_16`` (``active_lower_bound`` 16: users under 16 rows
are left at zero) and ``ratio_capped`` (``features_to_samples_ratio``
``--ratio``: a user keeps at most ratio x its rows coefficients, the rest
projected out by Pearson score). Both have to come out not correct. The
benchmark's own runs never call this.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import control, control_ragged, device, program, run  # noqa: E402

GUARANTEE_FAULTS = {
    "lower_bound_16": lambda ratio: dict(active_lower_bound=16),
    "ratio_capped": lambda ratio: dict(features_to_samples_ratio=ratio),
}


def build_fit_with(real_build_fit, **re_options):
    """``program.build_fit`` with every random-effect coordinate's data
    configuration given ``re_options`` (``active_lower_bound``,
    ``features_to_samples_ratio``): the estimator a deployment would build
    had it set them. Same signature as ``build_fit``."""

    def build(config, xf, shards, ids, y, entities):
        estimator, batch, opt = real_build_fit(config, xf, shards, ids, y, entities)
        random = {c["id"] for c in program.coordinates(config, "random")}
        estimator.coordinate_configs = [
            dataclasses.replace(c, **re_options) if c.coordinate_id in random else c
            for c in estimator.coordinate_configs]
        return estimator, batch, opt

    return build


def fault_fit(config, xf, shards, ids, y, entities, **re_options):
    """One fit of the program with the guarantee broken by ``re_options``."""
    estimator, batch, opt = build_fit_with(program.build_fit, **re_options)(
        config, xf, shards, ids, y, entities)
    model, _tracker = program.fit_once(estimator, batch, opt)
    return model


def readings(config, traffic, seed, what, ratio):
    """One seed's line: the data and the reference made once, every token of
    ``what`` read against that reference."""
    import jax
    import jax.numpy as jnp

    from benchmark import data_ragged
    from benchmark.reference import glmix_ragged
    from benchmark.traffic import fit_loop

    entities, re = fit_loop.sizes(config, traffic)
    (fixed,) = program.coordinates(config, "fixed")
    rows = int(traffic["rows"])
    made = data_ragged.make_glmix(seed, rows, fixed["dim"], re,
                                  traffic.get("law", {}))
    out = dict(seed=seed)
    models = {}
    if "program" in what:
        model, counts, first, warm = control.program_fit(config, *made, entities)
        gc.collect()
        models["program"] = model
        out["program.run"] = dict(first_fit_s=first, fit_s=warm, counts=counts)
        control.log(f"program: first fit {first:.2f}s, warm fit {warm:.3f}s")
    for name in sorted(what & set(GUARANTEE_FAULTS)):
        gc.collect()    # the last estimator's blocks go first
        models[name] = fault_fit(config, *made, entities,
                                 **GUARANTEE_FAULTS[name](ratio))
        control.log(f"{name}: fitted")
    gc.collect()
    t0 = time.perf_counter()
    ref = glmix_ragged.fit(config, *made, entities)
    jax.block_until_ready(ref)
    out["reference_s"] = time.perf_counter() - t0
    for name, model in models.items():
        out[name] = control.gaps(config, model, ref)
    del models
    if "control" in what:
        out["control"] = control.gaps(config, glmix_ragged.fit(
            config, *made, entities, control=True), ref)
    if "unchanged" in what:
        out["unchanged"] = control.gaps(
            config, {k: jnp.zeros_like(v) for k, v in ref.items()}, ref)
    if "half" in what:
        half = control_ragged.take(slice(0, rows // 2), *made)
        out["half"] = control.gaps(
            config, glmix_ragged.fit(config, *half, entities), ref)
    if "altered" in what:
        moved = {k: v.at[(0,) * v.ndim].add(0.05) for k, v in ref.items()}
        out["altered"] = control.gaps(config, moved, ref)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default="program,control")
    ap.add_argument("--ratio", type=float, default=1.0)
    ap.add_argument("--out", default="chiprun_out/control")
    args = ap.parse_args()
    bench = run.load_json("BENCHMARK.json")
    cell, config, traffic = run.load_cell(bench, args.workload)
    block = device.require_tpu(int(cell["chips"]))
    device.configure_cache()
    what = set(args.what.split(","))
    os.makedirs(os.path.join(ROOT, args.out), exist_ok=True)
    path = os.path.join(ROOT, args.out, args.workload + ".jsonl")
    for seed in (int(s) for s in args.seeds.split(",")):
        rec = readings(config, traffic, seed, what, args.ratio)
        rec.update(workload=args.workload, device=block, ratio=args.ratio)
        line = json.dumps(rec)
        print(line, flush=True)
        with open(path, "a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
