#!/usr/bin/env python3
"""Readings for the limits of ``correct`` in a cell of kind
``fit_loop_tron``, on the chip at the cell's own size, several seeds in one
process:

    python3 benchmark/control_linear.py --workload fit.glmix2-linear-tron \
        --seeds 1,2 --what program,tight,control,unchanged,half,altered,tron_one_iter

A JSON line a seed with the numbers ``compare_linear`` would read, each model
against the normal-equations reference: ``program`` (one warm fit of the
program, with its solver counts and warm ``fit_s``), ``tight`` (the same at
the fixed effect's tol 1e-7, every other cell's rule: the reading the
configuration's stopping rule is decided by), ``control`` (the reference at
bfloat16 products in its place), and the faults: ``unchanged`` (a zero
model), ``half`` (half of the batch left out), ``altered`` (one coefficient
moved by 0.05) and ``tron_one_iter`` (the PROGRAM with the fixed effect's
max_iter 1). The benchmark's own runs never call this.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare, data_linear, device, program, program_tron, run  # noqa: E402
from benchmark.control import log  # noqa: E402

TIGHT_TOL = 1e-7


def gaps(config, model, ref):
    out = compare.model_gaps(config, model, ref)
    out.update({cid: compare.rel_gap(model[cid], want) for cid, want in ref.items()})
    return out


def with_fixed(config, **changes):
    """The configuration with the fixed effect's keys changed."""
    return dict(config, coordinates=[dict(c, **changes) if c["type"] == "fixed"
                                     else c for c in config["coordinates"]])


def program_fit(config, xf, shards, ids, y, entities):
    """One fit of the program after a first that pays the grouping and the
    compiles: ``(model, counts, first_fit_s, fit_s)``."""
    estimator, batch, opt = program_tron.build_fit(config, xf, shards, ids, y,
                                                   entities)
    t0 = time.perf_counter()
    program.fit_once(estimator, batch, opt)
    t1 = time.perf_counter()
    model, tracker = program.fit_once(estimator, batch, opt)
    t2 = time.perf_counter()
    counts = program_tron.tracker_counts(config, tracker)
    del estimator, batch, tracker
    gc.collect()    # the estimator's copy of the batch goes before the next comes
    return model, counts, t1 - t0, t2 - t1


def readings(config, traffic, seed, what):
    import jax

    from benchmark.reference import glmix_linear as reference
    from benchmark.traffic import fit_loop

    entities, re = fit_loop.sizes(config, traffic)
    (fixed,) = program.coordinates(config, "fixed")
    rows = int(traffic["rows"])
    xf, shards, ids, y = data_linear.make_glmix(seed, rows, fixed["dim"], re,
                                                traffic["truth"])
    out = dict(seed=seed, label_sd=float(y.std()))
    runs = {"program": config, "tight": with_fixed(config, tol=TIGHT_TOL),
            "tron_one_iter": with_fixed(config, max_iter=1)}
    models = {}
    for name in [n for n in runs if n in what]:
        model, counts, first, warm = program_fit(runs[name], xf, shards, ids, y,
                                                 entities)
        models[name] = model
        out[name + ".run"] = dict(first_fit_s=first, fit_s=warm, counts=counts)
        log(f"{name}: first fit {first:.2f}s, warm fit {warm:.3f}s, {counts}")
    t0 = time.perf_counter()
    ref = reference.fit(config, xf, shards, ids, y, entities, log=log)
    jax.block_until_ready(ref)
    out["reference"] = dict(seconds=time.perf_counter() - t0)
    for name, model in models.items():
        out[name] = gaps(config, model, ref)
    if "control" in what:
        out["control"] = gaps(config, reference.fit(
            config, xf, shards, ids, y, entities, control=True), ref)
    if "unchanged" in what:
        out["unchanged"] = gaps(
            config, {k: jax.numpy.zeros_like(v) for k, v in ref.items()}, ref)
    if "half" in what:
        n = rows // 2
        half = reference.fit(config, xf[:n], {k: v[:n] for k, v in shards.items()},
                             {k: v[:n] for k, v in ids.items()}, y[:n], entities)
        out["half"] = gaps(config, half, ref)
    if "altered" in what:
        # The fixed effect's first FEATURE and the first user's intercept.
        moved = {k: v.at[(1,) if v.ndim == 1 else (0, 0)].add(0.05)
                 for k, v in ref.items()}
        out["altered"] = gaps(config, moved, ref)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default="program,control")
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
        rec = readings(config, traffic, seed, what)
        rec.update(workload=args.workload, device=block)
        line = json.dumps(rec)
        print(line, flush=True)
        with open(path, "a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
