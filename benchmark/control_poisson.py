#!/usr/bin/env python3
"""Readings for the limits of ``correct`` in a cell of kind
``fit_loop_poisson``, on the chip at the cell's own size, several seeds in
one process (``control.py`` is bound to ``fit_loop``'s generator and
reference):

    python3 benchmark/control_poisson.py --workload fit.glmix2-poisson \
        --seeds 1,2,3 --what program,control,unchanged,half,altered,l1_dropped,clipped

A JSON line a seed with the numbers ``compare_poisson`` would read, each
model against the proximal-Newton reference: ``program`` (one warm fit of the
program, with its solver counts and warm ``fit_s``), ``control`` (the
reference at bfloat16 products in its place) and the faults: ``unchanged`` (a
zero model), ``half`` (half of the batch left out), ``altered`` (one
coefficient moved by 0.05), ``l1_dropped`` (the same reg_weight as pure L2:
the penalty's zeros gone), ``clipped`` (labels cut to {0, 1}: a count read
as a click). ``reference`` holds the share of the reference's feature
coefficients that are exactly zero, which the configuration's reg_weight is
chosen by. The benchmark's own runs never call this.
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

from benchmark import (compare, compare_poisson, data_poisson, device,  # noqa: E402
                       program, program_enet, run)
from benchmark.control import log  # noqa: E402


def gaps(config, model, ref):
    out = compare_poisson.model_gaps(config, model, ref)
    out.update({cid: compare.rel_gap(model[cid], want) for cid, want in ref.items()})
    return out


def program_fit(config, xf, shards, ids, y, entities):
    """One sound fit of the program after a first that pays the grouping and
    the compiles: ``(model, counts, diverged, first_fit_s, fit_s)``."""
    estimator, batch, opt = program_enet.build_fit(config, xf, shards, ids, y,
                                                   entities)
    t0 = time.perf_counter()
    program.fit_once(estimator, batch, opt)
    t1 = time.perf_counter()
    model, tracker = program.fit_once(estimator, batch, opt)
    t2 = time.perf_counter()
    return (model, program.tracker_counts(config, tracker),
            program_enet.quarantined(config, tracker), t1 - t0, t2 - t1)


def pure_l2(config):
    """The configuration with every coordinate's L1 part dropped at the same
    reg_weight."""
    return dict(config, coordinates=[dict(c, alpha=0.0)
                                     for c in config["coordinates"]])


def readings(config, traffic, seed, what):
    import jax
    import jax.numpy as jnp

    from benchmark.reference import glmix_poisson_enet as reference
    from benchmark.traffic import fit_loop

    entities, re = fit_loop.sizes(config, traffic)
    (fixed,) = program.coordinates(config, "fixed")
    rows = int(traffic["rows"])
    xf, shards, ids, y = data_poisson.make_glmix(seed, rows, fixed["dim"], re,
                                                 traffic["truth"])
    out = dict(seed=seed, mean_count=float(y.mean()), max_count=float(y.max()))
    model = None
    if "program" in what:
        model, counts, diverged, first, warm = program_fit(
            config, xf, shards, ids, y, entities)
        gc.collect()    # the estimator's blocks go before the reference's come
        out["program.run"] = dict(first_fit_s=first, fit_s=warm, counts=counts,
                                  diverged_users=diverged)
        log(f"program: first fit {first:.2f}s, warm fit {warm:.3f}s, {counts}")
    t0 = time.perf_counter()
    ref = reference.fit(config, xf, shards, ids, y, entities, log=log)
    jax.block_until_ready(ref)
    out["reference"] = dict(seconds=time.perf_counter() - t0,
                            zero_share=compare_poisson.zero_share(config, ref),
                            largest=float(jnp.max(jnp.abs(ref[fixed["id"]]))))
    if model is not None:
        out["program"] = gaps(config, model, ref)
    if "control" in what:
        out["control"] = gaps(config, reference.fit(
            config, xf, shards, ids, y, entities, control=True), ref)
    if "unchanged" in what:
        out["unchanged"] = gaps(
            config, {k: jnp.zeros_like(v) for k, v in ref.items()}, ref)
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
    if "l1_dropped" in what:
        out["l1_dropped"] = gaps(config, reference.fit(
            pure_l2(config), xf, shards, ids, y, entities), ref)
    if "clipped" in what:
        out["clipped"] = gaps(config, reference.fit(
            config, xf, shards, ids, jnp.minimum(y, 1.0), entities), ref)
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
