#!/usr/bin/env python3
"""Readings for the limits of ``correct`` in a cell of kind
``fit_loop_ragged``, on the chip at the cell's own size, several seeds in one
process (``control.py`` is bound to ``fit_loop``'s generator and reference):

    python3 benchmark/control_ragged.py --workload fit.glmix2-zipf \
        --seeds 1,2,3 --what program,control,half,altered,capped,unchanged

A JSON line a seed with the numbers ``compare`` would read: ``program`` (one
warm fit of the program; ``witness:<re kernel>`` the same on another of the
program's own Newton-system lowerings, with its warm ``fit_s``), ``control`` (the ragged reference at bfloat16
products in its place) and the faults: ``unchanged`` (a zero model),
``half`` (half of the batch left out), ``altered`` (one coefficient moved
by 0.05), ``capped`` (the largest user's rows beyond ``--cap`` left out: the
guarantee that every row trains). The benchmark's own runs never call this.
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

from benchmark import control, data_ragged, device, program, run  # noqa: E402


def cap_largest(ids, cap: int):
    """Row indices that are left when the entity with the most rows keeps
    its first ``cap`` only (host numpy over the id column)."""
    import numpy as np

    ids = np.asarray(ids)
    top = np.bincount(ids).argmax()
    mine = np.flatnonzero(ids == top)
    keep = np.ones(ids.shape, bool)
    keep[mine[cap:]] = False
    return np.flatnonzero(keep)


def take(rows, xf, shards, ids, y):
    return (xf[rows], {k: v[rows] for k, v in shards.items()},
            {k: v[rows] for k, v in ids.items()}, y[rows])


def readings(config, traffic, seed, what, cap):
    import jax
    import jax.numpy as jnp

    from benchmark.reference import glmix_ragged
    from benchmark.traffic import fit_loop

    entities, re = fit_loop.sizes(config, traffic)
    (fixed,) = program.coordinates(config, "fixed")
    rows = int(traffic["rows"])
    xf, shards, ids, y = data_ragged.make_glmix(
        seed, rows, fixed["dim"], re, traffic.get("law", {}))
    out = dict(seed=seed)
    models = {}
    for token in sorted(w for w in what if w == "program" or w.startswith("witness:")):
        kernel = (token.split(":") + [None])[1]
        try:
            model, counts, first, warm = control.program_fit(
                config, xf, shards, ids, y, entities, kernel)
        except Exception as exc:  # noqa: BLE001 — a lowering the chip refuses reads nothing
            control.log(f"{token}: {exc!r}")
            out[token] = dict(error=repr(exc)[:300])
            continue
        gc.collect()
        models[token] = model
        out[token + ".run"] = dict(first_fit_s=first, fit_s=warm, counts=counts)
        control.log(f"{token}: first fit {first:.2f}s, warm fit {warm:.3f}s")
    t0 = time.perf_counter()
    ref = glmix_ragged.fit(config, xf, shards, ids, y, entities)
    jax.block_until_ready(ref)
    out["reference_s"] = time.perf_counter() - t0
    for token, model in models.items():
        out[token] = control.gaps(config, model, ref)
    if "control" in what:
        out["control"] = control.gaps(config, glmix_ragged.fit(
            config, xf, shards, ids, y, entities, control=True), ref)
    if "unchanged" in what:
        out["unchanged"] = control.gaps(
            config, {k: jnp.zeros_like(v) for k, v in ref.items()}, ref)
    if "half" in what:
        half = take(slice(0, rows // 2), xf, shards, ids, y)
        out["half"] = control.gaps(
            config, glmix_ragged.fit(config, *half, entities), ref)
    if "altered" in what:
        moved = {k: v.at[(0,) * v.ndim].add(0.05) for k, v in ref.items()}
        out["altered"] = control.gaps(config, moved, ref)
    if "capped" in what:
        (cid,) = [c["id"] for c in program.coordinates(config, "random")][:1]
        left = jnp.asarray(cap_largest(ids[cid], cap))
        capped = take(left, xf, shards, ids, y)
        out["capped"] = dict(control.gaps(
            config, glmix_ragged.fit(config, *capped, entities), ref),
            rows_left=int(left.shape[0]), cap=cap)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default="program,control")
    ap.add_argument("--cap", type=int, default=4096)
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
        rec = readings(config, traffic, seed, what, args.cap)
        rec.update(workload=args.workload, device=block)
        line = json.dumps(rec)
        print(line, flush=True)
        with open(path, "a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
