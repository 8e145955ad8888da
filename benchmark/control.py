#!/usr/bin/env python3
"""Readings for the limits of ``correct``, taken on the chip at a cell's own
size, several seeds in one process (set-up is most of a run):

    python3 benchmark/control.py --workload fit.glmix2 --seeds 1,2,3 \
        --what program,control,half,altered

For each seed one JSON line with the numbers ``compare`` would read:
``program`` (the timed entry, one fit or a short window), ``control`` (the
reference at bfloat16 products put in the program's place), and for fit
cells the faults ``half`` (half of the batch left out) and ``altered`` (one
coefficient moved). ``witness:xla:highest`` is the second witness for a gap
that is laid to the program: the program's own XLA lowering of the Newton
system, traced at ``highest`` matmul precision, against the same reference.
``--fresh-rows`` draws the rows from each seed (the runs keep one data set).
The benchmark's own runs never call this.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare, data, device, program, run  # noqa: E402


def log(msg):
    print(f"[control {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr, flush=True)


def gaps(config, model, ref):
    out = compare.model_gaps(config, model, ref)
    out.update({cid: compare.rel_gap(model[cid], want) for cid, want in ref.items()})
    return out


def program_fit(config, xf, shards, ids, y, entities, kernel=None, precision=None):
    """One sound fit of the program on this data, after a first fit that
    pays the grouping and the compiles: ``(model, counts, first_fit_s,
    fit_s)``. ``kernel`` / ``precision`` are the witness: another of the
    program's own Newton-system lowerings, and JAX's default matmul
    precision while the program is traced."""
    import jax

    forced = program.re_kernel_forced(kernel) if kernel else contextlib.nullcontext()
    matmul = (jax.default_matmul_precision(precision) if precision
              else contextlib.nullcontext())
    with forced, matmul:
        estimator, batch, opt = program.build_fit(config, xf, shards, ids, y, entities)
        t0 = time.perf_counter()
        program.fit_once(estimator, batch, opt)
        t1 = time.perf_counter()
        model, tracker = program.fit_once(estimator, batch, opt)
        t2 = time.perf_counter()
    return model, program.tracker_counts(config, tracker), t1 - t0, t2 - t1


def fit_readings(config, traffic, seed, what, fresh_rows=False):
    """``what`` holds ``program``, ``control``, ``half``, ``altered`` and any
    number of ``witness:<re kernel>[:<matmul precision>]``."""
    import jax

    from benchmark.reference import glmix

    from benchmark.traffic import fit_loop

    entities, re = fit_loop.sizes(config, traffic)
    (fixed,) = program.coordinates(config, "fixed")
    rows = int(traffic["rows"])
    xf, shards, ids, y = data.make_glmix(seed, rows, fixed["dim"], re,
                                         fresh_rows=fresh_rows)
    out = dict(seed=seed, fresh_rows=fresh_rows)
    models = {}
    for token in sorted(w for w in what if w == "program" or w.startswith("witness:")):
        kernel, precision = (token.split(":") + [None, None])[1:3]
        try:
            model, counts, first, warm = program_fit(
                config, xf, shards, ids, y, entities, kernel, precision)
        except Exception as exc:  # noqa: BLE001 — a witness the chip refuses reads nothing
            log(f"{token}: {exc!r}")
            out[token] = dict(error=repr(exc)[:300])
            continue
        gc.collect()    # the estimator's blocks go before the next one's come
        models[token] = model
        out[token + ".run"] = dict(first_fit_s=first, fit_s=warm, counts=counts)
        log(f"{token}: first fit {first:.2f}s, warm fit {warm:.3f}s")
    t0 = time.perf_counter()
    ref = glmix.fit(config, xf, shards, ids, y, entities)
    jax.block_until_ready(ref)
    out["reference_s"] = time.perf_counter() - t0
    for token, model in models.items():
        out[token] = gaps(config, model, ref)
    if "control" in what:
        out["control"] = gaps(config, glmix.fit(config, xf, shards, ids, y,
                                                entities, control=True), ref)
    if "half" in what:
        n = rows // 2
        half = glmix.fit(config, xf[:n], {k: v[:n] for k, v in shards.items()},
                         {k: v[:n] for k, v in ids.items()}, y[:n], entities)
        out["half"] = gaps(config, half, ref)
    if "altered" in what:
        moved = {k: v.at[(0,) * v.ndim].add(0.05) for k, v in ref.items()}
        out["altered"] = gaps(config, moved, ref)
    return out


def serve_readings(config, traffic, seed, seconds):
    """The control needs no engine: the same requests, scored by the
    reference at float32 and with operands cut to bfloat16."""
    import numpy as np

    from benchmark.traffic import open_loop_score as ols

    n = int(round(float(traffic["rate_per_s"]) * seconds))
    feats, ids = ols.build_inputs(seed, config, traffic, n)
    want = ols.reference_scores(seed, config, traffic, feats, ids, n)
    got = ols.reference_scores(seed, config, traffic, feats, ids, n, control=True)
    (check,) = compare.scores(dict(limits=dict(score_gap=None)), got, want,
                              np.ones(n, bool))
    return dict(seed=seed, requests=n, control=dict(score_gap=check[1]))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default="program,control")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--fresh-rows", action="store_true",
                    help="fit cells: draw the rows from the seed too")
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
        if traffic["kind"] == "fit_loop":
            rec = fit_readings(config, traffic, seed, what, args.fresh_rows)
        else:
            rec = serve_readings(config, traffic, seed, args.seconds)
        rec.update(workload=args.workload, device=block)
        line = json.dumps(rec)
        print(line, flush=True)
        with open(path, "a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
