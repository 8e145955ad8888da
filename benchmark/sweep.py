#!/usr/bin/env python3
"""The rate sweep that found a serving cell's knee, on the chip, once:

    python3 benchmark/sweep.py --workload serve.glmix2 --rates 400,800,1200 --seconds 8

One engine, one window per rate. The knee is the highest rate at which
nothing is shed and the backlog does not grow over the window (the last
quarter's latencies no worse than the first's); the traffic file then holds
0.8 × knee as a number. The benchmark's own runs never search for a rate.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark import device, run  # noqa: E402
from benchmark.traffic import open_loop_score as ols  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default="chiprun_out/sweep")
    args = ap.parse_args()
    bench = run.load_json("BENCHMARK.json")
    cell, config, traffic = run.load_cell(bench, args.workload)
    block = device.require_tpu(int(cell["chips"]))
    device.configure_cache()
    ctx = run.Context(cell=args.workload, seed=args.seed, seconds=args.seconds,
                      trace=False, config=config, traffic=traffic,
                      clock=device.CompileClock(),
                      work_dir=os.path.join(ROOT, ".bench_work", args.workload))
    rates = [float(r) for r in args.rates.split(",")]
    n_max = int(max(rates) * args.seconds) + 1
    engine, _, _, requests = ols.prepare(ctx, n_max)
    ols.drive(engine, requests[:256], np.arange(256) / rates[0])
    os.makedirs(os.path.join(ROOT, args.out), exist_ok=True)
    path = os.path.join(ROOT, args.out, args.workload + ".jsonl")
    for rate in rates:
        due = ols.schedule(rate, args.seconds, args.seed)
        w = ols.drive(engine, requests[:len(due)], due)
        lat = w["latency_ms"]
        q = len(lat) // 4
        rec = dict(
            workload=args.workload, device=block, rate_per_s=rate,
            requests=len(due), shed=w["shed"],
            failed=int((~np.isfinite(lat)).sum()),
            p50_ms=ols.percentile(lat, 0.5), p95_ms=ols.percentile(lat, 0.95),
            p99_ms=ols.percentile(lat, 0.99),
            first_quarter_p50_ms=ols.percentile(lat[:q], 0.5),
            last_quarter_p50_ms=ols.percentile(lat[-q:], 0.5),
            last_quarter_p95_ms=ols.percentile(lat[-q:], 0.95),
            gen_late_p95_ms=ols.percentile(w["late_ms"][np.isfinite(w["late_ms"])], 0.95),
            sent_wall_s=w["sent_wall_s"], drain_s=w["window_s"] - w["sent_wall_s"],
            retraces=engine.retraces_since_warmup)
        line = json.dumps(rec)
        print(line, flush=True)
        with open(path, "a") as f:
            f.write(line + "\n")
    engine.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
