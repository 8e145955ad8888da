#!/usr/bin/env python3
"""One run of one cell of the benchmark, in a new process:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (``--trace 1``:
``breakdown`` too; a fit cell: ``counts``, the solvers' own) and, last,
``checks``: every number that was compared, beside its limit. The same
numbers are the last lines of standard error. The line before the last holds
``setup``: ``setup_s`` with the compile clock and a fit cell's first fit.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file found by its name in ``BENCHMARK.json`` (see
``benchmark/README.md``); this file knows none of them. Off a TPU, or in a
directory without the program, it exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import json
import math
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import device  # noqa: E402  (needs ROOT on the path)

T_PROCESS = device.process_start_time()


def load_json(*parts: str) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json; it has "
                     f"{[c['name'] for c in bench['workloads']]}")


def load_cell(bench: dict, name: str):
    """``(cell, config, traffic)``: the cell's entry, its configuration's file
    and its traffic mix's file, found by the names in ``BENCHMARK.json``."""
    cell = find_cell(bench, name)
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return (cell, load_json(cfg_entry["file"]),
            load_json("benchmark", "workloads", cell["traffic"] + ".json"))


def cell_metrics(bench: dict, group: str, cell: str, reported=None) -> list:
    """The metrics of ``group`` that this cell reports: those that list it,
    and those that list no cells and move a metric this cell reports."""
    out = []
    for m in bench[group]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif reported is None or m.get("moves") in reported:
            out.append(m)
    return out


@dataclasses.dataclass
class Context:
    cell: str
    seed: int
    seconds: float
    trace: bool
    config: dict
    traffic: dict
    clock: object
    work_dir: str
    t_process: float = T_PROCESS
    setup_s: float = None
    setup_detail: dict = None

    def log(self, msg: str) -> None:
        print(f"[bench {time.time() - self.t_process:7.1f}s] {msg}",
              file=sys.stderr, flush=True)

    def setup_done(self, **detail) -> None:
        """Called by the traffic module at the first timed event. ``detail``
        (a fit cell's ``first_fit_s``) goes out with the compile clock on the
        set-up line, which comes before the result's and is not a metric."""
        self.setup_s = time.time() - self.t_process
        self.setup_detail = dict(setup_s=self.setup_s, **detail,
                                 **self.clock.snapshot())
        self.log(f"set-up done: {self.setup_detail}")


def run_cell(bench: dict, cell_name: str, seed: int, seconds: float, trace: bool,
             device_block: dict, overrides: dict = None) -> dict:
    """Drive one cell and build the result object. ``overrides`` replaces
    keys of the traffic file (the tests' tiny sizes; never used by main)."""
    _, config, traffic = load_cell(bench, cell_name)
    if overrides:
        config = {**config, **overrides.get("config", {})}
        traffic = {**traffic, **overrides.get("traffic", {})}
    ctx = Context(cell=cell_name, seed=seed, seconds=seconds, trace=trace,
                  config=config, traffic=traffic, clock=device.CompileClock(),
                  work_dir=os.path.join(ROOT, ".bench_work", cell_name))
    os.makedirs(ctx.work_dir, exist_ok=True)
    module = importlib.import_module(f"benchmark.traffic.{traffic['kind']}")
    out = module.run(ctx)

    from benchmark import compare, layers

    e2e = cell_metrics(bench, "end_to_end", cell_name)
    values = dict(out["end_to_end"], setup_s=ctx.setup_s)
    dev = dict(device_block, memory_peak_bytes=out["memory_peak_bytes"])
    result = dict(correct=False, attempted=out["attempted"], failed=out["failed"])
    checks = list(out["checks"])
    if trace:
        per_layer = cell_metrics(bench, "per_layer", cell_name,
                                 reported={m["name"] for m in e2e})
        facts = dict(out["facts"], device_kind=device_block["kind"])
        metrics, extra = layers.read_all(per_layer, facts, ctx.log)
        shutil.rmtree(ctx.work_dir, ignore_errors=True)   # the trace is reduced
        dev.update(extra.get("device", {}))
        if "breakdown" in extra:
            result["breakdown"] = extra["breakdown"]
    else:
        metrics = {}
        for m in e2e:
            value = values.get(m["name"])
            if value is None or not math.isfinite(value):
                # A metric the run could not take (p95 on a failed request):
                # the run is a failed run, and the line holds no non-number.
                checks.append((m["name"] + "_taken", 0, 1))
                continue
            metrics[m["name"]] = dict(value=value, unit=m["unit"])
    result["metrics"] = metrics
    result["device"] = dev
    if out.get("counts"):
        result["counts"] = out["counts"]   # the solvers' own, of the last fit
    result["setup"] = ctx.setup_detail     # printed on a line of its own
    result["correct"] = compare.verdict(checks)
    result["checks"] = {name: dict(value=value, limit=limit)
                        for name, value, limit in checks}
    return result


def print_result(result: dict) -> None:
    print(json.dumps({"setup": result.pop("setup", None)}), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_json("BENCHMARK.json")
    cell = find_cell(bench, args.workload)
    block = device.require_tpu(int(cell["chips"]))   # exits 3 off a TPU
    device.configure_cache()
    result = run_cell(bench, args.workload, args.seed, args.seconds,
                      bool(args.trace), block)
    print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
