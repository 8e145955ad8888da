"""Tiny sizes for the CPU rehearsal: the same files, the same functions,
``run.run_cell`` with the traffic's scale overridden."""

import copy
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def shrink_config(name: str, fixed_dim: int = 24, random_dim: int = 4) -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        config = json.load(f)
    coords = copy.deepcopy(config["coordinates"])
    for c in coords:
        c["dim"] = fixed_dim if c["type"] == "fixed" else random_dim
    return dict(coordinates=coords)


FIT = dict(rows=1 << 13, entities={"per_user": 48, "per_item": 6}, trace_fits=2,
           limits={"fixed_gap": 5e-4, "random_gap": 5e-4, "random_row_gap": 5e-3})
SERVE = dict(entities={"per_user": 4096}, rate_per_s=400.0, warm_requests=96,
             trace_seconds=0.5, limits={"score_gap": 1e-5},
             serve={"max_batch_size": 16, "max_delay_ms": 2.0, "queue_cap": 1024,
                    "hot_bytes": 1 << 30})
CPU = dict(platform="cpu", kind="cpu", count=1)
