"""The reads of X a trust-region Newton fixed effect needs, from the
solver's reported counts, the same whatever the program's passes.

A value and gradient needs one read of X, and so does a Hessian-vector
product: forward and transpose share every row tile, so one read can serve
each. A TRON solve needs one value and gradient to start, one at each outer
iteration's trial point, and one product a conjugate-gradient step; its
linearized margins and ρ's product could be had from those. The count is
fixed by iterations and CG steps, so no fusion of the program's passes can
take a share built on it past 100 %.
"""

from __future__ import annotations

from typing import Dict, Optional

from benchmark.work import F32


def tron_reads(rows: int, dim: int, solves: int, iterations: int, cg_steps: int
               ) -> Dict[str, float]:
    """``solves + iterations + cg_steps`` reads of X (rows × dim float32),
    each a value and gradient or a product: two matrix-vector products,
    4·rows·dim flops."""
    reads = solves + iterations + cg_steps
    return dict(flops=4.0 * rows * dim * reads,
                bytes=float(rows) * dim * F32 * reads, reads=reads)


def fit_tron_reads(facts: dict) -> Optional[Dict[str, float]]:
    """All TRON fixed-effect solves of ONE fit (its passes summed); nothing
    where no fixed effect reports its CG steps (a program that counts none)."""
    out, hit = dict(flops=0.0, bytes=0.0), False
    for cid, c in facts["counts"].items():
        if c["type"] == "fixed" and "cg_steps" in c:
            w = tron_reads(facts["rows"], facts["dims"][cid], c["passes"],
                           c["iterations"], c["cg_steps"])
            out["flops"] += w["flops"]
            out["bytes"] += w["bytes"]
            hit = True
    return out if hit else None
