"""The serving window: requests built during set-up and sent in-process to
``ServingEngine.submit`` on a fixed schedule, open loop.

Latency is completion minus the time the request was *due*, so a stall
delays every later request too; how late the generator itself ran is
reported beside it. A shed, errored or never-answered request is ``failed``
and ranks as slower than every completed one when the percentile is taken.
"""

from __future__ import annotations

import gc
import math
import threading
import time

import numpy as np

from benchmark import compare, data, device, program, tracing
from benchmark.readers import registry_histogram


def schedule(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times of a Poisson stream: every seed gets the SAME set of
    inter-arrival gaps (the exponential's quantiles at this rate), in
    another order, so that no seed offers more load or wider bursts than
    another by its draw."""
    n = max(int(round(rate * seconds)), 1)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps *= (seconds / gaps.sum())
    rng = np.random.default_rng([int(seed), 1])
    order = rng.permutation(gaps)
    return np.cumsum(order) - order[0]      # the first request is due at 0


def zipf_ids(rng, n: int, entities: int, s: float) -> np.ndarray:
    """Zipf(s) ranks over [0, entities), rank r → a fixed scattered id so the
    hot entities are not neighbours in the table."""
    ranks = np.empty(n, np.int64)
    filled = 0
    while filled < n:
        draw = rng.zipf(s, size=2 * (n - filled))
        draw = draw[draw <= entities][: n - filled]
        ranks[filled:filled + draw.size] = draw - 1
        filled += draw.size
    return (ranks * 2654435761) % entities


def percentile(latencies: np.ndarray, q: float) -> float:
    """The q-th percentile by nearest rank over ALL requests; a request that
    failed holds +inf and so ranks last."""
    ordered = np.sort(latencies)
    return float(ordered[min(len(ordered) - 1, math.ceil(q * len(ordered)) - 1)])


def build_inputs(seed: int, config, traffic, n: int):
    """Feature rows and entity ids for n requests, from the seed (host numpy:
    a few hundred MB at most), keyed by coordinate id."""
    rng = np.random.default_rng([int(seed), 2])
    feats, ids = {}, {}
    for c in config["coordinates"]:
        x = rng.standard_normal((n, c["dim"]), dtype=np.float32)
        x[:, c["intercept"]] = 1.0
        feats[c["id"]] = x
    for c in program.coordinates(config, "random"):
        entities = int(traffic["entities"][c["id"]])
        eid = zipf_ids(rng, n, entities, float(traffic["zipf_s"]))
        unseen = rng.uniform(size=n) < float(traffic["unseen_share"])
        eid[unseen] = entities + eid[unseen]      # outside the table: cold start
        ids[c["id"]] = eid
    return feats, ids


def make_table(ctx_seed: int, config: dict, traffic: dict, c: dict):
    """One coordinate's seeded coefficient table, on the device."""
    stream = [k["id"] for k in config["coordinates"]].index(c["id"])
    if c["type"] == "fixed":
        return data.make_table(ctx_seed, stream, 1, c["dim"],
                               1.0 / math.sqrt(c["dim"]))[0]
    return data.make_table(ctx_seed, stream, int(traffic["entities"][c["id"]]),
                           c["dim"], 0.5)


def reference_scores(seed: int, config: dict, traffic: dict, feats, ids, n: int,
                     control: bool = False) -> np.ndarray:
    """The plain reference's score of the first ``n`` requests, the tables
    made anew from the seed; an id outside the table is clamped to -1 (cold
    start, fixed effect only)."""
    import jax.numpy as jnp

    from benchmark.reference import score as ref

    (fixed,) = program.coordinates(config, "fixed")
    random = program.coordinates(config, "random")
    clamp = {c["id"]: jnp.asarray(np.where(
        ids[c["id"]][:n] < int(traffic["entities"][c["id"]]), ids[c["id"]][:n], -1
    ).astype(np.int32)) for c in random}
    return np.asarray(ref.score(
        jnp.asarray(feats[fixed["id"]][:n]),
        make_table(seed, config, traffic, fixed),
        {c["id"]: jnp.asarray(feats[c["id"]][:n]) for c in random},
        {c["id"]: make_table(seed, config, traffic, c) for c in random},
        clamp, control=control))


def prepare(ctx, n: int):
    """Set-up: tables from the seed (made on the device, one copy to the
    host for the store's master), the engine warmed on its own bucket grid,
    and ``n`` requests. Returns ``(engine, feats, ids, requests)``."""
    config, traffic = ctx.config, ctx.traffic
    host = {}
    for c in config["coordinates"]:
        dev_table = make_table(ctx.seed, config, traffic, c)
        host[c["id"]] = np.asarray(dev_table)
        del dev_table
    ctx.log("tables made on the device and copied to the host")
    engine = program.build_engine(config, host, traffic["serve"])
    ctx.log(f"engine built and warmed; compile clock {ctx.clock.snapshot()}")
    feats, ids = build_inputs(ctx.seed, config, traffic, n)
    requests = [
        program.score_request(config, {k: v[i] for k, v in feats.items()},
                              {k: int(v[i]) for k, v in ids.items()})
        for i in range(n)]
    ctx.log(f"{n} requests built")
    # The pre-built requests are ~10 live objects each; left to the cyclic
    # collector, its full passes over them stall both threads for tens of ms.
    # A server holds no such backlog: park what set-up made.
    gc.collect()
    gc.freeze()
    return engine, feats, ids, requests


def drive(engine, requests, due: np.ndarray) -> dict:
    """Send ``requests[i]`` at ``due[i]`` (seconds from now), open loop, and
    wait for every answer, a minute past the close if need be. Latency is
    taken from the due time; a shed or unanswered request keeps +inf."""
    n = len(due)
    done_at = np.full(n, np.inf)
    served = np.full(n, np.nan, np.float32)
    sent_at = np.full(n, np.nan)
    futures = [None] * n
    shed = 0

    def on_done(i, t0):
        def cb(f):
            if f.exception() is None:
                served[i] = f.result()
                done_at[i] = time.perf_counter() - t0
        return cb

    t0 = time.perf_counter()
    for i in range(n):
        delay = t0 + due[i] - time.perf_counter()
        if delay > 0:
            with tracing.annotate("bench/wait"):
                time.sleep(delay)
        sent_at[i] = time.perf_counter() - t0
        with tracing.annotate("bench/submit"):
            try:
                fut = engine.submit(requests[i])
            except Exception:  # noqa: BLE001 — shed (backpressure/quota): failed
                shed += 1
                continue
        fut.add_done_callback(on_done(i, t0))
        futures[i] = fut
    sent_wall = time.perf_counter() - t0
    deadline = time.perf_counter() + 60.0
    for f in futures:
        if f is not None:
            try:
                f.exception(timeout=max(0.0, deadline - time.perf_counter()))
            except Exception:  # noqa: BLE001 — never came: stays failed
                pass
    return dict(done_at=done_at, served=served, sent_at=sent_at, shed=shed,
                sent_wall_s=sent_wall, window_s=time.perf_counter() - t0,
                latency_ms=(done_at - due) * 1e3, late_ms=(sent_at - due) * 1e3)


def run(ctx) -> dict:
    config, traffic = ctx.config, ctx.traffic
    rate = float(traffic["rate_per_s"])
    warm_n = int(traffic["warm_requests"])
    due = schedule(rate, ctx.seconds, ctx.seed)
    n = len(due)

    # -- set-up ------------------------------------------------------------------
    engine, feats, ids, requests = prepare(ctx, n + warm_n)
    warm = drive(engine, requests[n:], np.arange(warm_n) / rate)
    ctx.log(f"warm-up: {warm_n} requests, p95 "
            f"{percentile(warm['latency_ms'], 0.95):.3f} ms")
    retraces0 = engine.retraces_since_warmup
    registry0 = program.registry_snapshot()
    before = ctx.clock.snapshot()
    tracer = tracing.Tracer(ctx)
    tracer.start()
    stopper = None
    if tracer.enabled:
        stopper = threading.Timer(float(traffic["trace_seconds"]), tracer.stop)
        stopper.daemon = True
        stopper.start()
    ctx.setup_done()

    # -- the window -------------------------------------------------------------
    w = drive(engine, requests[:n], due)
    if stopper:
        stopper.cancel()
    tracer.stop()
    after = ctx.clock.snapshot()
    retraces = engine.retraces_since_warmup - retraces0
    registry1 = program.registry_snapshot()
    peak = device.peak_bytes()
    answered = np.isfinite(w["done_at"])
    failed = int(n - answered.sum())
    late_ms = w["late_ms"][np.isfinite(w["late_ms"])]
    p95 = percentile(w["latency_ms"], 0.95)
    ctx.log(f"window: sent {n} in {w['sent_wall_s']:.2f}s, answered "
            f"{int(answered.sum())}, shed {w['shed']}, p50 "
            f"{percentile(w['latency_ms'], 0.5):.3f} ms, p95 {p95:.3f} ms, "
            f"generator late p95 {percentile(late_ms, 0.95):.3f} ms")

    # -- free the engine, then the reference over every request of the window ---
    engine.close()
    del engine, requests
    with tracing.annotate("bench/reference"):
        want = reference_scores(ctx.seed, config, traffic, feats, ids, n)
    batch_rows = registry_histogram.read(
        dict(metric="serve_batch_rows"),
        dict(registry_before=registry0, registry_after=registry1))
    checks = compare.scores(traffic, w["served"], want, answered)
    checks.append(("compiles_in_window",
                   after["backend_compiles"] - before["backend_compiles"], 0))
    checks.append(("retraces_in_window", retraces, 0))
    return dict(
        attempted=n, failed=failed, memory_peak_bytes=peak, checks=checks,
        end_to_end={"score_p95_ms": p95},
        facts=dict(
            answered=int(answered.sum()), mean_batch_rows=batch_rows,
            late_ms=late_ms.tolist(),
            registry_before=registry0, registry_after=registry1,
            dims={c["id"]: c["dim"] for c in config["coordinates"]},
            random=[c["id"] for c in program.coordinates(config, "random")],
            trace_path=tracer.path, window_s=w["window_s"]),
    )
