"""Bounded-RSS streaming ingest at huge-file scale (VERDICT r3 #5).

Builds a multi-GB Avro container WITHOUT hours of pure-Python encoding:
one container body (blocks + sync markers) is encoded once with the repo
writer and its BYTES are replicated after the header — every copy is a
valid independent set of blocks under the same sync marker, so the result
is a spec-valid container of N× the rows. The streaming read then runs in
a FRESH subprocess whose VmHWM (peak RSS) is asserted against a bound
that a slurp of the file would necessarily break.

Gated by PHOTON_BIG_INGEST_GB (disk + minutes): unset → skipped. The
round-4 evidence run used PHOTON_BIG_INGEST_GB=32 on a 125 GB-RAM host
(file > RAM/4; see BENCH_FULL.md for the recorded numbers).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from photon_tpu.io.avro import write_avro_records
from photon_tpu.io.columnar import _load_lib, _read_header
from photon_tpu.io.schemas import TRAINING_EXAMPLE_SCHEMA

BIG_GB = float(os.environ.get("PHOTON_BIG_INGEST_GB", "0"))

pytestmark = [
    pytest.mark.skipif(BIG_GB <= 0, reason="set PHOTON_BIG_INGEST_GB to run"),
    pytest.mark.skipif(_load_lib() is None, reason="native decoder unavailable"),
]

_CHILD = r"""
import json, sys, time
import jax
jax.config.update("jax_platforms", "cpu")

from photon_tpu.data.index_map import IndexMap
from photon_tpu.io.data_reader import FeatureShardConfig, stream_merged
from photon_tpu.io.columnar import read_avro_columnar  # noqa: F401 (native build)

path = sys.argv[1]
# Index maps come from the feature-indexing stage in production (the
# FeatureIndexingDriver); the fixture's feature space is known: f0..f47.
imaps = {"s": IndexMap.build([IndexMap.key(f"f{j}") for j in range(48)])}

def peak_mb():
    for line in open("/proc/self/status"):
        if line.startswith("VmHWM"):
            return int(line.split()[1]) / 1024.0
    return float("nan")

cfg = {"s": FeatureShardConfig(feature_bags=["features"])}
eidx = {}
base_mb = peak_mb()
rows = 0
t0 = time.perf_counter()
for chunk in stream_merged([path], cfg, imaps, entity_id_columns={"userId": "userId"},
                           entity_indexes=eidx, chunk_rows=1 << 16):
    rows += chunk.n  # chunk dropped immediately — bounded memory is the contract
dt = time.perf_counter() - t0
print(json.dumps({
    "rows": rows,
    "secs": round(dt, 2),
    "base_mb": round(base_mb, 1),
    "peak_mb": round(peak_mb(), 1),
    "entities": len(eidx["userId"].ids()),
}))
"""


def _build_big_file(path: str, target_bytes: int) -> int:
    """Replicate one encoded container body to ``target_bytes``. Returns
    total row count."""
    base = path + ".base"
    n, d = 1 << 16, 48
    rng = np.random.default_rng(7)
    records = []
    for i in range(n):
        idx = rng.choice(d, size=12, replace=False)
        records.append({
            "uid": str(i),
            "label": float(i % 2),
            "features": [
                {"name": f"f{j}", "term": "", "value": float(rng.standard_normal())}
                for j in idx
            ],
            "metadataMap": {"userId": f"u{i % 4096}"},
            "weight": 1.0,
            "offset": 0.0,
        })
    write_avro_records(base, TRAINING_EXAMPLE_SCHEMA, records, block_records=8192)

    with open(base, "rb") as f:
        blob = f.read()
    os.unlink(base)
    import io as _io

    _schema, _codec, _sync, body_off = _read_header(_io.BytesIO(blob))
    header, body = blob[:body_off], blob[body_off:]
    repeats = max(1, int(np.ceil((target_bytes - len(header)) / len(body))))
    with open(path, "wb") as f:
        f.write(header)
        for _ in range(repeats):
            f.write(body)
    return n * repeats


def test_streaming_ingest_bounded_rss_on_huge_file(tmp_path):
    target = int(BIG_GB * (1 << 30))
    path = str(tmp_path / "huge.avro")
    expected_rows = _build_big_file(path, target)
    file_gb = os.path.getsize(path) / (1 << 30)
    assert file_gb >= BIG_GB * 0.95

    out = subprocess.run(
        [sys.executable, "-c", _CHILD, path],
        capture_output=True, text=True, timeout=3600,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": os.path.dirname(os.path.dirname(__file__))},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["rows"] == expected_rows
    # Bounded-memory contract: peak RSS delta stays near one chunk, never
    # near the file. 3 GB admits interpreter+jax+chunk with headroom; a
    # slurp of a >=8 GB file cannot fit under it.
    delta_mb = r["peak_mb"] - r["base_mb"]
    assert delta_mb < 3072, r
    gbps = file_gb * (1 << 30) / r["secs"] / 1e9
    print(f"\nhuge-file ingest: {file_gb:.1f} GiB in {r['secs']}s "
          f"({gbps:.2f} GB/s), peak RSS delta {delta_mb:.0f} MB, "
          f"{r['entities']} entities")
