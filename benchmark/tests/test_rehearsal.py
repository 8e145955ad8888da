"""Each traffic module end to end at a tiny size on the CPU, through the same
``run_cell`` that ``run.py`` calls, with the look for a chip skipped."""

import json
import subprocess
import sys

import pytest

from benchmark import run
from benchmark.tests import tiny


@pytest.mark.parametrize("cell,config", [("fit.glmix2", "glmix2-logistic"),
                                         ("fit.glmix3", "glmix3-logistic")])
@pytest.mark.parametrize("trace", [False, True])
def test_fit_cell_runs_and_is_correct(cell, config, trace):
    result = run.run_cell(tiny.bench(), cell, seed=2**31 + 11, seconds=1.0,
                          trace=trace, device_block=tiny.CPU,
                          overrides=dict(config=tiny.shrink_config(config),
                                         traffic=tiny.FIT))
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    json.dumps(result)
    # the set-up line: one fit in set-up, timed, beside the compile clock
    assert result["setup"]["first_fit_s"] > 0 and "compile_s" in result["setup"]
    assert result["counts"]["global"]["evals"] > 0
    if trace:
        # No device plane on the CPU: trace-born metrics stay silent, none is 0.
        assert "fit_s" not in result["metrics"]
        assert all(m["value"] != 0 for m in result["metrics"].values())
        # the solvers' counts need no trace
        assert {"fe_evals_per_fit", "re_newton_iters_per_fit",
                "re_newton_max_iters_per_fit"} <= set(result["metrics"])
    else:
        assert set(result["metrics"]) == {"fit_s", "setup_s"}
        assert result["metrics"]["fit_s"]["value"] > 0


@pytest.mark.parametrize("trace", [False, True])
def test_serve_cell_runs_and_is_correct(trace):
    result = run.run_cell(tiny.bench(), "serve.glmix2", seed=7, seconds=1.5,
                          trace=trace, device_block=tiny.CPU,
                          overrides=dict(config=tiny.shrink_config("glmix2-logistic"),
                                         traffic=tiny.SERVE))
    assert result["correct"], result["checks"]
    assert result["attempted"] == 600 and result["failed"] == 0
    if trace:
        assert {"queue_wait_ms", "batch_rows", "gen_late_ms"} <= set(result["metrics"])
    else:
        assert set(result["metrics"]) == {"score_p95_ms", "setup_s"}


def test_run_py_exits_nonzero_off_tpu_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "fit.glmix2", "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=tiny.ROOT, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr
