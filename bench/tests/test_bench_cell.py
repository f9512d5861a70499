"""One cell's code path end to end at a tiny size on the CPU, called as a
function: the Pallas megakernel in interpret mode, the served path, the
comparison with the reference, and the faults it has to catch."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import harness
from bench.spec import ROOT

TINY = {"hidden_size": 256, "intermediate_size": 512, "density": 0.5,
        "backend": "interpret", "max_batch": 8}
OPEN = {"kind": "poisson", "rate_rps": 150.0, "warmup_s": 0.1,
        "pool_rows": 64}
OFFLINE = {"kind": "offline", "rows_per_call": 24, "pool_calls": 2}
# Minitron's epilogue and no bias, on the BERT cell's configuration
RELU2 = {"activation": "squared_relu", "bias": False}
CELL = "bert-large-ffn.steady"


def _run(workload, traffic, seed=2**31 + 5, trace=False, **config):
    return harness.run_cell(workload, seed, 0.5, trace, time.perf_counter(),
                            require_chip=False,
                            config_overrides={**TINY, **config},
                            traffic_overrides=traffic)


@pytest.mark.parametrize("traffic,config,metrics", [
    (OPEN, {}, {"latency_p50_ms", "setup_s"}),
    # the cell's latency metrics find nothing to read in an offline window
    (OFFLINE, RELU2, {"setup_s"}),
])
def test_tiny_cell_is_correct(jax_cache_off, traffic, config, metrics):
    result, checks = _run(CELL, traffic, **config)
    assert result["correct"], checks
    assert set(result["metrics"]) == metrics
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"
    err = result["checks"]["max_err_over_absmax"]
    assert 0 < err["value"] < err["limit"]
    json.dumps(result)


def test_fp8_control_is_not_correct(jax_cache_off):
    """The control: the configuration's bf16 weights streamed as fp8."""
    result, _ = _run(CELL, OPEN, weight_dtype="fp8")
    err = result["checks"]["max_err_over_absmax"]
    assert not result["correct"] and err["value"] > err["limit"]


@pytest.mark.parametrize("traffic,config", [(OPEN, {}), (OFFLINE, RELU2)])
def test_an_altered_answer_is_not_correct(jax_cache_off, monkeypatch,
                                          traffic, config):
    """Each answer altered where the plan set produces it."""
    from repro.serving import BucketedPlanSet
    call = BucketedPlanSet.__call__

    def altered(self, x):
        y = np.array(call(self, x))
        y[:, 0] += 0.5 * np.abs(y).max()
        return y

    monkeypatch.setattr(BucketedPlanSet, "__call__", altered)
    result, _ = _run(CELL, traffic, **config)
    assert not result["correct"]
    assert result["checks"]["max_err_over_absmax"]["value"] > 0.1


def test_an_answer_that_never_comes_is_not_correct(jax_cache_off,
                                                   monkeypatch):
    """Every batch of the window fails in the plan set: no answer comes."""
    from repro.serving import BucketedPlanSet
    call = BucketedPlanSet.__call__
    armed = {"on": False}

    def failing(self, x):
        if armed["on"]:
            raise RuntimeError("planted fault")
        return call(self, x)

    from bench import loadgen
    run = loadgen.OpenLoop.run

    def armed_run(self, seconds):
        armed["on"] = True
        return run(self, seconds)

    monkeypatch.setattr(BucketedPlanSet, "__call__", failing)
    monkeypatch.setattr(loadgen.OpenLoop, "run", armed_run)
    result, _ = _run(CELL, OPEN)
    assert not result["correct"]
    assert result["checks"]["answers_lost"]["value"] == result["attempted"]
    assert result["checks"]["batch_faults"]["value"] > 0
    json.loads(json.dumps(result, allow_nan=False))


def test_the_command_refuses_a_machine_without_an_accelerator():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "bert-large-ffn.steady", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr
    assert "{" not in proc.stdout


def test_the_command_fails_without_the_program(tmp_path):
    """A checkout that holds only the benchmark cannot run a cell."""
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "bert-large-ffn.steady", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
