"""The gated FFN configuration: its network module through a cell on the
CPU, its counts and pruning at Olmo-Hybrid-7B's widths, and the reader of
the kernel's time per grid step."""

import ast
import json
import time
import types

import numpy as np
import pytest

from bench import harness, network, spec
from bench.spec import BENCH_DIR, CONFIGS_DIR

DATA = BENCH_DIR / "tests" / "data"
OLMO = "olmo-hybrid-7b-mlp"
V5E = {"flops_per_s": 197e12, "bytes_per_s": 819e9}


def _config(path):
    with open(path) as f:
        return json.load(f)


def _run_small_cell(monkeypatch, tmp_path, traffic, modules=CONFIGS_DIR):
    """The test configuration as a cell of its own: a ``BENCHMARK.json``
    that lists it, read in place of the checkout's, with the gated modules
    in ``modules``."""
    bench = {
        "configs": [{"name": "glu-ffn-small",
                     "file": str(DATA / "glu-ffn-small.json")}],
        "workloads": [{"name": "glu-ffn-small.offline",
                       "config": "glu-ffn-small",
                       "traffic": "offline_4096", "chips": 1}],
        "end_to_end": [
            {"name": "rows_per_s.offline", "unit": "rows/s",
             "better": "higher", "bound": 0.06, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(harness, "load_cell",
                        lambda w: spec.load_cell(w, tmp_path, modules))
    return harness.run_cell("glu-ffn-small.offline", 2**31 + 17, 0.5, False,
                            time.perf_counter(), require_chip=False,
                            traffic_overrides=traffic)


# 20 rows a call: two full chunks of 8 and one of 4, padded
CHUNKED = {"rows_per_call": 20, "pool_calls": 2}


def test_the_gated_module_runs_a_cell_correctly(jax_cache_off, monkeypatch,
                                                tmp_path):
    result, checks = _run_small_cell(monkeypatch, tmp_path, CHUNKED)
    assert result["correct"], checks
    assert set(result["metrics"]) == {"rows_per_s.offline", "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    err = result["checks"]["max_err_over_absmax"]
    assert 0 < err["value"] < err["limit"]
    assert result["checks"]["plan_faults"]["value"] == 0


def test_a_gated_module_that_compiles_another_activation_is_not_correct(
        jax_cache_off, monkeypatch, tmp_path):
    """The planted fault: a copy of the module whose gate takes relu, where
    the configuration and the reference say silu."""
    modules = tmp_path / "modules"
    modules.mkdir()
    source = (CONFIGS_DIR / "glu_ffn_net.py").read_text()
    stated = 'activation=config["activation"]'
    assert source.count(stated) == 1
    (modules / "glu_ffn_net.py").write_text(
        source.replace(stated, 'activation="relu"'))
    (modules / "glu_ffn_ref.py").write_text(
        (CONFIGS_DIR / "glu_ffn_ref.py").read_text())
    result, _ = _run_small_cell(monkeypatch, tmp_path, CHUNKED, modules)
    err = result["checks"]["max_err_over_absmax"]
    assert not result["correct"] and err["value"] > err["limit"]


@pytest.fixture(scope="module")
def olmo_seed0():
    """Seed 0's dense Olmo-Hybrid-7B MLP (3 x 169 MB of f32), pruned by
    the program and by the reference."""
    config = _config(CONFIGS_DIR / f"{OLMO}.json")
    net = network.hooks(config)
    ws, bs = net["make_dense"](config, 0)
    block = net["prune"](config, ws, bs)
    ref = spec.load_module(config["reference"])
    kept, nnz = ref.masks(config, ws), ref.prune(config, ws)[1]
    return config, net, block, kept, nnz


def test_olmo_keeps_774_blocks_and_counts_a_call(olmo_seed0):
    config, net, block, _, nnz = olmo_seed0
    assert [lay.nnz_blocks for lay in block.layers] == [258, 258, 258]
    assert nnz == 774
    c = net["counts"](config, nnz)
    assert c.flops(128) == 2 * 128 * 774 * 128 ** 2 == 3_246_391_296
    # weight blocks, their scales, 128 rows in and out; no bias bytes
    assert c.bytes(128) == (774 * 128 ** 2 * 2 + 774 * 4
                            + 128 * (3840 + 3840) * 4) == 29_297_688
    assert c.bound_by(128, V5E) == "memory"
    assert c.bound_s(128, V5E) * 1e6 == pytest.approx(35.77, abs=0.01)
    assert c.flops_per_row() == 2 * 774 * 128 ** 2


def test_the_reference_prunes_as_the_program_does(olmo_seed0):
    _, _, block, kept, _ = olmo_seed0
    for lay, mask in zip(block.layers, kept):
        ours = np.zeros_like(mask)
        ours[lay.rows, lay.cols] = True
        np.testing.assert_array_equal(ours, mask)
    np.testing.assert_array_equal(kept[0], kept[1])


def test_the_reference_imports_nothing_of_the_program():
    tree = ast.parse((CONFIGS_DIR / "glu_ffn_ref.py").read_text())
    modules = {a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
               for a in n.names}
    modules |= {n.module for n in ast.walk(tree)
                if isinstance(n, ast.ImportFrom)}
    assert not {m for m in modules if m.split(".")[0] in ("repro", "bench")}


def test_a_program_without_the_gated_entry_fails_at_once(monkeypatch):
    import repro.sparse
    monkeypatch.delattr(repro.sparse, "prune_glu")
    with pytest.raises(ImportError, match="no gated FFN"):
        network.hooks(_config(CONFIGS_DIR / f"{OLMO}.json"))


def test_kernel_step_ns_reads_the_step_counter(monkeypatch):
    import repro.obs
    from repro.obs import trace

    read = spec.load_reader("kernel_step_ns.offline")
    run = types.SimpleNamespace(trace=types.SimpleNamespace(
        kernel_calls=[(128, 250e-6), (128, 270e-6)]))
    trace.reset_span_totals()
    assert read(run) is None                   # no steps counted
    monkeypatch.setattr(trace, "_profiling", lambda: True)
    trace.count("megakernel.steps", 2 * 800)   # two calls of 800 steps
    assert read(run) == pytest.approx(1e9 * 520e-6 / 1600)
    assert read(types.SimpleNamespace(trace=None)) is None
    monkeypatch.delattr(repro.obs, "counter_totals")
    assert read(run) is None                   # a program with no counter
    trace.reset_span_totals()
