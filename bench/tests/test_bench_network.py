"""A configuration's network: the chain that configurations without a
``"network"`` key get, and a module that a configuration names."""

import json
import time

import numpy as np
import pytest

from bench import harness, network, spec
from bench.counts import SparseFFN
from bench.spec import BENCH_DIR

DATA = BENCH_DIR / "tests" / "data"
# seed 0's dense weights and biases on the CPU before configurations could
# name a network module: each array's shape, sum, sum of squares and first
# three values.  Compared to float rounding, since the CPU's code may round
# a last bit otherwise on another machine; another draw moves each sum by
# tens and the sum of squares by about 0.07%.
DENSE_SEED0 = {
    "bert-large-ffn": [
        ((1024, 4096), 26.350466211106678, 3772.932077896305,
         (-0.0172434039413929, 0.02399509772658348, -0.007788205984979868)),
        ((4096, 1024), -47.09595443640567, 3778.383099697362,
         (-0.0377243272960186, -0.012048131786286831, -0.033640798181295395)),
        ((4096,), -8.149402254846791, 41.34866068637463,
         (-0.07438454031944275, 0.0637478157877922, -0.23020805418491364)),
        ((1024,), -1.9049334148294292, 9.324359954493936,
         (0.03399112820625305, 0.10337255895137787, -0.033944956958293915)),
    ],
    "minitron-4b-ffn": [
        ((3072, 9216), -50.477383560908976, 25475.857328081933,
         (-0.0172434039413929, 0.02399509772658348, -0.007788205984979868)),
        ((9216, 3072), -180.49874004082042, 25474.878981866408,
         (-0.0377243272960186, -0.012048131786286831, -0.033640798181295395)),
        ((9216,), 0.0, 0.0, (0.0, 0.0, 0.0)),
        ((3072,), 0.0, 0.0, (0.0, 0.0, 0.0)),
    ],
}
CHAIN_COUNTS = {
    "bert-large-ffn": SparseFFN(n_in=1024, n_hid=4096, n_out=1024,
                                block=128, nnz=52, weight_dtype="bf16"),
    "minitron-4b-ffn": SparseFFN(n_in=3072, n_hid=9216, n_out=3072,
                                 block=128, nnz=346, weight_dtype="bf16"),
}
OPEN = {"kind": "poisson", "rate_rps": 150.0, "warmup_s": 0.1,
        "pool_rows": 64}


def _config(name):
    with open(BENCH_DIR / "configs" / f"{name}.json") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def seed0():
    """Each shipped configuration's seed-0 dense weights, made once."""
    made = {}

    def get(name):
        if name not in made:
            config = _config(name)
            made[name] = config, network.hooks(config)["make_dense"](config, 0)
        return made[name]
    return get


def _data_config():
    with open(DATA / "three-layer-ffn.json") as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(DENSE_SEED0))
def test_the_chain_makes_the_same_weights(seed0, name):
    config, (ws, bs) = seed0(name)
    assert "network" not in config
    arrays = ws + bs
    assert len(arrays) == len(DENSE_SEED0[name])
    for a, (shape, total, squares, head) in zip(arrays, DENSE_SEED0[name]):
        assert a.dtype == np.float32 and a.shape == shape
        a64 = a.astype(np.float64).ravel()
        assert a64.sum() == pytest.approx(total, abs=1e-3)
        assert (a64 * a64).sum() == pytest.approx(squares, rel=1e-6)
        np.testing.assert_allclose(a64[:3], head, rtol=1e-5, atol=1e-9)


@pytest.mark.parametrize("name", sorted(DENSE_SEED0))
def test_the_chain_keeps_the_same_blocks(seed0, name):
    config, (ws, bs) = seed0(name)
    layers = network.hooks(config)["prune"](config, ws, bs)
    assert len(layers) == 2
    assert sum(l.nnz_blocks for l in layers) == CHAIN_COUNTS[name].nnz
    _, nnz = spec.load_module(config["reference"]).prune(config, ws)
    assert nnz == CHAIN_COUNTS[name].nnz


@pytest.mark.parametrize("name", sorted(DENSE_SEED0))
def test_the_chain_counts_as_before(name):
    config = _config(name)
    assert network.hooks(config) == network.CHAIN
    assert network.hooks(config)["counts"](
        config, CHAIN_COUNTS[name].nnz) == CHAIN_COUNTS[name]


def test_a_module_brings_three_matrices_and_its_counts():
    config = _data_config()
    net = network.hooks(config, DATA)
    assert all(net[h] is not network.CHAIN[h] for h in network.CHAIN)
    ws, bs = network.make_dense(config, 3)
    assert len(ws) == 2          # what the chain would make of it
    ws, bs = net["make_dense"](config, 3)
    assert [w.shape for w in ws] == [(256, 512), (512, 512), (512, 256)]
    assert [b.shape for b in bs] == [(512,), (512,), (256,)]
    layers = net["prune"](config, ws, bs)
    per_layer = [l.nnz_blocks for l in layers]
    assert per_layer == [4, 8, 4]    # half of 8, 16 and 8 tiles
    _, nnz = spec.load_module(config["reference"]).prune(config, ws)
    assert nnz == sum(per_layer)
    c = net["counts"](config, nnz)
    assert not isinstance(c, SparseFFN)
    assert c.flops_per_row() == 2 * 16 * 128 ** 2 == c.flops(1)
    assert c.bytes(1) == (16 * 128 ** 2 * 2 + 16 * 4 + (256 + 256) * 4
                          + (512 + 512 + 256) * 4)
    v5e = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    assert c.bound_by(1, v5e) == "memory"
    assert c.bound_s(1, v5e) == pytest.approx(c.bytes(1) / 819e9)


@pytest.mark.parametrize("hook", sorted(network.CHAIN))
def test_a_module_missing_a_hook_fails_with_its_name(tmp_path, hook):
    """A module that leaves a hook out gets no chain's hook in its place:
    the chain's counts would be the wrong yardstick for its network."""
    source = (DATA / "three_layer_net.py").read_text()
    (tmp_path / "short_net.py").write_text(
        f"{source}\n\ndel {hook}\n")
    with pytest.raises(AttributeError,
                       match=f"'short_net' defines no {hook}$"):
        network.hooks({"network": "short_net"}, tmp_path)


@pytest.mark.parametrize("key", ["network", "reference"])
def test_a_module_no_file_answers_fails_at_once(jax_cache_off, monkeypatch,
                                                key):
    def never(*args):
        raise AssertionError("set-up went on without the module")

    monkeypatch.setattr(network, "CHAIN", {h: never for h in network.CHAIN})
    with pytest.raises(FileNotFoundError, match="no_such_module"):
        harness.run_cell("bert-large-ffn.steady", 1, 0.5, False,
                         time.perf_counter(), require_chip=False,
                         config_overrides={key: "no_such_module"})


def _run_data_cell(monkeypatch, tmp_path, network_source):
    """The test configuration as a cell of its own: a ``BENCHMARK.json``
    that lists it, read in place of the checkout's, with its modules (the
    network ``network_source``, the shipped reference) in a directory of
    their own."""
    modules = tmp_path / "modules"
    modules.mkdir()
    (modules / "three_layer_net.py").write_text(network_source)
    (modules / "sparse_ffn_ref.py").write_text(
        (spec.CONFIGS_DIR / "sparse_ffn_ref.py").read_text())
    bench = {
        "configs": [{"name": "three-layer-ffn",
                     "file": str(DATA / "three-layer-ffn.json")}],
        "workloads": [{"name": "three-layer-ffn.steady",
                       "config": "three-layer-ffn",
                       "traffic": "poisson_4800", "chips": 1}],
        "end_to_end": [
            {"name": "latency_p50_ms", "unit": "ms", "better": "lower",
             "bound": 0.09, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(harness, "load_cell",
                        lambda w: spec.load_cell(w, tmp_path, modules))
    return harness.run_cell("three-layer-ffn.steady", 2**31 + 11, 0.5, False,
                            time.perf_counter(), require_chip=False,
                            traffic_overrides=OPEN)


def test_a_module_runs_a_cell_correctly(jax_cache_off, monkeypatch,
                                        tmp_path):
    result, checks = _run_data_cell(
        monkeypatch, tmp_path, (DATA / "three_layer_net.py").read_text())
    assert result["correct"], checks
    assert set(result["metrics"]) == {"latency_p50_ms", "setup_s"}
    assert result["failed"] == 0 and result["attempted"] > 0
    err = result["checks"]["max_err_over_absmax"]
    assert 0 < err["value"] < err["limit"]


def test_a_module_that_compiles_another_activation_is_not_correct(
        jax_cache_off, monkeypatch, tmp_path):
    """The planted fault: a copy of the module whose compile takes relu,
    where the configuration and the reference say gelu."""
    source = (DATA / "three_layer_net.py").read_text()
    stated = 'activation=config["activation"]'
    assert source.count(stated) == 1
    result, _ = _run_data_cell(
        monkeypatch, tmp_path, source.replace(stated, 'activation="relu"'))
    err = result["checks"]["max_err_over_absmax"]
    assert not result["correct"] and err["value"] > err["limit"]
