"""The yardstick: nonzero blocks, operations, bytes and peaks."""

import json

import pytest

from bench import counts, network, peaks
from bench.spec import BENCH_DIR, load_cell, load_reader


def _ref():
    import importlib.util
    path = BENCH_DIR / "configs" / "sparse_ffn_ref.py"
    spec = importlib.util.spec_from_file_location("bench_ref_test", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("name,nnz", [("bert-large-ffn", 52),
                                      ("minitron-4b-ffn", 346)])
def test_seed0_networks_keep_the_configured_blocks(name, nnz):
    with open(BENCH_DIR / "configs" / f"{name}.json") as f:
        config = json.load(f)
    ws, _ = network.make_dense(config, 0)
    _, kept = _ref().prune(config, ws)
    assert kept == nnz
    from repro.sparse import prune_dense_stack
    layers = prune_dense_stack(ws, [w[0] for w in ws], config["density"],
                               config["block"], config["block"])
    assert sum(l.nnz_blocks for l in layers) == nnz


def test_counts_of_one_call():
    bert = counts.SparseFFN(n_in=1024, n_hid=4096, n_out=1024, block=128,
                            nnz=52, weight_dtype="bf16")
    assert bert.flops(1) == 2 * 52 * 128 * 128 == bert.flops_per_row()
    assert bert.flops(256) == 256 * bert.flops(1)
    assert bert.bytes(1) == (52 * 128 * 128 * 2 + 52 * 4 + 2048 * 4
                             + 5120 * 4)
    v5e = peaks.peak("TPU v5 lite")
    # a single row streams its weights; 256 rows still read more bytes
    # per FLOP than the chip's balance point of 240
    assert bert.bound_by(1, v5e) == "memory"
    assert bert.bound_s(1, v5e) == pytest.approx(bert.bytes(1) / 819e9)
    f32 = counts.SparseFFN(1024, 4096, 1024, 128, 52, "f32")
    assert f32.bytes(1) - bert.bytes(1) == 52 * 128 * 128 * 2


def test_peaks_refuse_an_unknown_device_kind():
    assert peaks.peak("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="TPU v9"):
        peaks.peak("TPU v9")


def test_every_cell_finds_its_pieces():
    bench = json.loads((BENCH_DIR.parent / "BENCHMARK.json").read_text())
    e2e = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        cell = load_cell(w["name"])
        assert cell.traffic["kind"] in ("poisson", "closed", "offline")
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(load_reader(m["name"]))
            assert m["moves"] in e2e and m["moves"] in reported
        for m in cell.end_to_end:
            assert callable(load_reader(m["name"]))


def test_every_reader_reads_a_run():
    """Each metric's reader on a hand-made run: a number where the run has
    something to read, None where it has not (no trace, no server)."""
    import types

    import numpy as np

    from bench import loadgen, spec, tracing

    bench = json.loads((BENCH_DIR.parent / "BENCHMARK.json").read_text())
    window = loadgen.Window(
        seconds=2.0, attempted=101, failed=0, lost=0, rows=101,
        sample_idx=np.arange(3), sample_out=np.zeros((3, 4)),
        latency_s=np.linspace(0.005, 0.015, 101),
        gen_lag_s=np.full(101, 1e-4),
        counters={"served": 100, "batches": 10, "form_wait_s": 0.5,
                  "form_wait_n": 100, "exec_s": 0.02, "exec_n": 10})
    summary = tracing.Summary(window_s=2.0, busy_s=0.01,
                              kernel_calls=[(8, 2e-5), (16, 2.2e-5)],
                              device_ops=[], idle_gaps=[])
    run = types.SimpleNamespace(
        window=window, setup_s=20.0, trace=summary,
        setup_phases={"engine_compile": 0.1, "bucket_warmup": 3.0},
        device_kind="TPU v5 lite",
        counts=counts.SparseFFN(1024, 4096, 1024, 128, 52, "bf16"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        value = spec.load_reader(m["name"])(run)
        assert isinstance(value, float) and value > 0, m["name"]
        if m["unit"] == "%":
            assert value <= 100.0, m["name"]
    assert spec.load_reader("latency_p50_ms")(run) == pytest.approx(10.0)
    assert spec.load_reader("form_wait_mean_ms")(run) == pytest.approx(5.0)
    assert spec.load_reader("batch_rows_mean")(run) == pytest.approx(10.0)
    assert spec.load_reader("device_idle.flood")(run) == pytest.approx(99.5)

    run.trace = None
    run.window = loadgen.Window(seconds=2.0, attempted=0, failed=0, lost=0,
                                rows=0, sample_idx=np.arange(0),
                                sample_out=np.zeros((0, 4)))
    for name in ("latency_p99_ms", "gen_lag_p99_ms", "form_wait_mean_ms",
                 "bsr_megakernel_roofline.steady", "device_idle.flood",
                 "mfu.offline", "rows_per_s.offline"):
        assert spec.load_reader(name)(run) is None, name


def test_a_split_metric_falls_back_to_its_reader():
    for name in ("device_idle.steady", "device_idle.flood"):
        assert not (BENCH_DIR / "metrics" / f"{name}.py").exists()
        assert load_reader(name).__code__.co_filename.endswith(
            "device_idle.py")


@pytest.mark.parametrize("refused,expect", [
    (0, "base"), (5, "worse"), (20, None)])
def test_refused_requests_count_as_infinitely_late(refused, expect):
    """A refused or lost request reads as infinitely late in the tail: a
    few of them push the p99 up, more than 1% leave it with no finite
    value."""
    import types

    import numpy as np

    from bench import loadgen

    lat = np.random.default_rng(3).uniform(0.005, 0.015, 1000)
    base = float(np.quantile(lat, 0.99, method="inverted_cdf")) * 1e3
    lat[:refused] = np.inf
    window = loadgen.Window(
        seconds=1.0, attempted=1000, failed=refused, lost=0,
        rows=1000 - refused, sample_idx=np.arange(0),
        sample_out=np.zeros((0, 4)), latency_s=lat)
    p99 = load_reader("latency_p99_ms")(types.SimpleNamespace(window=window))
    if expect is None:
        assert p99 is None
    elif expect == "base":
        assert p99 == pytest.approx(base)
    else:
        assert p99 > base
    assert load_reader("latency_p50_ms")(
        types.SimpleNamespace(window=window)) < 15.0
