"""The open loop's collector falling behind, as after a stall of the
machine: every answer the server produced is still taken, late."""

import time

from bench import harness, loadgen

# the jnp path at a tiny size keeps up with the steady cell's 4800 req/s
TINY_JNP = {"hidden_size": 256, "intermediate_size": 512, "density": 0.5,
            "backend": "jnp", "max_batch": 256}
PAUSE_S = 3.0


def test_answers_finished_while_the_collector_stalls_are_not_lost(
        jax_cache_off, monkeypatch):
    """The collector stops for 3 s of a 4 s window at 4800 req/s: some
    10,000 answers finish uncollected, past the server's default capacity
    of 4096 finished results, and each still comes back."""
    take = loadgen.Outstanding.take
    paused = []

    def stalled_take(self, handle):
        if not paused:
            paused.append(True)
            time.sleep(PAUSE_S)
        return take(self, handle)

    run = loadgen.OpenLoop.run

    def armed_run(self, seconds):
        monkeypatch.setattr(loadgen.Outstanding, "take", stalled_take)
        return run(self, seconds)

    monkeypatch.setattr(loadgen.OpenLoop, "run", armed_run)
    result, checks = harness.run_cell(
        "bert-large-ffn.steady", 2**31 + 23, 4.0, False, time.perf_counter(),
        require_chip=False, config_overrides=TINY_JNP)
    assert paused
    assert result["checks"]["answers_lost"]["value"] == 0, checks
    assert result["correct"], checks
