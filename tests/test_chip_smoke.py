"""``chip_smoke.py`` on the CPU: it refuses to run, and its phases' checks
pass on a tiny network in interpret mode (the same code the chip runs at
the BERT-large widths with ``backend="pallas"``)."""

from __future__ import annotations

import importlib.util
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny(smoke):
    sizes, block = (256, 512, 256), 64
    layers = smoke.make_layers(0, sizes=sizes, density=0.5, block=block)
    xs = smoke.make_requests(0, 6, sizes[0], block=block)
    return layers, xs, smoke.reference(layers, xs)


def test_chip_smoke_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, SMOKE], capture_output=True,
                         text=True, timeout=120, env=env, cwd=ROOT)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    assert "no TPU" in res.stderr


def test_requests_have_dead_tiles_for_the_gate(tiny):
    _, xs, _ = tiny
    assert (xs[:, :64] == 0).all() and (xs[:, 64:] != 0).any()


@pytest.mark.parametrize("phase", ["f32", "gated", "bf16", "fp8"])
def test_smoke_phase_checks_pass_in_interpret_mode(smoke, tiny, phase):
    layers, xs, ref = tiny
    name, wdt, gate = next(p for p in smoke.PHASES if p[0] == phase)
    problems = smoke.run_phase(name, layers, xs, ref, wdt, gate,
                               backend="interpret", max_batch=4,
                               bursts=(1, 2, 3), reorder_iters=20)
    assert problems == []


def test_plan_check_reports_a_layered_plan_on_another_backend(smoke, tiny):
    layers, _, _ = tiny
    plan = smoke.Engine(backend="jnp", fuse=False).compile(layers)
    problems = []
    smoke.check_plan("f32", plan, "pallas", problems)
    assert any("backend 'jnp'" in p for p in problems)
    assert any("not fused" in p for p in problems)


def test_smoke_sharded_path_on_four_cpu_devices():
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import importlib.util
        spec = importlib.util.spec_from_file_location("chip_smoke", {SMOKE!r})
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        layers = cs.make_layers(0, sizes=(256, 512, 256), density=0.5,
                                block=64)
        xs = cs.make_requests(0, 6, 256, block=64)
        ref = cs.reference(layers, xs)
        problems = cs.run_sharded(layers, xs, ref, model=4,
                                  backend="interpret", max_batch=4,
                                  bursts=(1, 2, 3), reorder_iters=20)
        assert problems == [], problems
        print("SHARDED_SMOKE_OK")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, env=env, cwd=ROOT)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "SHARDED_SMOKE_OK" in res.stdout
    assert "output_devices=4" in res.stdout
