"""The network a cell serves, made from ``--seed``, and the program's plan.

Weights come from one jitted call on the device.  The dense matrices stay
with the benchmark: the program prunes its own copy through its public
``prune_dense_stack``, and the reference (``configs/sparse_ffn_ref.py``)
prunes the same matrices by its own code, so it takes nothing the
program made.
"""

from __future__ import annotations

import dataclasses
import time
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine import Engine
from repro.serving import BucketedPlanSet
from repro.sparse import prune_dense_stack


def widths(config: dict) -> Tuple[int, int, int]:
    return (config["hidden_size"], config["intermediate_size"],
            config["hidden_size"])


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any seed below 2**64: the low and high 32 bits."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed must be in [0, 2**64), got {seed}")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def make_dense(config: dict, seed: int
               ) -> Tuple[List[np.ndarray], List[np.ndarray]]:
    """Dense f32 weights N(0, weight_std^2) and biases N(0, bias_std^2)
    (zeros where the configuration has no bias), made on the device."""
    sizes = widths(config)
    w_std = config["weight_std"]
    b_std = config["bias_std"] if config["bias"] else 0.0

    @jax.jit
    def gen(key):
        ks = jax.random.split(key, 2 * (len(sizes) - 1))
        ws = [w_std * jax.random.normal(ks[i], (a, b), jnp.float32)
              for i, (a, b) in enumerate(zip(sizes[:-1], sizes[1:]))]
        bs = [b_std * jax.random.normal(ks[len(ws) + i], (b,), jnp.float32)
              for i, b in enumerate(sizes[1:])]
        return ws, bs

    ws, bs = jax.device_get(gen(seed_key(seed)))
    return [np.asarray(w) for w in ws], [np.asarray(b) for b in bs]


@dataclasses.dataclass
class System:
    """The system under test and what its set-up took."""

    plans: BucketedPlanSet
    dense: Tuple[List[np.ndarray], List[np.ndarray]]
    weights_s: float          # dense weights made on the device
    prune_s: float            # the program's pruning on the host
    engine_compile_s: float   # BucketedPlanSet.compile


def build(config: dict, seed: int) -> System:
    """Weights from ``seed``, pruned and compiled by the program."""
    (ws, bs), weights_s = timed(make_dense, config, seed)
    block = config["block"]
    layers, prune_s = timed(prune_dense_stack, ws, bs, config["density"],
                            block, block)
    engine = Engine(backend=config["backend"],
                    activation=config["activation"],
                    weight_dtype=config["weight_dtype"])
    plans = BucketedPlanSet.compile(layers, engine=engine,
                                    max_batch=config["max_batch"])
    return System(plans=plans, dense=(ws, bs), weights_s=weights_s,
                  prune_s=prune_s, engine_compile_s=plans.compile_s)


def plan_problems(plans: BucketedPlanSet, config: dict) -> List[str]:
    """What the compiled plan does differently from what the configuration
    states: the backend, the fused megakernel, no fallback."""
    base = plans.base
    problems = []
    if base.backend != config["backend"]:
        problems.append(f"plan backend {base.backend!r}, configuration "
                        f"{config['backend']!r}")
    if not base.fused:
        problems.append("plan is not fused")
    if base.fallback_reason is not None:
        problems.append(f"plan fell back: {base.fallback_reason}")
    if plans.weight_dtype != config["weight_dtype"]:
        problems.append(f"plan weight dtype {plans.weight_dtype!r}, "
                        f"configuration {config['weight_dtype']!r}")
    return problems


def timed(fn, *args):
    """``(fn(*args), seconds)`` by the host clock."""
    t0 = time.perf_counter()
    out = fn(*args)
    return out, time.perf_counter() - t0
