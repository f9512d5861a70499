"""The network a cell serves, made from ``--seed``, and the program's plan.

Weights come from one jitted call on the device.  The dense matrices stay
with the benchmark: the program prunes its own copy through its public
entries, and the reference (the configuration's ``"reference"`` module)
prunes the same matrices by its own code, so it takes nothing the
program made.

A configuration whose ``"network"`` key names a module
(``configs/<module>.py``) brings its own network through these hooks:

  * ``make_dense(config, seed) -> (ws, bs)``: the dense f32 weights and
    biases, which the reference's ``prune`` and ``forward`` also receive;
  * ``prune(config, ws, bs) -> layers``: the program's pruning;
  * ``compile(config, layers) -> BucketedPlanSet``: the program's plan;
  * ``counts(config, nnz)``: the operations and bytes of one kernel call
    (``flops``, ``bytes``, ``flops_per_row``, ``bound_s``, ``bound_by``, as
    ``counts.SparseFFN`` has them), ``nnz`` the reference's kept blocks.

The module defines all four.  A configuration without the key gets the
chain's below: hidden -> intermediate -> hidden.
"""

from __future__ import annotations

import dataclasses
import time
from pathlib import Path
from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.counts import SparseFFN
from bench.spec import CONFIGS_DIR, load_module
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
    """The chain's dense weights, at ``widths(config)``."""
    return dense_chain(widths(config), config, seed)


def dense_chain(sizes: Sequence[int], config: dict, seed: int
                ) -> Tuple[List[np.ndarray], List[np.ndarray]]:
    """Dense f32 weights N(0, weight_std^2) of a chain through ``sizes``,
    and biases N(0, bias_std^2) (zeros where the configuration has no
    bias), made on the device."""
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


def _prune(config: dict, ws, bs) -> list:
    block = config["block"]
    return prune_dense_stack(ws, bs, config["density"], block, block)


def _compile(config: dict, layers) -> BucketedPlanSet:
    engine = Engine(backend=config["backend"],
                    activation=config["activation"],
                    weight_dtype=config["weight_dtype"])
    return BucketedPlanSet.compile(layers, engine=engine,
                                   max_batch=config["max_batch"])


def _counts(config: dict, nnz: int) -> SparseFFN:
    hidden = config["hidden_size"]
    return SparseFFN(n_in=hidden, n_hid=config["intermediate_size"],
                     n_out=hidden, block=config["block"], nnz=nnz,
                     weight_dtype=config["weight_dtype"])


CHAIN = {"make_dense": make_dense, "prune": _prune, "compile": _compile,
         "counts": _counts}


def hooks(config: dict, directory: Path = CONFIGS_DIR
          ) -> Dict[str, Callable]:
    """The configuration's hooks: its ``"network"`` module's, found in
    ``directory``, else the chain's."""
    name = config.get("network")
    if name is None:
        return dict(CHAIN)
    module = load_module(name, directory)
    missing = [hook for hook in CHAIN
               if not callable(getattr(module, hook, None))]
    if missing:
        raise AttributeError(f"network module {name!r} defines no "
                             f"{', '.join(missing)}")
    return {hook: getattr(module, hook) for hook in CHAIN}


def build(config: dict, seed: int, net: Dict[str, Callable]) -> System:
    """Weights from ``seed``, pruned and compiled by the program through
    the hooks ``net``."""
    (ws, bs), weights_s = timed(net["make_dense"], config, seed)
    layers, prune_s = timed(net["prune"], config, ws, bs)
    plans = net["compile"](config, layers)
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
