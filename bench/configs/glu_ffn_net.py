"""The network module of a gated FFN: ``y = (act(x Wg + bg) * (x Wu + bu))
Wd + bd``, hidden -> 2 x intermediate -> hidden (SwiGLU where the
activation is silu).  It brings the three dense matrices, the program's
gated pruning and plan, and the counts of the three.
"""

from __future__ import annotations

import dataclasses
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.counts import WEIGHT_BYTES
from bench.network import seed_key
from repro.engine import Engine
from repro.serving import BucketedPlanSet

try:
    from repro.sparse import prune_glu
except ImportError as e:
    raise ImportError("this program has no gated FFN (no "
                      "repro.sparse.prune_glu), so it cannot run a gated "
                      "configuration") from e


def shapes(config: dict) -> Tuple[List[Tuple[int, int]], List[int]]:
    """The shapes of ``(Wg, Wu, Wd)`` and of their biases."""
    h, i = config["hidden_size"], config["intermediate_size"]
    return [(h, i), (h, i), (i, h)], [i, i, h]


def make_dense(config: dict, seed: int):
    """Dense f32 ``[Wg, Wu, Wd]`` N(0, weight_std^2) and biases N(0,
    bias_std^2) (zeros where the configuration has no bias), made on the
    device from ``seed``."""
    w_shapes, b_sizes = shapes(config)
    w_std = config["weight_std"]
    b_std = config["bias_std"] if config["bias"] else 0.0

    @jax.jit
    def gen(key):
        ks = jax.random.split(key, 6)
        ws = [w_std * jax.random.normal(k, s, jnp.float32)
              for k, s in zip(ks[:3], w_shapes)]
        bs = [b_std * jax.random.normal(k, (n,), jnp.float32)
              for k, n in zip(ks[3:], b_sizes)]
        return ws, bs

    ws, bs = jax.device_get(gen(seed_key(seed)))
    return [np.asarray(w) for w in ws], [np.asarray(b) for b in bs]


def prune(config: dict, ws, bs):
    return prune_glu(ws, bs, config["density"], config["block"])


def compile(config: dict, layers) -> BucketedPlanSet:  # noqa: A001
    engine = Engine(backend=config["backend"],
                    activation=config["activation"],
                    weight_dtype=config["weight_dtype"])
    return BucketedPlanSet.compile(layers, engine=engine,
                                   max_batch=config["max_batch"])


@dataclasses.dataclass(frozen=True)
class GLUCounts:
    """Operations and bytes of one call of the gated FFN, with ``nnz`` kept
    blocks over gate, up and down (the reference's pruning), for a call of
    ``rows`` rows:

      * FLOPs = 2 * rows * nnz * block^2;
      * bytes = nnz * block^2 * (bytes per weight element)   weight blocks
              + nnz * 4                                      dequant scales
              + rows * (n_in + n_out) * 4                    input and output
              + (2 * n_hid + n_out) * 4                      biases, if any
    """

    n_in: int
    n_hid: int
    n_out: int
    block: int
    nnz: int
    weight_dtype: str
    bias: bool

    def flops(self, rows: int) -> int:
        return rows * self.flops_per_row()

    def bytes(self, rows: int) -> int:
        return (self.nnz * self.block ** 2 * WEIGHT_BYTES[self.weight_dtype]
                + self.nnz * 4
                + rows * (self.n_in + self.n_out) * 4
                + ((2 * self.n_hid + self.n_out) * 4 if self.bias else 0))

    def flops_per_row(self) -> int:
        return 2 * self.nnz * self.block ** 2

    def bound_s(self, rows: int, peak: dict) -> float:
        return max(self.flops(rows) / peak["flops_per_s"],
                   self.bytes(rows) / peak["bytes_per_s"])

    def bound_by(self, rows: int, peak: dict) -> str:
        t_flops = self.flops(rows) / peak["flops_per_s"]
        t_bytes = self.bytes(rows) / peak["bytes_per_s"]
        return "compute" if t_flops >= t_bytes else "memory"


def counts(config: dict, nnz: int) -> GLUCounts:
    h, i = config["hidden_size"], config["intermediate_size"]
    return GLUCounts(n_in=h, n_hid=i, n_out=h, block=config["block"],
                     nnz=nnz, weight_dtype=config["weight_dtype"],
                     bias=bool(config["bias"]))
