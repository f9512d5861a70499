"""A network module of a configuration: three chained matrices, hidden ->
intermediate -> intermediate -> hidden, the activation after the first
two.  The default builder of ``bench/network.py`` makes a chain of two; this
module brings the third matrix and the counts that go with it."""

from __future__ import annotations

import dataclasses
from typing import Tuple

from bench.counts import WEIGHT_BYTES
from bench.network import dense_chain
from repro.engine import Engine
from repro.serving import BucketedPlanSet
from repro.sparse import prune_dense_stack


def widths(config: dict) -> Tuple[int, ...]:
    hidden, inter = config["hidden_size"], config["intermediate_size"]
    return (hidden, inter, inter, hidden)


def make_dense(config: dict, seed: int):
    return dense_chain(widths(config), config, seed)


def prune(config: dict, ws, bs):
    block = config["block"]
    return prune_dense_stack(ws, bs, config["density"], block, block)


def compile(config: dict, layers) -> BucketedPlanSet:  # noqa: A001
    engine = Engine(backend=config["backend"],
                    activation=config["activation"],
                    weight_dtype=config["weight_dtype"])
    return BucketedPlanSet.compile(layers, engine=engine,
                                   max_batch=config["max_batch"])


@dataclasses.dataclass(frozen=True)
class ChainCounts:
    """Operations and bytes of one call through a chain of ``sizes``, with
    ``nnz`` kept blocks over all its matrices."""

    sizes: Tuple[int, ...]
    block: int
    nnz: int
    weight_dtype: str

    def flops(self, rows: int) -> int:
        return rows * self.flops_per_row()

    def bytes(self, rows: int) -> int:
        return (self.nnz * self.block ** 2 * WEIGHT_BYTES[self.weight_dtype]
                + self.nnz * 4
                + rows * (self.sizes[0] + self.sizes[-1]) * 4
                + sum(self.sizes[1:]) * 4)

    def flops_per_row(self) -> int:
        return 2 * self.nnz * self.block ** 2

    def bound_s(self, rows: int, peak: dict) -> float:
        return max(self.flops(rows) / peak["flops_per_s"],
                   self.bytes(rows) / peak["bytes_per_s"])

    def bound_by(self, rows: int, peak: dict) -> str:
        t_flops = self.flops(rows) / peak["flops_per_s"]
        t_bytes = self.bytes(rows) / peak["bytes_per_s"]
        return "compute" if t_flops >= t_bytes else "memory"


def counts(config: dict, nnz: int) -> ChainCounts:
    return ChainCounts(sizes=widths(config), block=config["block"], nnz=nnz,
                       weight_dtype=config["weight_dtype"])
