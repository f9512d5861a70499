"""Scheduled sparse FFNN execution: the paper's pipeline end to end.

prune -> BSR -> block DAG -> Theorem-1 schedule -> (optional) Connection
Reordering -> fused execution plan.

``ScheduledSparseFFNN`` is the legacy-shaped wrapper kept for existing call
sites and tests; since the engine refactor it is a thin veneer over
``repro.engine.Engine`` — the schedule is compiled once for the whole network
and every call runs the fused plan instead of dispatching layer by layer.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.blocksparse import (
    BlockFFNN,
    BSRLayer,
    GLUBlock,
    simulated_tile_traffic,
    tile_mass,
    to_bsr,
    top_tiles,
)
from repro.engine import Engine, ExecutionPlan
from repro.kernels.ops import CompiledSchedule


def prune_dense_stack(
    weights: Sequence[np.ndarray],
    biases: Sequence[np.ndarray],
    density: float,
    block_m: int = 128,
    block_n: int = 128,
) -> List[BSRLayer]:
    """Block-magnitude-prune a stack of dense layers to ``density``."""
    return [
        to_bsr(w, block_m, block_n, density=density, bias=b)
        for w, b in zip(weights, biases)
    ]


def prune_glu(
    weights: Sequence[np.ndarray],
    biases: Sequence[np.ndarray],
    density: float,
    block: int = 128,
) -> GLUBlock:
    """Block-magnitude-prune a gated unit ``(Wg, Wu, Wd)`` to ``density``.

    Gate and up keep one mask: the top ``density`` share of (input tile,
    hidden tile) positions by their joint mass ``sqrt(|G_ij|^2 + |U_ij|^2)``.
    A hidden unit of a GLU is the product of its gate and up columns, so
    structured GLU pruning keeps or drops the pair together: a gate block
    kept without its up block (or the reverse) would spend a weight on a
    product the other factor zeroes.  Down is pruned by its own block mass.
    """
    wg, wu, wd = weights
    bg, bu, bd = biases
    joint = np.sqrt(tile_mass(wg, block, block) ** 2
                    + tile_mass(wu, block, block) ** 2)
    mask = top_tiles(joint, density)
    return GLUBlock(
        gate=to_bsr(wg, block, block, bias=bg, mask=mask),
        up=to_bsr(wu, block, block, bias=bu, mask=mask),
        down=to_bsr(wd, block, block, density=density, bias=bd),
    )


@dataclasses.dataclass
class ScheduledSparseFFNN:
    """Multi-layer block-sparse FFNN with a paper-optimized execution schedule."""

    layers: List[BSRLayer]
    schedules: List[CompiledSchedule]
    block_ffnn: BlockFFNN
    order: np.ndarray          # block-DAG connection order in effect
    activation: Callable = jax.nn.relu
    plan: ExecutionPlan = None
    engine: Engine = None

    @classmethod
    def build(
        cls,
        layers: Sequence[BSRLayer],
        activation: Callable = jax.nn.relu,
        reorder: bool = False,
        M_tiles: int = 3,
        reorder_iters: int = 2000,
        seed: int = 0,
        backend: str = "auto",
        fuse: bool = True,
    ) -> "ScheduledSparseFFNN":
        """Compile with the Theorem-1 schedule; optionally improve it with CR.

        ``M_tiles`` is the VMEM budget in tiles used as the CR objective
        (M=3 matches the kernel's single-resident-tile residency model).
        CR proposals that break the contiguous-by-output contract are unusable
        by the kernel, so the engine re-groups the CR result by output tile,
        keeping CR's improved *input-tile locality* within each group.

        With ``fuse=True`` (default) the whole net lowers to ONE flat
        cross-layer dispatch — the Pallas megakernel on TPU backends, with
        hidden states VMEM-resident across layer boundaries.
        """
        engine = Engine(
            backend=backend, activation=activation, final_activation=None,
            reorder=reorder, M_tiles=M_tiles, reorder_iters=reorder_iters,
            seed=seed, fuse=fuse,
        )
        plan = engine.compile(list(layers))
        return cls(
            layers=plan.layers, schedules=plan.schedules,
            block_ffnn=plan.block_ffnn, order=plan.order,
            activation=activation, plan=plan, engine=engine,
        )

    @property
    def fused(self) -> bool:
        """True when the compiled plan runs as one flat cross-layer dispatch."""
        return self.plan is not None and self.plan.fused

    def __call__(self, x: jnp.ndarray, interpret: Optional[bool] = None) -> jnp.ndarray:
        """Run the fused plan.  ``interpret`` forces the Pallas interpret-mode
        backend (True) or the compiled Pallas kernel (False); None keeps the
        engine's resolved backend.

        Instances constructed directly from the dataclass fields (the
        pre-engine API) have no plan; they fall back to per-layer dispatch
        with the stored schedules, exactly the old behavior."""
        if self.plan is None:
            from repro.kernels.ops import scheduled_bsr_layer

            h = x
            for k, (lay, sch) in enumerate(zip(self.layers, self.schedules)):
                act = self.activation if k < len(self.layers) - 1 else None
                h = scheduled_bsr_layer(h, lay, sch, activation=act,
                                        interpret=interpret)
            return h
        if interpret is None:
            return self.plan(x)
        backend = "interpret" if interpret else "pallas"
        return self.engine.compile(self.block_ffnn, backend=backend)(x)

    def simulated_ios(self, M_tiles: int = 3, policy: str = "min"):
        """Exact simulated tile I/Os of the current order (paper's cost model)."""
        return simulated_tile_traffic(self.block_ffnn, self.order, M_tiles, policy)
