"""Sharded execution plans: the block DAG partitioned across a device mesh.

The paper's I/O model is *per device* — each accelerator has its own small
fast memory — so the way to scale past one device is not a bigger schedule
but **one independent Theorem-1 schedule per shard**:

    from repro.engine import Engine, Mesh

    plan = Engine().compile(layers, mesh=Mesh(model=4, data=2))
    y = plan(x)
    print(plan.io_report().summary())   # per-shard traffic + imbalance

``Mesh(model, data)`` partitions the block-column DAG **tile-parallel** over
``model`` (each shard owns an equal-count, load-balanced subset of every
layer's output tiles — ``core.graph.partition_columns_balanced``) and
**batch-parallel** over ``data``.  Each model shard gets its own shard DAG:
its connections are every weight block targeting an owned tile; tiles it
reads but does not produce (inputs and remote boundary tiles that arrive by
all-gather) are the shard DAG's *inputs*, and every owned tile is an
*output* (it must reach HBM to be gathered).  The shard DAG is a perfectly
ordinary paper-FFNN, so the whole single-device machinery applies per shard
unchanged: Theorem-1 grouping, Connection Reordering (embarrassingly
parallel — each shard anneals independently), schedule packing, exact I/O
simulation and Theorem-1 bounds.  EIE distributes a sparse network over
processing elements exactly this way (per-PE queues + activation
broadcast); SparseNN's observation that *load balance*, not total traffic,
governs end-to-end throughput is why :class:`ShardedIOReport` exposes a
load-imbalance ratio next to the aggregate.

Execution lowers through ``compat.shard_map`` when the host has a device
per mesh slot, and through a sequential jnp loop over the shard index
otherwise — the same segment arithmetic either way, so both lowerings (and
the unsharded plan) agree bitwise under the default (un-annealed) schedule.
A 1-shard ``model`` axis does not build any of this: its per-device body is
the unsharded plan's own forward, which makes the single-device path the
1×1-mesh special case rather than a parallel code path.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import host_mesh
from repro.core.blocksparse import BlockFFNN, BSRLayer
from repro.core.graph import FFNN, partition_columns_balanced

from .backends import ShardedSegment, make_sharded_forward
from .plan import ExecutionPlan, IOReport


@dataclasses.dataclass(frozen=True)
class Mesh:
    """Logical device mesh for a sharded plan: tile-parallel ``model`` axis
    × batch-parallel ``data`` axis.

    This is a *spec*, not a device object: compiling against ``Mesh(4, 2)``
    on a 1-device host is legal — the plan lowers to the sequential shard
    loop instead of ``shard_map`` and computes the identical function (the
    CI multi-device lane runs the same tests under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to cover the
    collective lowering).
    """

    model: int = 1
    data: int = 1

    def __post_init__(self):
        if self.model < 1 or self.data < 1:
            raise ValueError(f"mesh axes must be >= 1, got {self}")

    @property
    def size(self) -> int:
        return self.model * self.data

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.model, self.data)

    @classmethod
    def parse(cls, spec: str) -> "Mesh":
        """Parse a CLI mesh spec: ``"4x2"`` = 4 model shards × 2 data
        replicas; ``"4"`` means ``4x1``.  One parser (and one error
        message) for every mesh-taking command line."""
        model, _, data = spec.strip().lower().partition("x")
        try:
            return cls(model=int(model), data=int(data) if data else 1)
        except ValueError:
            raise ValueError(
                f"bad mesh spec {spec!r}: expected MODELxDATA, e.g. 4x2"
            ) from None

    def jax_mesh(self):
        """The physical ``(data, model)`` mesh, or None to use the loop
        fallback (single-slot mesh, or fewer host devices than slots)."""
        if self.size <= 1 or jax.device_count() < self.size:
            return None
        return host_mesh((self.data, self.model), ("data", "model"))


@dataclasses.dataclass
class ShardSpec:
    """One model shard's view of the network.

    ``layers[k]`` keeps the full layer-``k`` input width (the shard reads
    the gathered activation) but only the owned output tiles, re-indexed to
    local column ids.  ``owned[k][p]`` is the global output tile behind
    local tile ``p`` — the reassembly permutation of the all-gather.
    ``bffnn`` is the shard DAG described in the module docstring.
    """

    bffnn: BlockFFNN
    owned: List[np.ndarray]


def partition_model(bffnn: BlockFFNN, model: int) -> List[ShardSpec]:
    """Partition the block-column DAG into ``model`` balanced shards.

    Every layer's output tiles are split into equal-count groups (a
    ``shard_map`` shape requirement) balancing per-shard nonzero-block load;
    raises ``ValueError`` when a layer's tile grid is not divisible by
    ``model``.  ``model=1`` returns the whole network as the single shard —
    the unsharded compile *is* this special case.
    """
    layers = bffnn.layers
    if model == 1:
        return [ShardSpec(bffnn=bffnn,
                          owned=[np.arange(l.grid_out) for l in layers])]

    offsets = [0, layers[0].grid_in]
    for lay in layers:
        offsets.append(offsets[-1] + lay.grid_out)
    n_tiles = offsets[-1]

    assigns = []
    for k, lay in enumerate(layers):
        if lay.grid_out % model:
            raise ValueError(
                f"layer {k} has {lay.grid_out} output tiles, not divisible "
                f"by the model axis ({model}); pick a mesh whose model size "
                "divides every layer's tile grid"
            )
        loads = np.bincount(lay.cols, minlength=lay.grid_out)
        assigns.append(partition_columns_balanced(loads, model))

    shards = []
    for s in range(model):
        owned_s: List[np.ndarray] = []
        shard_layers: List[BSRLayer] = []
        src_l, dst_l, lay_l, blk_l = [], [], [], []
        owned_mask = np.zeros(n_tiles, dtype=bool)
        for k, lay in enumerate(layers):
            owned = np.flatnonzero(assigns[k] == s)
            owned_s.append(owned)
            owned_mask[offsets[k + 1] + owned] = True
            local = np.full(lay.grid_out, -1, dtype=np.int64)
            local[owned] = np.arange(len(owned))
            sel = np.flatnonzero(local[lay.cols] >= 0)
            bias = np.ascontiguousarray(
                lay.bias.reshape(lay.grid_out, lay.block_n)[owned]
            ).reshape(-1)
            shard_layers.append(BSRLayer(
                n_in=lay.n_in,
                n_out=len(owned) * lay.block_n,
                block_m=lay.block_m,
                block_n=lay.block_n,
                rows=lay.rows[sel].astype(np.int32),
                cols=local[lay.cols[sel]].astype(np.int32),
                blocks=lay.blocks[sel],
                bias=bias.astype(np.float32),
            ))
            src_l.append(lay.rows[sel].astype(np.int64) + offsets[k])
            dst_l.append(lay.cols[sel].astype(np.int64) + offsets[k + 1])
            lay_l.append(np.full(len(sel), k, dtype=np.int32))
            blk_l.append(np.arange(len(sel), dtype=np.int64))
        src = np.concatenate(src_l)
        dst = np.concatenate(dst_l)
        # outputs = owned tiles this shard actually *produces* (the gather
        # reads them back from HBM).  Owned tiles with no incoming block are
        # bias-patched dead code — dropped from the I/O analysis exactly
        # like the unsharded path drops them (see ``drop_isolated``).
        produced = np.zeros(n_tiles, dtype=bool)
        produced[dst] = True
        net = FFNN(
            n_neurons=n_tiles, src=src, dst=dst,
            weight=np.ones(len(src), dtype=np.float32),
            is_input=~owned_mask,     # inputs + tiles arriving by all-gather
            is_output=owned_mask & produced,
            bias=np.zeros(n_tiles, dtype=np.float32),
        )
        shards.append(ShardSpec(
            bffnn=BlockFFNN(layers=shard_layers, net=net,
                            conn_layer=np.concatenate(lay_l),
                            conn_block=np.concatenate(blk_l)),
            owned=owned_s,
        ))
    return shards


# --------------------------------------------------------------------------- #
# aggregate I/O report
# --------------------------------------------------------------------------- #

@dataclasses.dataclass(frozen=True)
class ShardedIOReport:
    """Per-shard Theorem-1 I/O reports + the cross-shard aggregates.

    Each entry of ``per_shard`` is the exact simulated tile traffic of that
    shard's independent schedule next to *that shard DAG's* Theorem-1
    bounds (the model is per-device, so the bounds are too).  The aggregate
    is the sum; ``load_imbalance`` = max shard traffic / mean shard traffic
    (1.0 = perfectly balanced) — the number that actually bounds end-to-end
    throughput, since every shard's gather waits for the slowest shard.
    ``data`` replicas stream the same tiles for different batch rows, so
    per-shard counts are per data replica.
    """

    per_shard: Tuple[IOReport, ...]
    model: int = 1
    data: int = 1

    @property
    def reads(self) -> int:
        return sum(r.simulated.reads for r in self.per_shard)

    @property
    def writes(self) -> int:
        return sum(r.simulated.writes for r in self.per_shard)

    @property
    def total(self) -> int:
        return self.reads + self.writes

    @property
    def within_bounds(self) -> bool:
        return all(r.within_bounds for r in self.per_shard)

    @property
    def load_imbalance(self) -> float:
        totals = [r.simulated.total for r in self.per_shard]
        mean = sum(totals) / max(1, len(totals))
        if mean == 0:
            return 1.0
        return max(totals) / mean

    @property
    def max_shard_total(self) -> int:
        return max(r.simulated.total for r in self.per_shard)

    @property
    def weight_dtype(self) -> str:
        return self.per_shard[0].weight_dtype if self.per_shard else "f32"

    @property
    def weight_bytes_streamed(self) -> int:
        return sum(r.weight_bytes_streamed for r in self.per_shard)

    @property
    def scale_bytes_streamed(self) -> int:
        return sum(r.scale_bytes_streamed for r in self.per_shard)

    @property
    def weight_stream_bytes(self) -> int:
        """Aggregate weight-stream bytes (blocks + scales) per data replica."""
        return sum(r.weight_stream_bytes for r in self.per_shard)

    def summary(self) -> str:
        return (f"sharded tile I/O {self.total} over {self.model} model "
                f"shard(s) x {self.data} data (max shard "
                f"{self.max_shard_total}, imbalance "
                f"x{self.load_imbalance:.2f}, "
                f"{'within' if self.within_bounds else 'OUTSIDE'} per-shard "
                "Theorem-1 bounds)")

    def to_dict(self) -> dict:
        return {"model": self.model, "data": self.data,
                "per_shard": [r.to_dict() for r in self.per_shard]}

    @classmethod
    def from_dict(cls, d: dict) -> "ShardedIOReport":
        return cls(per_shard=tuple(IOReport.from_dict(r)
                                   for r in d["per_shard"]),
                   model=d["model"], data=d["data"])


# --------------------------------------------------------------------------- #
# the sharded plan
# --------------------------------------------------------------------------- #

def _shard_not_runnable(*_a, **_k):
    raise RuntimeError(
        "a model-parallel shard plan is not standalone-runnable — its "
        "layers read the all-gathered activation; call the "
        "ShardedExecutionPlan instead"
    )


@dataclasses.dataclass
class ShardedExecutionPlan:
    """A compiled plan partitioned over a ``Mesh``.  Call it on inputs.

    ``shards[s]`` is a full :class:`ExecutionPlan` built by the same
    single-device builder (``Engine._build``) on shard ``s``'s DAG — its
    ``order``, ``schedules``, ``flat`` arrays and ``io`` report are the
    per-shard artifacts the plan store persists.  The collective forward
    consumes those per-shard schedule arrays directly.
    """

    mesh: Mesh
    shards: List[ExecutionPlan]
    owned: List[List[np.ndarray]]   # [shard][layer] global output-tile ids
    backend: str
    gate: bool = False              # runtime tile-occupancy gating
    block_ffnn: BlockFFNN = None    # the unpartitioned network
    _forward: Callable = dataclasses.field(repr=False, default=None)
    _rebuild: Callable = dataclasses.field(repr=False, default=None)
    calls: int = dataclasses.field(default=0, compare=False)
    compile_s: float = 0.0

    @property
    def n_in(self) -> int:
        return self.shards[0].n_in

    @property
    def n_out(self) -> int:
        return sum(s.layers[-1].n_out for s in self.shards)

    @property
    def n_layers(self) -> int:
        return len(self.shards[0].layers)

    @property
    def dtype(self) -> np.dtype:
        """Input dtype the collective forward was traced with (the sharded
        analogue of :attr:`ExecutionPlan.dtype`)."""
        return self.shards[0].dtype

    @property
    def weight_dtype(self) -> str:
        """Storage dtype of the streamed weight blocks (all shards agree)."""
        return self.shards[0].weight_dtype

    @property
    def annealer_iters(self) -> int:
        return sum(s.annealer_iters for s in self.shards)

    @property
    def io(self) -> ShardedIOReport:
        return self.io_report()

    def io_report(self) -> ShardedIOReport:
        """Aggregate per-shard traffic + load-imbalance ratio."""
        return ShardedIOReport(per_shard=tuple(s.io for s in self.shards),
                               model=self.mesh.model, data=self.mesh.data)

    def __call__(self, x) -> jnp.ndarray:
        """Run inference.  ``x`` is ``[n_in]`` or batched ``[B, n_in]``;
        the batch is padded up to a multiple of the data-axis size and
        sliced back (zero rows never perturb real rows)."""
        x = jnp.asarray(x)
        single = x.ndim == 1
        if single:
            x = x[None, :]
        if x.ndim != 2 or x.shape[1] != self.n_in:
            raise ValueError(
                f"expected input [B, {self.n_in}] or [{self.n_in}], "
                f"got {tuple(x.shape)}"
            )
        B = x.shape[0]
        pad = (-B) % self.mesh.data
        if pad:
            x = jnp.pad(x, ((0, pad), (0, 0)))
        if self.gate and self.mesh.model > 1:
            # padding happened outside the collective trace, so the gated
            # forward takes the real-row mask explicitly (occupancy is
            # computed over real rows only)
            valid = jnp.arange(x.shape[0]) < B
            y = self._forward(x, valid)[:B]
        else:
            y = self._forward(x)[:B]
        self.calls += 1
        return y[0] if single else y

    def with_fresh_forward(self, jit: bool = True) -> "ShardedExecutionPlan":
        """A copy with a newly lowered collective forward (call count 0);
        the per-shard schedule substrate is shared by reference — this is
        the sharded analogue of :meth:`ExecutionPlan.with_fresh_forward`
        that ``repro.serving.bucketing`` fans over batch buckets."""
        return dataclasses.replace(self, _forward=self._rebuild(jit), calls=0)

    def safe_twin(self, jit: bool = True) -> "ShardedExecutionPlan":
        """The sharded analogue of :meth:`ExecutionPlan.safe_twin`: the
        same per-shard schedules lowered through the jnp collective path
        with gating off — bit-exact (the model>1 collective already lowers
        segments through jnp; the gated/ungated forwards agree bitwise per
        PR 6), just without the fast-path machinery.  Used by the serving
        runtime's circuit breaker."""
        rebuild = self._rebuild
        return dataclasses.replace(
            self, backend="jnp", gate=False,
            _forward=rebuild(jit, safe=True),
            _rebuild=lambda j=True, safe=True: rebuild(j, safe=True),
            calls=0)

    def describe(self) -> str:
        shapes = " -> ".join(
            [str(self.n_in)]
            + [str(sum(s.layers[k].n_out for s in self.shards))
               for k in range(self.n_layers)])
        nnz = sum(l.nnz_blocks for s in self.shards for l in s.layers)
        # with >1 model shard the collective forward lowers per-shard
        # segments through the jnp path regardless of backend — say so
        # instead of letting the backend name imply the megakernel ran
        mode = self.backend if len(self.shards) == 1 \
            else f"{self.backend}/jnp-collective"
        if self.gate:
            mode += "+gated"
        return (f"ShardedExecutionPlan[{mode}] "
                f"mesh(model={self.mesh.model}, data={self.mesh.data}) "
                f"{shapes} ({self.n_layers} layers, {nnz} nonzero blocks); "
                + self.io_report().summary()
                + f"; compiled in {self.compile_s:.2f}s "
                  f"({self.annealer_iters} annealer iters), "
                  f"{self.calls} calls")

    def artifact_arrays(self) -> dict:
        """Persistable arrays: the partition assignment per layer plus each
        shard's own artifact (order + flat-schedule verification arrays),
        prefixed ``s{i}_`` — the plan-store entry for a sharded plan."""
        out = {}
        for k in range(self.n_layers):
            grid = sum(len(owned_s[k]) for owned_s in self.owned)
            assign = np.zeros(grid, dtype=np.int32)
            for s, owned_s in enumerate(self.owned):
                assign[owned_s[k]] = s
            out[f"assign_l{k}"] = assign
        for s, plan in enumerate(self.shards):
            for name, arr in plan.artifact_arrays().items():
                out[f"s{s}_{name}"] = arr
        return out


# --------------------------------------------------------------------------- #
# builder (called by Engine.compile — one shard through Engine._build each)
# --------------------------------------------------------------------------- #

def _sharded_segments(
    specs: Sequence[ShardSpec],
    shard_plans: Sequence[ExecutionPlan],
) -> List[ShardedSegment]:
    """Stack every shard's per-layer schedule arrays into uniform-shape
    ``ShardedSegment``s (padding routed to the sink segment)."""
    model = len(specs)
    n_layers = len(specs[0].bffnn.layers)
    segments = []
    for k in range(n_layers):
        full_lay = specs[0].bffnn.layers[k]
        tps = len(specs[0].owned[k])
        bm, bn = full_lay.block_m, full_lay.block_n
        scheds = [np.asarray(p.schedules[k].rows) for p in shard_plans]
        n_max = max(len(r) for r in scheds)
        rows = np.zeros((model, n_max), dtype=np.int32)
        cols = np.full((model, n_max), tps, dtype=np.int32)   # sink segment
        # keep the storage dtype: a quantized plan's shards stream the same
        # narrow blocks the unsharded plan does (pad steps are zero blocks
        # with scale 1.0, so they dequantize to exact zero)
        store_dtype = np.asarray(shard_plans[0].schedules[k].blocks).dtype
        quant = shard_plans[0].schedules[k].scales is not None
        blocks = np.zeros((model, n_max, bm, bn), dtype=store_dtype)
        scales = np.ones((model, n_max), dtype=np.float32) if quant else None
        bias = np.zeros((model, tps * bn), dtype=np.float32)
        grid_out_full = sum(len(sp.owned[k]) for sp in specs)
        perm = np.zeros(grid_out_full, dtype=np.int32)
        for s, (sp, plan) in enumerate(zip(specs, shard_plans)):
            sch = plan.schedules[k]
            n = len(np.asarray(sch.rows))
            rows[s, :n] = np.asarray(sch.rows)
            cols[s, :n] = np.asarray(sch.cols)
            blocks[s, :n] = np.asarray(sch.blocks)
            if quant:
                scales[s, :n] = np.asarray(sch.scales, dtype=np.float32)
            bias[s] = np.asarray(sp.bffnn.layers[k].bias, dtype=np.float32)
            perm[sp.owned[k]] = s * tps + np.arange(tps)
        segments.append(ShardedSegment(
            rows=rows, cols=cols, blocks=blocks, bias=bias, perm=perm,
            grid_in=full_lay.grid_in, tps=tps, block_m=bm, block_n=bn,
            activation=shard_plans[0].activations[k],
            scales=scales,
        ))
    return segments


def build_sharded_plan(
    engine,                      # repro.engine.Engine (duck-typed)
    bffnn: BlockFFNN,
    backend: str,
    mesh: Mesh,
    orders: Optional[Sequence[np.ndarray]] = None,
    ios: Optional[Sequence[IOReport]] = None,
) -> ShardedExecutionPlan:
    """Partition, build one per-shard plan each through ``engine._build``
    (the exact single-device builder: Theorem-1 order + independent CR +
    schedule packing + I/O report), then lower the collective forward.

    ``orders``/``ios`` are the plan-store warm path: one stored connection
    order (and optionally I/O report) per shard, skipping the annealing and
    re-simulation exactly like ``Engine.compile_with_order`` does.
    """
    if bffnn.glu:
        raise ValueError("sharded plans do not handle a gated (GLU) block")
    t0 = time.perf_counter()
    gate = bool(getattr(engine, "gate", False))
    specs = partition_model(bffnn, mesh.model)
    if orders is not None and len(orders) != len(specs):
        raise ValueError(
            f"got {len(orders)} stored orders for {len(specs)} shards")
    shard_plans = []
    for s, spec in enumerate(specs):
        if orders is not None:
            plan = engine._build(spec.bffnn, backend,
                                 order=np.asarray(orders[s]),
                                 io=None if ios is None else ios[s])
        else:
            plan = engine._build(spec.bffnn, backend)
        if mesh.model > 1:
            # shard layers read the gathered activation; the standalone
            # forward _build lowered would mis-chain them
            plan = dataclasses.replace(plan, _forward=_shard_not_runnable)
        shard_plans.append(plan)

    segments = _sharded_segments(specs, shard_plans) if mesh.model > 1 \
        else []

    def rebuild(jit: bool = True, safe: bool = False) -> Callable:
        # safe=True lowers the safe-mode twin: jnp per-shard body, gate
        # off — the degraded path the serving circuit breaker swaps to
        jm = mesh.jax_mesh()
        base = None
        if mesh.model == 1:
            shard0 = shard_plans[0].safe_twin(jit=False) if safe \
                else shard_plans[0]
            if jm is None:
                return shard0.with_fresh_forward(jit=jit)._forward
            base = shard0.with_fresh_forward(jit=False)._forward
        return make_sharded_forward(segments, mesh.model, mesh.data, jm,
                                    base_forward=base, jit=jit,
                                    gate=False if safe else gate)

    if mesh.model == 1 and mesh.jax_mesh() is None:
        # the 1×1 (or device-starved model=1) case IS the unsharded path:
        # share the very forward the single-device builder produced
        forward = shard_plans[0]._forward
    else:
        forward = rebuild(engine.jit)

    return ShardedExecutionPlan(
        mesh=mesh,
        shards=shard_plans,
        owned=[spec.owned for spec in specs],
        backend=backend,
        gate=gate,
        block_ffnn=bffnn,
        _forward=forward,
        _rebuild=rebuild,
        compile_s=time.perf_counter() - t0,
    )
