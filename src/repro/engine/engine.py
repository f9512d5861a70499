"""The fused multi-layer sparse inference engine.

The paper's headline numbers come from executing one 2-optimal connection
schedule over the *whole* network — not from dispatching layer-by-layer.
``Engine`` is that idea as an API:

    engine = Engine(reorder=True)
    plan = engine.compile(layers)        # offline: schedule + CR + lowering
    y = plan(x)                          # online: one fused jitted program
    print(plan.io.summary())             # predicted I/O vs Theorem-1 bounds

``compile`` builds the block DAG of all layers, takes the Theorem-1
(grouped-by-output) order, optionally improves it with Connection Reordering
over the *entire* DAG (so the annealer can trade locality across layer
boundaries), re-groups the result into the kernel-compatible 2-optimal
family, validates/packs per-layer schedule arrays, and lowers everything into
a single jitted forward for the chosen backend.  Plans are cached: compiling
the same layers with the same settings returns the same plan object.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np

from repro.core.blocksparse import (
    BlockFFNN,
    BSRLayer,
    GLUBlock,
    regroup_by_output,
    schedule_arrays,
    to_block_ffnn,
)
from repro.core.bounds import theorem1_bounds
from repro.core.graph import drop_isolated
from repro.core.iosim import simulate
from repro.core.reorder import connection_reordering
from repro.kernels.ops import (
    compile_flat_schedule,
    compile_schedule,
    pack_attrs,
    resolve_weight_dtype,
)
from repro.models.common import ACTIVATIONS as _MODEL_ACTIVATIONS
from repro.obs.trace import NULL_TRACER

from .backends import (
    make_forward,
    make_fused_forward,
    make_fused_measure,
    resolve_backend,
)
from .plan import ExecutionPlan, IOReport
from .sharding import Mesh, ShardedExecutionPlan, build_sharded_plan

# name -> activation callable (None = identity / linear output); extends the
# shared model registry rather than duplicating it.
ACTIVATIONS: Dict[Optional[str], Optional[Callable]] = {
    None: None,
    "none": None,
    "linear": None,
    "tanh": jax.numpy.tanh,
    "sigmoid": jax.nn.sigmoid,
    **_MODEL_ACTIVATIONS,
}


def _resolve_activation(act) -> Optional[Callable]:
    if act is None or callable(act):
        return act
    try:
        return ACTIVATIONS[act]
    except KeyError:
        raise ValueError(
            f"unknown activation {act!r}; pick from "
            f"{sorted(k for k in ACTIVATIONS if isinstance(k, str))} "
            "or pass a callable"
        ) from None


@dataclasses.dataclass
class Engine:
    """Compile-once/run-many driver for scheduled block-sparse inference.

    Args:
      backend: ``auto`` | ``pallas`` | ``interpret`` | ``jnp``.  ``auto``
        picks the Pallas TPU kernel on TPU hosts and the pure-``jnp``
        lowering elsewhere, so the same engine code runs (and is testable)
        on any machine.
      activation: epilogue fused into every layer but the last (name or
        callable or None); of a gated block (``GLUBlock``), the gate's.  A
        list/tuple gives each *hidden* layer of a chain its own
        epilogue (length must be ``len(layers) - 1``); the megakernel fuses
        only when all hidden epilogues compare equal (``functools.partial``
        instances are compared structurally), otherwise the plan falls back
        to layered dispatch and records why in ``plan.fallback_reason``.
      final_activation: epilogue of the last layer (default linear).
      reorder: run Connection Reordering over the whole block DAG.
      M_tiles: VMEM budget (in tiles) used as the CR objective and for the
        plan's I/O report; 3 matches the kernel's single-resident-tile model.
      reorder_iters / seed: annealing budget and RNG seed.
      max_move_span: cap on how far an annealer proposal may carry any
        connection (None = the paper's unbounded nearest-dependency scan).
        On 10k+-block DAGs a cap keeps the incremental delta evaluator's
        changed window small; schedule-affecting, so it is part of the plan
        cache key.
      policy: eviction policy for the simulated I/O report.
      fuse: lower the whole net into ONE flat cross-layer dispatch (the
        Pallas megakernel on pallas/interpret; one segment pass on jnp) with
        the hidden state VMEM-resident across layer boundaries.  Nets whose
        tile shapes cannot be flattened (non-uniform block sizes) silently
        fall back to per-layer dispatch; ``fuse=False`` forces that layered
        path.
      gate: runtime tile-occupancy gating.  The compiled forward computes a
        per-batch nonzero-tile bitmap over each activation and skips the
        weight blocks whose input tile is dead for the whole batch — the
        jnp lowering masks its gather/einsum, the megakernel predicates the
        matching grid steps (no-op steps still advance the double-buffered
        weight stream).  Bit-exact with the ungated forward; gated plans
        additionally expose :meth:`ExecutionPlan.measure_dynamic`.
      weight_dtype: storage dtype of the streamed weight blocks —
        ``"f32"`` (default, bit-exact), ``"bf16"`` or ``"fp8"``.  Narrow
        modes quantize each scheduled block once at compile time with one
        f32 dequant scale per block and fuse dequant (``block * scale``)
        into every backend right before the dot, cutting the dominant
        weight-stream I/O 2x/4x at the identical schedule.  Quantized
        plans are not bit-exact vs f32 (bf16 agrees within ~1e-2 relative,
        fp8 within ~1e-1 — see ``docs/engine.md``), but all backends of
        one quantized plan dequantize to identical f32 values, so
        cross-backend agreement and ``safe_twin`` degradation behave
        exactly as in f32.  ``"fp8"`` raises a clear ``ValueError`` at
        compile time when ``ml_dtypes`` lacks ``float8_e4m3fn``.
    """

    backend: str = "auto"
    activation: Union[str, Callable, None] = "relu"
    final_activation: Union[str, Callable, None] = None
    reorder: bool = False
    M_tiles: int = 3
    reorder_iters: int = 2000
    seed: int = 0
    max_move_span: Optional[int] = None
    policy: str = "min"
    fuse: bool = True
    gate: bool = False
    weight_dtype: str = "f32"
    jit: bool = True
    # a repro.obs.Tracer recording compile-phase spans (Theorem-1 schedule,
    # CR/annealing, packing, backend lowering, I/O simulation).  Not part
    # of _plan_key — tracing never changes what gets compiled or cached.
    tracer: Optional[object] = dataclasses.field(default=None, repr=False,
                                                 compare=False)
    _cache: Dict[Tuple, Union[ExecutionPlan, ShardedExecutionPlan]] = \
        dataclasses.field(default_factory=dict, repr=False)

    @property
    def _tr(self):
        tr = self.tracer
        return tr if tr is not None else NULL_TRACER

    # ------------------------------------------------------------------ #
    def compile(
        self,
        net: Union[BlockFFNN, GLUBlock, Sequence[BSRLayer]],
        backend: Optional[str] = None,
        mesh: Optional[Mesh] = None,
    ) -> Union[ExecutionPlan, ShardedExecutionPlan]:
        """Lower a whole network into one cached plan.

        Without ``mesh`` this is the single-device path: one whole-network
        :class:`ExecutionPlan`.  With ``mesh=Mesh(model, data)`` the block
        DAG is partitioned tile-parallel over ``model`` and the batch over
        ``data`` into a :class:`ShardedExecutionPlan` — each shard's
        schedule is built by the same ``_build`` the unsharded path uses
        (Theorem-1 order + independent Connection Reordering per shard),
        and ``Mesh(1, 1)`` shares the unsharded plan's forward outright.
        """
        bffnn = net if isinstance(net, BlockFFNN) else to_block_ffnn(net)
        backend = resolve_backend(backend or self.backend)
        key = self._plan_key(bffnn, backend) + self._mesh_key(mesh)
        plan = self._cache.get(key)
        if plan is not None:
            return plan
        if mesh is None:
            plan = self._build(bffnn, backend)
        else:
            plan = build_sharded_plan(self, bffnn, backend, mesh)
        self._cache[key] = plan
        return plan

    def compile_with_order(
        self,
        net: Union[BlockFFNN, GLUBlock, Sequence[BSRLayer]],
        order: np.ndarray,
        backend: Optional[str] = None,
        io: Optional[IOReport] = None,
    ) -> ExecutionPlan:
        """Lower a network onto a *precomputed* whole-DAG connection order.

        This is the warm-start path of the plan store
        (``repro.serving.plancache``): the expensive offline steps —
        Theorem-1 grouping and Connection Reordering — are skipped entirely
        (``plan.annealer_iters == 0``); only validation, packing, and
        backend lowering run.  Passing a stored ``io`` report also skips the
        I/O re-simulation.  The rebuild is deterministic, so the resulting
        plan is bit-identical to the cold compile the order came from.
        """
        bffnn = net if isinstance(net, BlockFFNN) else to_block_ffnn(net)
        backend = resolve_backend(backend or self.backend)
        return self._build(bffnn, backend, order=np.asarray(order), io=io)

    def compile_sharded_with_orders(
        self,
        net: Union[BlockFFNN, Sequence[BSRLayer]],
        mesh: Mesh,
        orders: Sequence[np.ndarray],
        backend: Optional[str] = None,
        ios: Optional[Sequence[IOReport]] = None,
    ) -> ShardedExecutionPlan:
        """Sharded analogue of :meth:`compile_with_order`: rebuild a
        sharded plan from one *stored* per-shard connection order each —
        zero annealer iterations, deterministic, bit-identical to the cold
        compile the orders came from (the plan store's warm path)."""
        bffnn = net if isinstance(net, BlockFFNN) else to_block_ffnn(net)
        backend = resolve_backend(backend or self.backend)
        return build_sharded_plan(self, bffnn, backend, mesh,
                                  orders=list(orders), ios=ios)

    @staticmethod
    def _mesh_key(mesh: Optional[Mesh]) -> Tuple:
        return ("mesh", None) if mesh is None \
            else ("mesh", mesh.model, mesh.data)

    @staticmethod
    def _act_key(act):
        # plans (hence their activations) stay strongly referenced by the
        # cache, so object ids cannot be recycled while an entry is alive.
        if isinstance(act, (str, type(None))):
            return act
        if isinstance(act, (list, tuple)):
            return tuple(Engine._act_key(a) for a in act)
        if isinstance(act, functools.partial):
            try:
                kw = tuple(sorted(act.keywords.items()))
                key = ("partial", Engine._act_key(act.func), act.args, kw)
                hash(key)
                return key
            except TypeError:
                return id(act)
        return id(act)

    def _plan_key(self, bffnn: BlockFFNN, backend: str) -> Tuple:
        return (
            tuple(id(l) for l in bffnn.layers), backend,
            self._act_key(self.activation),
            self._act_key(self.final_activation),
            self.reorder, self.M_tiles, self.reorder_iters, self.seed,
            self.max_move_span, self.policy, self.fuse, self.gate,
            resolve_weight_dtype(self.weight_dtype), self.jit, bffnn.glu,
        )

    # ------------------------------------------------------------------ #
    def _build(self, bffnn: BlockFFNN, backend: str,
               order: Optional[np.ndarray] = None,
               io: Optional[IOReport] = None) -> ExecutionPlan:
        t0 = time.perf_counter()
        tr = self._tr
        layers = bffnn.layers
        # resolve up front: an unavailable fp8 fails here with a clear
        # ValueError, never a deep kernel TypeError
        wdt = resolve_weight_dtype(self.weight_dtype)
        glu = bffnn.glu
        if glu:
            self._check_glu()
        annealer_iters = 0
        if order is None:
            order = self.schedule_order(bffnn)
            annealer_iters = self.reorder_iters if self.reorder else 0
        with tr.span("compile.pack", layers=len(layers)) as sp:
            schedules = []
            for k in range(len(layers)):
                # a gated block's up steps pair with its gate's, whatever
                # order Connection Reordering left them in
                perm, _, _, _, _ = schedule_arrays(bffnn, order,
                                                   0 if glu and k == 1 else k)
                schedules.append(compile_schedule(layers[k], perm,
                                                  weight_dtype=wdt))
            for key, value in pack_attrs(schedules, glu).items():
                sp[key] = value

        if glu:   # the gate's activation; the up projection has none
            hidden = [_resolve_activation(self.activation), None]
        elif isinstance(self.activation, (list, tuple)):
            if len(self.activation) != len(layers) - 1:
                raise ValueError(
                    f"per-layer activation sequence has {len(self.activation)} "
                    f"entries but the net has {len(layers) - 1} hidden layers"
                )
            hidden = [_resolve_activation(a) for a in self.activation]
        else:
            hidden = [_resolve_activation(self.activation)] * (len(layers) - 1)
        fact = _resolve_activation(self.final_activation)
        activations: List[Optional[Callable]] = hidden + [fact]

        with tr.span("compile.lower", backend=backend,
                     gate=self.gate) as sp:
            flat = None
            fallback_reason: Optional[str] = None
            if self.fuse:
                try:
                    flat = compile_flat_schedule(layers, schedules, glu=glu)
                except ValueError as e:
                    if glu:
                        raise ValueError(f"a gated (GLU) block runs only "
                                         f"fused, and {e}") from None
                    flat = None  # non-uniform tiles: per-layer fallback
                    fallback_reason = str(e)
            measure = None
            if flat is not None:
                try:
                    forward = make_fused_forward(layers, flat, activations,
                                                 backend, jit=self.jit,
                                                 gate=self.gate)
                    if self.gate:
                        measure = make_fused_measure(layers, flat,
                                                     activations, backend,
                                                     jit=self.jit)
                except ValueError as e:
                    # e.g. heterogeneous hidden epilogues: the megakernel
                    # fuses exactly one — record why instead of failing
                    # silently.
                    if glu:
                        raise
                    flat = None
                    fallback_reason = str(e)
            if flat is None:
                forward = make_forward(layers, schedules, activations,
                                       backend, jit=self.jit, gate=self.gate)
                if self.gate and backend != "jnp":
                    note = ("occupancy gating inactive on the layered "
                            "pallas path")
                    fallback_reason = f"{fallback_reason}; {note}" \
                        if fallback_reason else note
            sp["fused"] = flat is not None
        if io is None:
            with tr.span("compile.io_report", policy=self.policy,
                         M_tiles=self.M_tiles):
                io = self.io_report(bffnn, order, schedules,
                                    fused=flat is not None)
        return ExecutionPlan(
            layers=list(layers),
            schedules=schedules,
            activations=activations,
            backend=backend,
            order=order,
            block_ffnn=bffnn,
            io=io,
            flat=flat,
            gate=self.gate,
            fallback_reason=fallback_reason,
            _forward=forward,
            _measure=measure,
            compile_s=time.perf_counter() - t0,
            annealer_iters=annealer_iters,
        )

    def _check_glu(self) -> None:
        """A gated (GLU) block runs only on the fused paths: refuse, by
        name, what would run something else."""
        if not self.fuse:
            raise ValueError("a gated (GLU) block has no layered per-layer "
                             "path; compile it with fuse=True")
        if self.gate:
            raise ValueError("occupancy gating (gate=True) does not handle "
                             "a gated (GLU) block")
        if isinstance(self.activation, (list, tuple)):
            raise ValueError("a gated (GLU) block takes one activation, "
                             "the gate's")

    def schedule_order(self, bffnn: BlockFFNN) -> np.ndarray:
        """Whole-DAG connection order: Theorem-1 grouping, then optional CR
        re-grouped back into the kernel-compatible 2-optimal family."""
        tr = self._tr
        with tr.span("compile.theorem1") as sp:
            order = bffnn.net.theorem1_order()
            sp["connections"] = int(len(order))
        if self.reorder:
            with tr.span("compile.reorder", iters=self.reorder_iters,
                         M_tiles=self.M_tiles,
                         max_move_span=self.max_move_span):
                res = connection_reordering(
                    bffnn.net, order, M=self.M_tiles, policy=self.policy,
                    T=self.reorder_iters, seed=self.seed,
                    max_move_span=self.max_move_span,
                )
                order = regroup_by_output(bffnn.net, res.order)
        return order

    def io_report(self, bffnn: BlockFFNN, order: np.ndarray,
                  schedules: Optional[List] = None,
                  fused: bool = False) -> IOReport:
        """Exact simulated tile traffic of ``order`` next to Theorem 1.

        Theorem 1 assumes a connected FFNN, so isolated tiles (dead blocks
        left by pruning) are dropped from the analysis — connection indices
        are unaffected.  With per-layer ``schedules`` the report carries the
        per-dtype byte traffic of the weight stream (blocks + dequant
        scales, at the storage dtype); with ``fused=True`` it additionally
        carries the layered-dispatch traffic (each boundary round-trips the
        hidden state through HBM) so the fused plan's cross-layer savings
        are visible next to the Theorem-1 bounds."""
        net = drop_isolated(bffnn.net)
        sim = simulate(net, order, self.M_tiles, self.policy)
        layered_reads = layered_writes = 0
        hidden_tiles = hidden_bytes = 0
        weight_dtype = "f32"
        weight_bytes = scale_bytes = act_bytes = 0
        if schedules is not None:
            weight_dtype = schedules[0].weight_dtype
            weight_bytes = sum(s.weight_bytes for s in schedules)
            scale_bytes = sum(s.scale_bytes for s in schedules)
            # f32 activations crossing HBM per batch row: input + output
            # always; each layer boundary round-trips the hidden state only
            # on the layered path (the fused plan keeps it VMEM-resident)
            act_bytes = 4 * (bffnn.layers[0].n_in + bffnn.layers[-1].n_out)
            if not fused:
                act_bytes += sum(2 * lay.n_out * 4
                                 for lay in bffnn.layers[:-1])
        if schedules is not None and fused:
            layered_reads = sum(s.sim_reads for s in schedules)
            layered_writes = sum(s.sim_writes for s in schedules)
            for lay in bffnn.layers[:-1]:
                hidden_tiles += lay.grid_out
                # one write out plus one read back avoided per feature
                hidden_bytes += 2 * lay.n_out * 4
        return IOReport(
            simulated=sim,
            bounds=theorem1_bounds(net),
            M_tiles=self.M_tiles,
            policy=self.policy,
            layered_reads=layered_reads,
            layered_writes=layered_writes,
            hidden_tiles_kept=hidden_tiles,
            hidden_bytes_kept_per_row=hidden_bytes,
            weight_dtype=weight_dtype,
            weight_bytes_streamed=weight_bytes,
            scale_bytes_streamed=scale_bytes,
            activation_bytes_per_row=act_bytes,
        )
