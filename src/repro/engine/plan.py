"""Execution plans: the compile-once/run-many artifact of ``Engine.compile``.

A plan owns everything derived offline from a ``BlockFFNN``:

  * the whole-network connection order (Theorem-1 grouped, optionally
    Connection-Reordered) and its per-layer kernel schedules;
  * the fused per-layer activation epilogues;
  * a jitted forward function for the chosen backend;
  * an :class:`IOReport` — the exact simulated tile traffic of the compiled
    order next to the Theorem-1 bounds it must sit inside.

Calling the plan runs inference; nothing is re-derived per call.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.blocksparse import BlockFFNN, BSRLayer
from repro.core.bounds import Bounds
from repro.core.iosim import IOStats
from repro.kernels.ops import CompiledSchedule, FlatSchedule


@dataclasses.dataclass(frozen=True)
class DynamicIOReport:
    """Measured dynamic I/O of one gated forward on one concrete batch.

    The static Theorem-1 schedule reads every scheduled weight block; a
    gated forward only *consumes* the blocks whose input tile held a nonzero
    activation for some real batch row.  ``per_layer_dynamic[k]`` counts the
    scheduled layer-``k`` blocks that survived gating (the dynamic I/O a
    demand-driven weight stream pays), next to the full
    ``per_layer_static[k]`` schedule length; the per-block lower bound of
    any schedule is the dynamic count itself, since each surviving block
    must stream at least once.  Occupancy fields describe *why*:
    ``per_layer_live_tiles[k]`` of ``per_layer_in_tiles[k]`` input tiles
    were live, ``per_layer_row_occupancy[k]`` is the mean live-row fraction
    per tile, and ``per_layer_hist[k]`` buckets tiles by live-row fraction
    as ``(dead, (0,.25), [.25,.5), [.5,.75), [.75,1])``.

    Counts are computed over *real* batch rows only — engine batch padding
    is excluded, so sigmoid-style epilogues turning padded zero rows
    nonzero cannot make a dead tile look live.
    """

    batch: int
    per_layer_static: Tuple[int, ...]
    per_layer_dynamic: Tuple[int, ...]
    per_layer_in_tiles: Tuple[int, ...]
    per_layer_live_tiles: Tuple[int, ...]
    per_layer_row_occupancy: Tuple[float, ...]
    per_layer_hist: Tuple[Tuple[int, int, int, int, int], ...]
    # byte accounting: bytes one weight block (plus its dequant scale when
    # quantized) costs in the storage dtype — turns the block counts above
    # into the byte traffic a demand-driven stream actually pays.  0 in
    # reports persisted before byte accounting existed.
    bytes_per_block: int = 0
    weight_dtype: str = "f32"

    @property
    def static_total(self) -> int:
        return sum(self.per_layer_static)

    @property
    def dynamic_total(self) -> int:
        return sum(self.per_layer_dynamic)

    @property
    def blocks_skipped(self) -> int:
        return self.static_total - self.dynamic_total

    @property
    def dynamic_weight_bytes(self) -> int:
        """Weight-stream bytes the gated forward actually consumed."""
        return self.dynamic_total * self.bytes_per_block

    @property
    def static_weight_bytes(self) -> int:
        """Weight-stream bytes of the full static schedule."""
        return self.static_total * self.bytes_per_block

    @property
    def read_fraction(self) -> float:
        """dynamic / static block reads (1.0 = nothing was skippable)."""
        return self.dynamic_total / max(1, self.static_total)

    def summary(self) -> str:
        occ = "/".join(f"{f:.2f}" for f in self.per_layer_row_occupancy)
        return (f"dynamic I/O at B={self.batch}: read "
                f"{self.dynamic_total}/{self.static_total} scheduled weight "
                f"blocks ({100 * self.read_fraction:.0f}%, "
                f"{self.blocks_skipped} skipped); per-layer row occupancy "
                f"[{occ}]")

    def to_dict(self) -> dict:
        return {
            "batch": int(self.batch),
            "per_layer_static": [int(v) for v in self.per_layer_static],
            "per_layer_dynamic": [int(v) for v in self.per_layer_dynamic],
            "per_layer_in_tiles": [int(v) for v in self.per_layer_in_tiles],
            "per_layer_live_tiles": [int(v)
                                     for v in self.per_layer_live_tiles],
            "per_layer_row_occupancy": [float(v) for v in
                                        self.per_layer_row_occupancy],
            "per_layer_hist": [[int(v) for v in h]
                               for h in self.per_layer_hist],
            "bytes_per_block": int(self.bytes_per_block),
            "weight_dtype": self.weight_dtype,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "DynamicIOReport":
        return cls(
            batch=d["batch"],
            per_layer_static=tuple(d["per_layer_static"]),
            per_layer_dynamic=tuple(d["per_layer_dynamic"]),
            per_layer_in_tiles=tuple(d["per_layer_in_tiles"]),
            per_layer_live_tiles=tuple(d["per_layer_live_tiles"]),
            per_layer_row_occupancy=tuple(d["per_layer_row_occupancy"]),
            per_layer_hist=tuple(tuple(h) for h in d["per_layer_hist"]),
            # byte fields are absent from pre-quantization manifests
            bytes_per_block=int(d.get("bytes_per_block", 0)),
            weight_dtype=d.get("weight_dtype", "f32"),
        )


@dataclasses.dataclass(frozen=True)
class IOReport:
    """Predicted I/O of a compiled plan vs. the paper's Theorem-1 window.

    ``simulated`` is the exact tile traffic of the plan's connection order
    under the single-resident-tile VMEM model (``core.iosim.simulate`` on the
    block DAG); ``bounds`` are Theorem 1's bounds for the same (connected)
    DAG.  A correct plan always satisfies ``within_bounds``.

    The cross-layer fields quantify what fusing the whole net into one
    kernel saves over per-layer dispatch: ``layered_reads``/``layered_writes``
    are the summed per-layer simulated tile traffic (each layer boundary
    forces the hidden state through HBM there), ``hidden_tiles_kept`` is the
    number of intermediate activation tiles that stay VMEM-resident in the
    fused plan, and ``hidden_bytes_kept_per_row`` the HBM bytes that saves
    per batch row (one write plus one read-back per intermediate feature, at
    the kernel's float32 accumulator/hidden-buffer precision — 4 B/feature).

    The per-dtype byte fields restate the dominant I/O term in the unit the
    hardware pays: ``weight_bytes_streamed`` is the bytes of weight blocks
    one forward streams in the storage dtype (``weight_dtype``; halved for
    bf16, quartered for fp8 at the identical schedule),
    ``scale_bytes_streamed`` the f32 dequant-scale bytes riding along (0
    when unquantized), and ``activation_bytes_per_row`` the f32 activation
    bytes crossing HBM per batch row.  Tile counts and byte counts disagree
    exactly when dtypes differ: quantization changes bytes while the
    schedule — and so every tile count and Theorem-1 bound — is unchanged.
    All byte fields default to 0 so reports persisted before byte
    accounting existed still load.
    """

    simulated: IOStats
    bounds: Bounds
    M_tiles: int
    policy: str
    layered_reads: int = 0
    layered_writes: int = 0
    hidden_tiles_kept: int = 0
    hidden_bytes_kept_per_row: int = 0
    weight_dtype: str = "f32"
    weight_bytes_streamed: int = 0
    scale_bytes_streamed: int = 0
    activation_bytes_per_row: int = 0
    # measured dynamic I/O of the latest gated measurement run (None until
    # ExecutionPlan.measure_dynamic records one) — the static fields above
    # are schedule properties; this one is a property of actual data
    dynamic: Optional[DynamicIOReport] = None

    @property
    def within_total_bound(self) -> bool:
        return self.simulated.total <= self.bounds.total_hi

    @property
    def within_write_bounds(self) -> bool:
        return (self.bounds.writes_lo <= self.simulated.writes
                <= self.bounds.writes_hi)

    @property
    def within_bounds(self) -> bool:
        return self.within_total_bound and self.within_write_bounds

    @property
    def optimality_ratio(self) -> float:
        """simulated / lower bound — Theorem 1 guarantees ≤ 2 is achievable.

        An empty DAG (no connections survive pruning) moves no tiles and has
        a zero lower bound; it is vacuously optimal, so the ratio is 1.0
        rather than a 0/0.
        """
        if self.simulated.total == 0 and self.bounds.total_lo == 0:
            return 1.0
        return self.simulated.total / max(1, self.bounds.total_lo)

    @property
    def weight_stream_bytes(self) -> int:
        """Total weight-stream bytes per forward: narrow blocks + scales."""
        return self.weight_bytes_streamed + self.scale_bytes_streamed

    @property
    def layered_total(self) -> int:
        return self.layered_reads + self.layered_writes

    @property
    def cross_layer_savings(self) -> int:
        """Tile transfers the fused whole-net schedule avoids vs per-layer
        dispatch (hidden state kept in VMEM across layer boundaries)."""
        return max(0, self.layered_total - self.simulated.total)

    def summary(self) -> str:
        s, b = self.simulated, self.bounds
        msg = (f"tile I/O {s.total} (r={s.reads} w={s.writes}) in "
               f"[{b.total_lo}, {b.total_hi}] "
               f"(x{self.optimality_ratio:.2f} of lower bound, "
               f"M={self.M_tiles} tiles, {self.policy.upper()})")
        if self.weight_bytes_streamed:
            msg += (f"; weight stream {self.weight_stream_bytes} B "
                    f"as {self.weight_dtype}")
        if self.layered_total:
            msg += (f"; fused saves {self.cross_layer_savings} tile I/Os vs "
                    f"layered ({self.hidden_tiles_kept} hidden tiles / "
                    f"{self.hidden_bytes_kept_per_row} B/row VMEM-resident)")
        if self.dynamic is not None:
            msg += "; " + self.dynamic.summary()
        return msg

    def to_dict(self) -> dict:
        """JSON-serializable form (the plan store persists this alongside the
        schedule arrays so warm starts skip the I/O re-simulation too)."""
        return {
            "simulated": {"reads": int(self.simulated.reads),
                          "writes": int(self.simulated.writes)},
            "bounds": {
                "reads_lo": int(self.bounds.reads_lo),
                "reads_hi": int(self.bounds.reads_hi),
                "writes_lo": int(self.bounds.writes_lo),
                "writes_hi": int(self.bounds.writes_hi),
            },
            "M_tiles": int(self.M_tiles),
            "policy": self.policy,
            "layered_reads": int(self.layered_reads),
            "layered_writes": int(self.layered_writes),
            "hidden_tiles_kept": int(self.hidden_tiles_kept),
            "hidden_bytes_kept_per_row": int(self.hidden_bytes_kept_per_row),
            "weight_dtype": self.weight_dtype,
            "weight_bytes_streamed": int(self.weight_bytes_streamed),
            "scale_bytes_streamed": int(self.scale_bytes_streamed),
            "activation_bytes_per_row": int(self.activation_bytes_per_row),
            "dynamic": None if self.dynamic is None
            else self.dynamic.to_dict(),
        }

    @classmethod
    def from_dict(cls, d: dict) -> "IOReport":
        dyn = d.get("dynamic")
        return cls(
            simulated=IOStats(**d["simulated"]),
            bounds=Bounds(**d["bounds"]),
            M_tiles=d["M_tiles"],
            policy=d["policy"],
            layered_reads=d.get("layered_reads", 0),
            layered_writes=d.get("layered_writes", 0),
            hidden_tiles_kept=d.get("hidden_tiles_kept", 0),
            hidden_bytes_kept_per_row=d.get("hidden_bytes_kept_per_row", 0),
            # byte fields are absent from pre-quantization manifests
            weight_dtype=d.get("weight_dtype", "f32"),
            weight_bytes_streamed=d.get("weight_bytes_streamed", 0),
            scale_bytes_streamed=d.get("scale_bytes_streamed", 0),
            activation_bytes_per_row=d.get("activation_bytes_per_row", 0),
            dynamic=None if dyn is None else DynamicIOReport.from_dict(dyn),
        )


@dataclasses.dataclass
class ExecutionPlan:
    """A compiled whole-network inference plan.  Call it on inputs."""

    layers: List[BSRLayer]
    schedules: List[CompiledSchedule]
    activations: List[Optional[Callable]]   # fused epilogue per layer
    backend: str                            # resolved backend name
    order: np.ndarray                       # block-DAG connection order
    block_ffnn: BlockFFNN
    io: IOReport
    flat: Optional[FlatSchedule] = None     # cross-layer schedule (fused)
    _forward: Callable = dataclasses.field(repr=False, default=None)
    calls: int = dataclasses.field(default=0, compare=False)
    compile_s: float = 0.0                  # wall time of Engine._build
    annealer_iters: int = 0                 # CR proposals paid for this plan
    gate: bool = False                      # runtime tile-occupancy gating
    # why the plan is not (fully) what was asked for: flat-schedule /
    # megakernel fallbacks no longer degrade silently — the builder records
    # the reason here and describe() surfaces it
    fallback_reason: Optional[str] = None
    _measure: Optional[Callable] = dataclasses.field(repr=False,
                                                     default=None)

    @property
    def fused(self) -> bool:
        """True when the plan executes as one flat cross-layer dispatch (the
        megakernel on pallas/interpret, one segment pass on jnp)."""
        return self.flat is not None

    @property
    def n_in(self) -> int:
        return self.layers[0].n_in

    @property
    def n_out(self) -> int:
        return self.layers[-1].n_out

    @property
    def dtype(self) -> np.dtype:
        """The plan's input dtype: what its forward was traced (and should
        always be called) with.  Feeding any other dtype retraces a second
        program per batch shape — serving callers cast to this first.
        Independent of ``weight_dtype`` — activations stay f32."""
        return np.dtype(self.layers[0].blocks.dtype)

    @property
    def weight_dtype(self) -> str:
        """Storage dtype of the streamed weight blocks (f32/bf16/fp8)."""
        return self.schedules[0].weight_dtype if self.schedules else "f32"

    def __call__(self, x) -> jnp.ndarray:
        """Run inference.  ``x`` is ``[n_in]`` or batched ``[B, n_in]``."""
        x = jnp.asarray(x)
        single = x.ndim == 1
        if single:
            x = x[None, :]
        if x.ndim != 2 or x.shape[1] != self.n_in:
            raise ValueError(
                f"expected input [B, {self.n_in}] or [{self.n_in}], "
                f"got {tuple(x.shape)}"
            )
        y = self._forward(x)
        self.calls += 1
        return y[0] if single else y

    def with_fresh_forward(self, jit: bool = True) -> "ExecutionPlan":
        """A copy of this plan with a newly lowered forward (call count 0).

        The schedule substrate — layers, schedules, flat arrays, order, I/O
        report — is shared by reference; only the jitted dispatch (and the
        gated plan's instrumented measurement twin) is rebuilt.  This is how
        ``repro.serving.bucketing`` fans one compiled schedule out across
        batch buckets without ever re-deriving it.
        """
        from .backends import (
            make_forward,
            make_fused_forward,
            make_fused_measure,
        )

        measure = None
        if self.flat is not None:
            fwd = make_fused_forward(self.layers, self.flat, self.activations,
                                     self.backend, jit=jit, gate=self.gate)
            if self.gate:
                measure = make_fused_measure(self.layers, self.flat,
                                             self.activations, self.backend,
                                             jit=jit)
        else:
            fwd = make_forward(self.layers, self.schedules, self.activations,
                               self.backend, jit=jit, gate=self.gate)
        return dataclasses.replace(self, _forward=fwd, _measure=measure,
                                   calls=0)

    def safe_twin(self, jit: bool = True) -> "ExecutionPlan":
        """The plan's safe-mode twin: same schedule, jnp backend, gate off.

        The jnp segment lowering is the bit-exact reference the megakernel
        is checked against (PR 2), and the ungated forward is bit-exact
        with the gated one (PR 6) — so this twin computes the *identical*
        function through the simplest code path available, just without
        the fast-path machinery that can misbehave.  The serving runtime
        degrades to it when the circuit breaker trips (see
        ``repro.serving.resilience``).  The schedule substrate is shared
        by reference; only the forward is re-lowered.
        """
        twin = dataclasses.replace(self, backend="jnp", gate=False)
        return twin.with_fresh_forward(jit=jit)

    def measure_dynamic(self, x) -> DynamicIOReport:
        """Run one instrumented gated forward on ``x`` and report measured
        dynamic I/O: scheduled weight blocks actually consumed per layer vs
        the static Theorem-1 schedule, plus per-layer occupancy histograms.
        The report is also recorded on ``self.io.dynamic`` (so ``describe``
        and the plan store's serialized report carry it).
        """
        if self._measure is None:
            raise RuntimeError(
                "dynamic I/O measurement needs a gated fused plan — compile "
                "with Engine(gate=True) on a net the flat schedule can "
                "express (uniform square tiles)"
            )
        x = jnp.asarray(x)
        if x.ndim == 1:
            x = x[None, :]
        if x.ndim != 2 or x.shape[1] != self.n_in:
            raise ValueError(
                f"expected input [B, {self.n_in}] or [{self.n_in}], "
                f"got {tuple(x.shape)}"
            )
        _, occs = self._measure(x)
        B = int(x.shape[0])
        bs = self.flat.block
        bpb = bs * bs * np.dtype(np.asarray(self.flat.blocks).dtype).itemsize
        if self.flat.scales is not None:
            bpb += 4                     # the per-block f32 dequant scale
        rows = np.asarray(self.flat.rows)
        stat, dyn, in_tiles, live, row_occ, hists = [], [], [], [], [], []
        for k, (s, e) in enumerate(self.flat.segments):
            occ = np.asarray(occs[k])
            stat.append(int(e - s))
            dyn.append(int(np.sum(occ[rows[s:e]] > 0)))
            in_tiles.append(int(occ.size))
            live.append(int(np.sum(occ > 0)))
            frac = occ.astype(np.float64) / max(1, B)
            row_occ.append(float(frac.mean()) if frac.size else 0.0)
            alive = frac[occ > 0]
            hist = np.histogram(alive, bins=[0.0, 0.25, 0.5, 0.75,
                                             1.0 + 1e-9])[0]
            hists.append((int(np.sum(occ == 0)),)
                         + tuple(int(n) for n in hist))
        report = DynamicIOReport(
            batch=B,
            per_layer_static=tuple(stat),
            per_layer_dynamic=tuple(dyn),
            per_layer_in_tiles=tuple(in_tiles),
            per_layer_live_tiles=tuple(live),
            per_layer_row_occupancy=tuple(row_occ),
            per_layer_hist=tuple(hists),
            bytes_per_block=int(bpb),
            weight_dtype=self.flat.weight_dtype,
        )
        self.io = dataclasses.replace(self.io, dynamic=report)
        return report

    def trace_attrs(self) -> dict:
        """Flat span-attribute dict describing this plan's I/O profile —
        backend, fusion/gating, simulated tile I/O vs the Theorem-1 lower
        bound, and the latest measured dynamic read counts when present
        (:func:`repro.obs.telemetry.plan_io_attrs`).  This is what the
        serving runtime stamps on every ``batch.execute`` span."""
        from repro.obs.telemetry import plan_io_attrs
        return plan_io_attrs(self)

    def describe(self) -> str:
        shapes = " -> ".join(
            [str(self.n_in)] + [str(l.n_out) for l in self.layers])
        if self.block_ffnn.glu:
            shapes = (f"{self.n_in} -> 2 x {self.layers[0].n_out} (GLU) -> "
                      f"{self.n_out}")
        nnz = sum(l.nnz_blocks for l in self.layers)
        mode = "fused" if self.fused else "layered"
        if self.gate:
            mode += "+gated"
        if self.weight_dtype != "f32":
            mode += f"+{self.weight_dtype}"
        fallback = "" if self.fallback_reason is None \
            else f" [fallback: {self.fallback_reason}]"
        return (f"ExecutionPlan[{self.backend}/{mode}]{fallback} {shapes} "
                f"({len(self.layers)} layers, {nnz} nonzero blocks); "
                + self.io.summary()
                + f"; compiled in {self.compile_s:.2f}s "
                  f"({self.annealer_iters} annealer iters), "
                  f"{self.calls} calls")

    def artifact_arrays(self) -> dict:
        """The plan's persistable schedule arrays, as host numpy.

        ``order`` (the whole-DAG connection order) is the artifact everything
        else re-derives from deterministically; the flat-schedule prefetch
        arrays ride along so a loader can verify the rebuilt schedule matches
        the stored one bit-for-bit (``repro.serving.plancache``).
        """
        out = {"order": np.asarray(self.order, dtype=np.int64)}
        if self.flat is not None:
            f = self.flat
            for name in ("rows", "cols", "first", "last", "layer_id",
                         "hbm_row", "out_tile", "bias_idx"):
                out[f"flat_{name}"] = np.asarray(getattr(f, name),
                                                 dtype=np.int32)
            if f.scales is not None:
                # quantized stream: persist the narrow blocks + scales so a
                # warm start verifies the stored quantization byte-for-byte
                # (narrow dtypes ride the checkpoint void-view path)
                out["flat_qblocks"] = np.asarray(f.blocks)
                out["flat_scales"] = np.asarray(f.scales, dtype=np.float32)
        return out
