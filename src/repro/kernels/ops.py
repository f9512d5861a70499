"""Jitted public wrappers around the Pallas kernels.

``scheduled_bsr_layer`` is the user-facing op: it takes a ``BSRLayer`` plus a
block schedule (from ``core.blocksparse.schedule_arrays``), enforces the
Theorem-1 contiguity contract, patches empty output tiles, and dispatches to
the Pallas kernel (TPU) or the jnp oracle (non-TPU backends).

``compile_flat_schedule`` concatenates the per-layer schedules of a whole
network into one cross-layer :class:`FlatSchedule` — the input of the
megakernel (``bsr_matmul.bsr_megakernel``), which walks every nonzero block
of every layer in one Pallas grid and keeps the hidden state VMEM-resident
across layer boundaries.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.blocksparse import BSRLayer, is_contiguous_by_output
from . import ref
from .bsr_matmul import bsr_matmul

try:  # narrow weight-stream dtypes (already a jax dependency)
    import ml_dtypes as _ml_dtypes
except ImportError:  # pragma: no cover - jax always ships it
    _ml_dtypes = None

#: fp8 storage dtype of the quantized weight stream; None when the installed
#: ml_dtypes predates float8 support (tests monkeypatch this to exercise the
#: graceful compile-time guard).
FP8_DTYPE = getattr(_ml_dtypes, "float8_e4m3fn", None)
BF16_DTYPE = getattr(_ml_dtypes, "bfloat16", None)

#: largest finite magnitude representable in float8_e4m3fn — the per-block
#: scale maps each block's absmax onto it (the DeepSeek-V3 block-128 scheme
#: at our tile granularity).
FP8_MAX = 448.0

WEIGHT_DTYPES = ("f32", "bf16", "fp8")

_WEIGHT_DTYPE_ALIASES = {
    None: "f32", "f32": "f32", "float32": "f32", "fp32": "f32",
    "bf16": "bf16", "bfloat16": "bf16",
    "fp8": "fp8", "f8": "fp8", "float8": "fp8", "float8_e4m3fn": "fp8",
}


def resolve_weight_dtype(name) -> str:
    """Normalize a weight-stream dtype spec to ``f32`` | ``bf16`` | ``fp8``.

    Raises a clear ``ValueError`` at compile time when fp8 is requested but
    the installed ``ml_dtypes`` lacks ``float8_e4m3fn`` — never a deep
    kernel ``TypeError`` later.
    """
    key = name.lower() if isinstance(name, str) else name
    try:
        wdt = _WEIGHT_DTYPE_ALIASES[key]
    except KeyError:
        raise ValueError(
            f"unknown weight_dtype {name!r}; pick from {WEIGHT_DTYPES}"
        ) from None
    if wdt == "fp8" and FP8_DTYPE is None:
        raise ValueError(
            "weight_dtype='fp8' needs ml_dtypes with float8_e4m3fn; this "
            "installation lacks it — use 'bf16' or 'f32'"
        )
    if wdt == "bf16" and BF16_DTYPE is None:
        raise ValueError(
            "weight_dtype='bf16' needs ml_dtypes with bfloat16; this "
            "installation lacks it — use 'f32'"
        )
    return wdt


def weight_itemsize(weight_dtype: str) -> int:
    """Bytes per weight element in the streamed (storage) dtype."""
    return {"f32": 4, "bf16": 2, "fp8": 1}[resolve_weight_dtype(weight_dtype)]


def quantize_blocks(
    blocks: np.ndarray, weight_dtype: str
) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """Quantize ``[nnz, bm, bn]`` f32 blocks to the narrow storage dtype.

    Returns ``(qblocks, scales)`` where ``scales`` is one f32 factor per
    block (``None`` for f32: identity).  Dequant is ``q.astype(f32) *
    scale``.  bf16 keeps unit scales (its exponent range matches f32);
    fp8 maps each block's absmax onto ``FP8_MAX`` so the 4-bit mantissa is
    spent on the block's actual dynamic range.  All-zero blocks (including
    the bias-patch blocks) get scale 1.0, so they dequantize to exact zero.
    """
    wdt = resolve_weight_dtype(weight_dtype)
    blocks = np.asarray(blocks, dtype=np.float32)
    if wdt == "f32":
        return blocks, None
    nnz = blocks.shape[0]
    if wdt == "bf16":
        return blocks.astype(BF16_DTYPE), np.ones(nnz, dtype=np.float32)
    amax = np.max(np.abs(blocks), axis=(1, 2))
    scales = np.where(amax > 0, amax / FP8_MAX, 1.0).astype(np.float32)
    q = (blocks / scales[:, None, None]).astype(FP8_DTYPE)
    return q, scales


@dataclasses.dataclass
class CompiledSchedule:
    """A validated, kernel-ready block schedule for one BSR layer."""

    blocks: jnp.ndarray   # [nnz', bm, bn] in schedule order (incl. patch blocks)
    rows: jnp.ndarray     # int32 [nnz']
    cols: jnp.ndarray     # int32 [nnz']
    first: jnp.ndarray
    last: jnp.ndarray
    grid_out: int
    # simulated tile traffic of this schedule (reads, writes) under the
    # single-resident-tile VMEM model — the paper's I/O count for M=3.
    sim_reads: int
    sim_writes: int
    # quantized weight stream: ``blocks`` is stored in the narrow dtype and
    # ``scales`` holds one f32 dequant factor per block (None for f32)
    scales: Optional[jnp.ndarray] = None
    weight_dtype: str = "f32"
    # trailing steps that carry a zero block for an output tile no kept
    # block reaches (bias + activation still have to run there)
    patched: int = 0

    @property
    def weight_bytes(self) -> int:
        """Bytes the kernel streams for this layer's weight blocks."""
        return int(np.asarray(self.blocks).nbytes)

    @property
    def scale_bytes(self) -> int:
        return 0 if self.scales is None else int(np.asarray(self.scales).nbytes)


def compile_schedule(
    layer: BSRLayer,
    perm: Optional[np.ndarray] = None,
    weight_dtype: str = "f32",
) -> CompiledSchedule:
    """Validate + pack a schedule.  ``perm`` permutes the layer's block storage
    (default: as stored).  Raises if the schedule is not contiguous-by-output —
    the Theorem-1 family the kernel's VMEM-resident accumulator requires."""
    if perm is None:
        perm = np.arange(layer.nnz_blocks)
    perm = np.asarray(perm, dtype=np.int64)
    rows = layer.rows[perm].astype(np.int32)
    cols = layer.cols[perm].astype(np.int32)
    blocks = layer.blocks[perm]
    if not is_contiguous_by_output(cols):
        raise ValueError(
            "schedule is not contiguous by output tile; use a Theorem-1 "
            "(grouped-by-output) order — see core.blocksparse.schedule_arrays"
        )
    # patch: output tiles with no nonzero block still need bias+activation.
    present = np.zeros(layer.grid_out, dtype=bool)
    present[cols] = True
    missing = np.flatnonzero(~present).astype(np.int32)
    if len(missing):
        zero = np.zeros((len(missing), layer.block_m, layer.block_n), blocks.dtype)
        blocks = np.concatenate([blocks, zero])
        rows = np.concatenate([rows, np.zeros(len(missing), np.int32)])
        cols = np.concatenate([cols, missing])
    nnz = len(rows)
    first = np.zeros(nnz, np.int32)
    last = np.zeros(nnz, np.int32)
    first[0] = 1
    first[1:] = (cols[1:] != cols[:-1]).astype(np.int32)
    last[-1] = 1
    last[:-1] = (cols[1:] != cols[:-1]).astype(np.int32)
    # simulated tile I/O: weight blocks stream once each; an input tile is
    # re-read whenever rows[] changes; one write per output tile.
    row_changes = 1 + int((rows[1:] != rows[:-1]).sum()) if nnz else 0
    sim_reads = nnz + row_changes + layer.grid_out  # + bias tiles
    sim_writes = layer.grid_out
    qblocks, scales = quantize_blocks(blocks, weight_dtype)
    return CompiledSchedule(
        blocks=jnp.asarray(qblocks),
        rows=jnp.asarray(rows),
        cols=jnp.asarray(cols),
        first=jnp.asarray(first),
        last=jnp.asarray(last),
        grid_out=layer.grid_out,
        sim_reads=sim_reads,
        sim_writes=sim_writes,
        scales=None if scales is None else jnp.asarray(scales),
        weight_dtype=resolve_weight_dtype(weight_dtype),
        patched=int(len(missing)),
    )


def pack_attrs(schedules: Sequence[CompiledSchedule], glu: bool) -> dict:
    """What packing made of a network's schedules: ``glu_pairs``, the
    gate/up step pairs of a gated block (0 for a chain), and
    ``patched_steps``, the steps that carry a zero patch block."""
    return {"glu_pairs": int(schedules[0].rows.shape[0]) if glu else 0,
            "patched_steps": sum(sch.patched for sch in schedules)}


@dataclasses.dataclass
class FlatSchedule:
    """One whole-network block schedule: all layers' steps in one flat grid.

    The per-step arrays are the per-layer ``CompiledSchedule`` arrays
    concatenated in layer order (each layer segment keeps its Theorem-1
    contiguous-by-output grouping), plus the cross-layer scalar-prefetch
    arrays the megernel's index maps need:

      * ``layer_id[g]`` — which layer step ``g`` belongs to;
      * ``hbm_row[g]``  — the HBM input tile to map into VMEM: ``rows[g]``
        during layer 0, then frozen (no index change => no re-fetch) since
        later layers read the VMEM-resident hidden state instead;
      * ``out_tile[g]`` — the HBM output tile to map: ``cols[g]`` during the
        final layer, else pinned to the final layer's first output tile so
        the out buffer is never flushed before it holds real data;
      * ``bias_idx[g]`` — row of ``bias_tiles`` ([total output tiles, bs])
        holding the bias of step ``g``'s output tile.

    ``segments[k] = (start, end)`` delimits layer ``k``'s steps; the ``jnp``
    lowering consumes exactly these flat arrays one segment at a time, so
    all backends execute the identical connection order.

    A gated block (``operand`` not None) has two segments: the gate's and
    the up's steps interleaved pair by pair, then down.  ``operand[g]`` is 1
    on a gate step and 0 on every other; ``first`` marks only the gate's
    first step of a hidden tile (both accumulators start there) and
    ``last`` the gate's and the up's last steps of it.  ``bias_idx`` points
    at the gate's bias tile on every step of the pair segment but the up's
    last of each tile, so the bias tile moves twice a tile, not every step.
    """

    blocks: jnp.ndarray       # [nnz_total, bs, bs] scheduled order
    rows: jnp.ndarray         # int32 [nnz_total] layer-local input tile
    cols: jnp.ndarray         # int32 [nnz_total] layer-local output tile
    first: jnp.ndarray        # int32 [nnz_total]
    last: jnp.ndarray         # int32 [nnz_total]
    layer_id: jnp.ndarray     # int32 [nnz_total]
    hbm_row: jnp.ndarray      # int32 [nnz_total]
    out_tile: jnp.ndarray     # int32 [nnz_total]
    bias_idx: jnp.ndarray     # int32 [nnz_total]
    bias_tiles: jnp.ndarray   # [sum(grid_out_k), bs]
    segments: Tuple[Tuple[int, int], ...]
    n_layers: int
    block: int                # uniform tile size
    grid_out_final: int
    n_out: int
    hidden_tiles: int         # max tile count of any intermediate activation
    # simulated per-layer tile traffic (reads, writes) — flat totals are the
    # sums, which tests check against the per-layer reports
    per_layer_io: Tuple[Tuple[int, int], ...]
    # quantized weight stream: ``blocks`` is stored narrow, ``scales`` is one
    # f32 dequant factor per flat step (None for f32)
    scales: Optional[jnp.ndarray] = None
    weight_dtype: str = "f32"
    # gated block: int32 [nnz_total], 1 on a gate step (None for a chain)
    operand: Optional[jnp.ndarray] = None
    glu_pairs: int = 0        # gate/up step pairs (0 for a chain)
    patched_steps: int = 0    # steps that carry a zero patch block

    @property
    def nnz(self) -> int:
        return int(self.rows.shape[0])

    @property
    def glu(self) -> bool:
        return self.operand is not None

    @property
    def weight_bytes(self) -> int:
        """Bytes of weight blocks the megakernel streams per forward."""
        return int(np.asarray(self.blocks).nbytes)

    @property
    def scale_bytes(self) -> int:
        return 0 if self.scales is None else int(np.asarray(self.scales).nbytes)

    @property
    def sim_reads(self) -> int:
        return sum(r for r, _ in self.per_layer_io)

    @property
    def sim_writes(self) -> int:
        return sum(w for _, w in self.per_layer_io)


def _pairs(a, b):
    """``[a0, b0, a1, b1, ...]`` of two equally long arrays."""
    stack = jnp.stack if isinstance(a, jnp.ndarray) else np.stack
    return stack([a, b], axis=1).reshape((-1,) + tuple(a.shape[1:]))


def compile_flat_schedule(
    layers: Sequence[BSRLayer],
    schedules: Sequence[CompiledSchedule],
    glu: bool = False,
) -> FlatSchedule:
    """Concatenate per-layer schedules into one megakernel-ready flat schedule.

    Requires a uniform tile size: all layers must share ``block_m`` /
    ``block_n`` (and square tiles when depth > 1, since layer k's output
    tiles are layer k+1's input tiles).  Raises ``ValueError`` otherwise —
    the engine falls back to per-layer dispatch in that case.

    ``glu=True``: ``layers`` are a gated block's ``[gate, up, down]``; the
    gate's and the up's steps are interleaved into one segment (see
    :class:`FlatSchedule`), so the grid walks two layers.
    """
    if not layers or len(layers) != len(schedules):
        raise ValueError("need one schedule per layer")
    bs = layers[0].block_m
    for lay in layers:
        if lay.block_m != bs or lay.block_n != bs:
            raise ValueError(
                "flat schedule requires one uniform square tile size across "
                f"layers; got ({lay.block_m}, {lay.block_n}) vs {bs}"
            )
    wdt = schedules[0].weight_dtype
    for sch in schedules:
        if sch.weight_dtype != wdt:
            raise ValueError(
                "flat schedule requires one weight_dtype across layers; got "
                f"{sch.weight_dtype!r} vs {wdt!r}"
            )

    bias_off = np.cumsum([0] + [lay.grid_out for lay in layers])
    # one entry per segment: rows, cols, first, last, bias_idx, blocks,
    # scales, operand
    parts = []
    chain = list(enumerate(schedules))
    if glu:   # gate and up share one mask and one order (GLUBlock)
        g, u = schedules[0], schedules[1]
        rows, cols = np.asarray(g.rows), np.asarray(g.cols)
        last_u = np.asarray(u.last)
        parts.append((
            _pairs(rows, rows), _pairs(cols, cols),
            _pairs(np.asarray(g.first), np.zeros_like(last_u)),
            _pairs(np.asarray(g.last), last_u),
            _pairs(cols, np.where(last_u == 1, bias_off[1] + cols, cols)),
            _pairs(g.blocks, u.blocks),
            None if g.scales is None else _pairs(g.scales, u.scales),
            _pairs(np.ones_like(rows), np.zeros_like(rows))))
        chain = chain[2:]
    for k, sch in chain:
        cols = np.asarray(sch.cols, dtype=np.int32)
        parts.append((
            np.asarray(sch.rows), cols, np.asarray(sch.first),
            np.asarray(sch.last), bias_off[k] + cols, sch.blocks, sch.scales,
            np.zeros_like(cols) if glu else None))

    rows, cols, first, last, bias_idx = (
        np.concatenate(arrs).astype(np.int32) for arrs in zip(*[
            p[:5] for p in parts]))
    segments: List[Tuple[int, int]] = []
    off = 0
    for p in parts:
        segments.append((off, off + len(p[0])))
        off += len(p[0])
    layer_id = np.concatenate([np.full(e - s, k, dtype=np.int32)
                               for k, (s, e) in enumerate(segments)])

    # hbm_row: live during layer 0, frozen afterwards (constant index map
    # result => Pallas keeps the current block in VMEM, no extra fetch)
    n0 = segments[0][1]
    hbm_row = rows.copy()
    if off > n0:
        hbm_row[n0:] = hbm_row[n0 - 1]
    # out_tile: live during the final layer, pinned to its first output tile
    # before that (the buffer holds garbage until the final layer's first
    # epilogue overwrites it in place, so nothing bogus is ever flushed)
    fs, fe = segments[-1]
    out_tile = np.full(off, int(cols[fs]), dtype=np.int32)
    out_tile[fs:fe] = cols[fs:fe]
    # flat bias tiles; ``bias_idx`` holds each step's row of them
    bias_tiles = np.concatenate(
        [np.asarray(lay.bias, dtype=np.float32).reshape(lay.grid_out, -1)
         for lay in layers])

    scales = None if wdt == "f32" else jnp.concatenate([p[6] for p in parts])
    hidden_tiles = max([lay.grid_out for lay in layers[:-1]] or [1])
    return FlatSchedule(
        blocks=jnp.concatenate([p[5] for p in parts]),
        rows=jnp.asarray(rows),
        cols=jnp.asarray(cols),
        first=jnp.asarray(first),
        last=jnp.asarray(last),
        layer_id=jnp.asarray(layer_id),
        hbm_row=jnp.asarray(hbm_row),
        out_tile=jnp.asarray(out_tile),
        bias_idx=jnp.asarray(bias_idx),
        bias_tiles=jnp.asarray(bias_tiles),
        segments=tuple(segments),
        n_layers=len(parts),
        block=bs,
        grid_out_final=layers[-1].grid_out,
        n_out=layers[-1].n_out,
        hidden_tiles=int(hidden_tiles),
        per_layer_io=tuple((sch.sim_reads, sch.sim_writes)
                           for sch in schedules),
        scales=scales,
        weight_dtype=wdt,
        operand=jnp.asarray(np.concatenate([p[7] for p in parts]).astype(
            np.int32)) if glu else None,
        **pack_attrs(schedules, glu),
    )


def scheduled_bsr_layer(
    x: jnp.ndarray,
    layer: BSRLayer,
    schedule: Optional[CompiledSchedule] = None,
    activation: Optional[Callable] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """y = act(x @ W_bsr + b) via the scheduled Pallas kernel.

    On non-TPU backends ``interpret`` defaults to True (the Pallas body runs
    in Python — the correctness path used by tests on CPU).
    """
    if schedule is None:
        schedule = compile_schedule(layer)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return bsr_matmul(
        x,
        schedule.blocks,
        schedule.rows,
        schedule.cols,
        schedule.first,
        schedule.last,
        jnp.asarray(layer.bias),
        grid_out=schedule.grid_out,
        activation=activation,
        interpret=interpret,
        scales=schedule.scales,
    )


def bsr_layer_ref(
    x: jnp.ndarray,
    layer: BSRLayer,
    activation: Optional[Callable] = None,
) -> jnp.ndarray:
    """Oracle wrapper with the same signature family as scheduled_bsr_layer."""
    return ref.bsr_matmul_ref(
        x, layer.rows, layer.cols, jnp.asarray(layer.blocks),
        jnp.asarray(layer.bias), layer.grid_in, layer.grid_out, activation,
    )
