"""Execution backends for compiled plans.

Three ways to run the same flat cross-layer schedule:

  * ``pallas``    — the whole-network Pallas megakernel
                    (``kernels/bsr_matmul.bsr_megakernel``): ONE grid over
                    every nonzero block of every layer, hidden state resident
                    in VMEM across layer boundaries; the production path.
  * ``interpret`` — the identical megakernel body run in interpret mode;
                    exact kernel semantics on any host (the correctness path).
  * ``jnp``       — a pure-``jnp`` lowering of the same flat schedule: one
                    gather → batched block matmul → segment-sum pass per
                    layer segment of the flat arrays; runs fast on CPU/GPU
                    and is fully jittable.

All three consume the same ``FlatSchedule`` arrays, so the connection order —
the thing the paper is about — is identical across backends; only the
machinery that walks it differs.  ``auto`` resolves to ``pallas`` on TPU and
``jnp`` elsewhere.

Nets whose tile shapes cannot be flattened (non-uniform block sizes) fall
back to the per-layer dispatch path (``make_forward``), which is also what
``benchmarks/bench_engine.py`` uses as the layered baseline.

The TPU kernels tile the batch dimension, so ``B`` is padded up to the
sublane multiple of the dtype before a ``pallas``/``interpret`` launch and
the result is sliced back — odd batch sizes work on every backend.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import shard_map as compat_shard_map
from repro.core.blocksparse import BSRLayer
from repro.kernels.bsr_matmul import bsr_matmul, bsr_megakernel
from repro.kernels.ops import CompiledSchedule, FlatSchedule

BACKENDS = ("pallas", "interpret", "jnp")


def resolve_backend(name: str) -> str:
    """Resolve ``auto`` (and validate) to a concrete backend name."""
    if name == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    if name not in BACKENDS:
        raise ValueError(f"unknown backend {name!r}; pick from {('auto',) + BACKENDS}")
    return name


def sublane_multiple(dtype) -> int:
    """Minimum TPU sublane count for ``dtype`` (second-to-last dim tiling)."""
    itemsize = jnp.dtype(dtype).itemsize
    if itemsize == 2:
        return 16
    if itemsize == 1:
        return 32
    return 8


def pad_batch(x: jnp.ndarray) -> jnp.ndarray:
    """Pad the batch dim up to the sublane multiple (TPU tiling constraint)."""
    B = x.shape[0]
    m = sublane_multiple(x.dtype)
    pad = (-B) % m
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    return x


def tile_occupancy(
    h: jnp.ndarray,
    block: int,
    grid: int,
    valid: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Per-input-tile live-row counts of an activation: ``occ[t]`` is the
    number of batch rows with any nonzero in tile ``t``; a tile is *dead*
    (every consuming weight block skippable) exactly when ``occ[t] == 0``.

    ``valid`` ([B] bool) restricts the count to real batch rows — padded
    zero rows must be excluded from every batch-level reduction, because
    non-odd epilogues (sigmoid, gelu, softmax-style) turn them nonzero and
    would make dead tiles look live in the measured occupancy.  (Exclusion
    only ever *lowers* counts for rows whose outputs are sliced away, so it
    can never mark a tile dead that a real row needs.)
    """
    B = h.shape[0]
    live = h.reshape(B, grid, block) != 0
    if valid is not None:
        live = live & valid.reshape(B, 1, 1)
    return jnp.sum(jnp.any(live, axis=2), axis=0).astype(jnp.int32)


def activations_equal(a, b) -> bool:
    """Value-level equality for epilogue callables.

    Plain callables compare by identity (``==`` on functions), but
    ``functools.partial`` objects never do — two per-layer
    ``partial(leaky_relu, 0.1)`` instances are equal-but-distinct and used
    to silently lose the megakernel.  Compare partials structurally (same
    func, same bound args); anything unhashable/ambiguous in the bound args
    falls back to "not equal" rather than raising.
    """
    if a is b:
        return True
    if isinstance(a, functools.partial) and isinstance(b, functools.partial):
        try:
            return (activations_equal(a.func, b.func)
                    and bool(a.args == b.args)
                    and bool(a.keywords == b.keywords))
        except (TypeError, ValueError):
            return False
    try:
        return bool(a == b)
    except (TypeError, ValueError):
        return False


# --------------------------------------------------------------------------- #
# per-layer dispatch (layered baseline + fallback for non-uniform tiles)
# --------------------------------------------------------------------------- #

def _jnp_layer(
    x: jnp.ndarray,
    layer: BSRLayer,
    schedule: CompiledSchedule,
    activation: Optional[Callable],
    occ: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """One layer of the schedule as gather → block matmul → segment-sum.

    Accumulates in float32 (like the kernel's VMEM accumulator) and walks the
    blocks in schedule order, so the arithmetic is the schedule's.
    """
    return _jnp_segment(
        x, schedule.rows, schedule.cols, schedule.blocks,
        jnp.asarray(layer.bias), layer.block_m, layer.block_n,
        layer.grid_in, layer.grid_out, activation, occ=occ,
        scales=schedule.scales,
    )


def _jnp_segment(
    x: jnp.ndarray,
    rows: jnp.ndarray,
    cols: jnp.ndarray,
    blocks: jnp.ndarray,
    bias: jnp.ndarray,
    bm: int,
    bn: int,
    grid_in: int,
    grid_out: int,
    activation: Optional[Callable],
    pad_segments: int = 0,
    occ: Optional[jnp.ndarray] = None,
    scales: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """One schedule segment as gather → block matmul → segment-sum.

    ``pad_segments`` > 0 reserves that many trailing sink segments: schedule
    steps with ``cols >= grid_out`` land there and are dropped before the
    bias/activation epilogue.  The sharded forward pads every shard's
    schedule to a uniform length with steps routed to the sink, so padding
    never perturbs a real output tile (not even by adding 0.0).

    ``occ`` ([grid_in] int32, from :func:`tile_occupancy`) masks the gather:
    steps whose input tile is dead contribute a hard zero instead of their
    (already exactly-zero) tile values.  A dead tile's entries are all ±0,
    and ``(±0) * 0 = ±0`` preserves each bit pattern, so the masked segment
    is bit-identical to the unmasked one — the mask is how the jnp lowering
    *expresses* the skip an I/O-aware kernel would take.

    ``scales`` ([nnz] f32) marks a quantized weight stream: ``blocks`` is
    stored narrow (bf16/fp8) and dequantized here per block right before
    the einsum — the exact f32 values the megakernel's fused dequant
    produces, so quantized backends agree the same way f32 ones do.
    """
    B = x.shape[0]
    xt = x.reshape(B, grid_in, bm).transpose(1, 0, 2)          # [gi, B, bm]
    gathered = jnp.take(xt, rows, axis=0)                      # [nnz, B, bm]
    if occ is not None:
        gathered = gathered * (occ[rows] > 0).astype(
            gathered.dtype)[:, None, None]
    w = blocks.astype(jnp.float32)
    if scales is not None:
        w = w * scales[:, None, None]
    contrib = jnp.einsum(
        "gbm,gmn->gbn",
        gathered.astype(jnp.float32),
        w,
    )                                                          # [nnz, B, bn]
    y = jax.ops.segment_sum(contrib, cols,
                            num_segments=grid_out + pad_segments)
    if pad_segments:
        y = y[:grid_out]                                       # [go, B, bn]
    y = y.transpose(1, 0, 2).reshape(B, grid_out * bn)
    y = y + bias.astype(jnp.float32)
    if activation is not None:
        y = activation(y)
    return y.astype(x.dtype)


def _pallas_layer(
    x: jnp.ndarray,
    layer: BSRLayer,
    schedule: CompiledSchedule,
    activation: Optional[Callable],
    interpret: bool,
) -> jnp.ndarray:
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


def make_forward(
    layers: Sequence[BSRLayer],
    schedules: Sequence[CompiledSchedule],
    activations: Sequence[Optional[Callable]],
    backend: str,
    jit: bool = True,
    gate: bool = False,
) -> Callable:
    """Per-layer dispatch forward: x [B, n_in] -> [B, n_out].

    One ``pallas_call`` (or jnp pass) per layer inside one jitted program —
    the PR-1 call pattern, kept as the layered baseline the megakernel is
    benchmarked against and as the fallback for nets the flat schedule
    cannot express (non-uniform tile sizes).

    ``gate`` masks each layer's gather on runtime tile occupancy — honored
    on the ``jnp`` path only (the per-layer Pallas kernel has no occupancy
    predication; the engine records that on the plan's fallback reason).
    """
    layers = list(layers)
    schedules = list(schedules)
    activations = list(activations)
    gate = gate and backend == "jnp"

    def forward(x):
        B = x.shape[0]
        h = x
        if backend != "jnp":
            h = pad_batch(h)
        for layer, schedule, act in zip(layers, schedules, activations):
            if backend == "jnp":
                occ = tile_occupancy(h, layer.block_m, layer.grid_in) \
                    if gate else None
                h = _jnp_layer(h, layer, schedule, act, occ=occ)
            else:
                h = _pallas_layer(h, layer, schedule, act,
                                  interpret=(backend == "interpret"))
        return h[:B]

    return jax.jit(forward) if jit else forward


# --------------------------------------------------------------------------- #
# fused dispatch: the whole net as one flat schedule
# --------------------------------------------------------------------------- #

def _check_fusible_activations(activations: Sequence[Optional[Callable]]):
    """The megakernel fuses ONE hidden epilogue; equal-but-distinct
    callables (per-layer partials with the same bound args) count as one."""
    hidden = list(activations[:-1])
    distinct = sum(1 for a in hidden[1:] if not activations_equal(hidden[0], a))
    if distinct:
        raise ValueError(
            "the megakernel fuses ONE hidden-layer activation; got "
            f"{distinct + 1} distinct hidden epilogues — use fuse=False "
            "(per-layer dispatch) for heterogeneous activations"
        )


def _flat_segments(layers, flat: FlatSchedule, activations):
    """Materialize per-layer views of the flat arrays once, outside any
    trace, so no per-call slicing of the big block array survives into the
    compiled program (shared by the fused jnp forward and its instrumented
    measurement twin).

    A gated block's pair segment sums the up's steps into output tiles
    ``[0, h)`` and the gate's into ``[h, 2h)``, ``h`` the hidden tiles, with
    no activation: :func:`_glu_product` then makes the hidden state."""
    segs = []
    bias_row = 0
    if flat.glu:
        s, e = flat.segments[0]
        gate = layers[0]
        h = gate.grid_out
        bias = jnp.concatenate([flat.bias_tiles[h:2 * h],
                                flat.bias_tiles[:h]]).reshape(-1)
        scales = None if flat.scales is None else flat.scales[s:e]
        segs.append((flat.rows[s:e], flat.cols[s:e] + h * flat.operand[s:e],
                     flat.blocks[s:e], scales, bias, gate.grid_in, 2 * h,
                     None))
        layers, activations = layers[2:], activations[2:]
        bias_row = 2 * h
    for lay, a, (s, e) in zip(layers, activations, flat.segments[len(segs):]):
        bias = flat.bias_tiles[bias_row:bias_row + lay.grid_out].reshape(-1)
        scales = None if flat.scales is None else flat.scales[s:e]
        segs.append((flat.rows[s:e], flat.cols[s:e], flat.blocks[s:e],
                     scales, bias, lay.grid_in, lay.grid_out, a))
        bias_row += lay.grid_out
    return segs


def _glu_product(h: jnp.ndarray, activation: Optional[Callable]):
    """``[up | gate]`` pre-activations -> ``act(gate) * up``."""
    n = h.shape[1] // 2
    g = h[:, n:] if activation is None else activation(h[:, n:])
    return g * h[:, :n]


def make_fused_forward(
    layers: Sequence[BSRLayer],
    flat: FlatSchedule,
    activations: Sequence[Optional[Callable]],
    backend: str,
    jit: bool = True,
    gate: bool = False,
) -> Callable:
    """Whole-network fused forward over one ``FlatSchedule``.

    ``pallas``/``interpret``: a single ``bsr_megakernel`` dispatch — one grid
    for all layers, hidden state in VMEM end to end.  ``jnp``: the identical
    flat arrays consumed segment-by-segment.

    ``gate`` turns on runtime tile-occupancy gating: every segment's gather
    (jnp) or grid step (megakernel) is predicated on its input tile holding
    any nonzero activation, skipping work that would contribute exactly
    zero — outputs stay bit-identical to the ungated forward.

    A gated block (``flat.glu``; ``layers`` gate, up, down) runs as one
    megakernel call, or as the pair segment and down on ``jnp``; its
    ``activations[0]`` is the gate's.
    """
    layers = list(layers)
    activations = list(activations)
    if not flat.glu:
        _check_fusible_activations(activations)
    act = activations[0] if len(activations) > 1 else None
    fact = activations[-1]

    if backend == "jnp":
        bs = flat.block
        segs = _flat_segments(layers, flat, activations)

        def forward_jnp(x):
            h = x
            for k, (rows, cols, blocks, scales, bias, gi, go, a) in \
                    enumerate(segs):
                occ = tile_occupancy(h, bs, gi) if gate else None
                h = _jnp_segment(h, rows, cols, blocks, bias,
                                 bs, bs, gi, go, a, occ=occ, scales=scales)
                if flat.glu and k == 0:
                    h = _glu_product(h, act)
            return h

        return jax.jit(forward_jnp) if jit else forward_jnp

    grid_in0 = layers[0].grid_in

    def forward(x):
        B = x.shape[0]
        xp = pad_batch(x)
        kw = dict(
            n_layers=flat.n_layers,
            block=flat.block,
            grid_out_final=flat.grid_out_final,
            hidden_tiles=flat.hidden_tiles,
            activation=act,
            final_activation=fact,
            interpret=(backend == "interpret"),
        )
        kw["scales"] = flat.scales
        kw["operand"] = flat.operand
        args = (xp, flat.blocks, flat.rows, flat.cols, flat.first,
                flat.last, flat.layer_id, flat.hbm_row, flat.out_tile,
                flat.bias_idx, flat.bias_tiles)
        if gate:
            # layer-0 occupancy over the UNPADDED rows (pad rows are zero
            # anyway there, but valid_b also scopes the kernel's own
            # hidden-layer occupancy counts to real rows)
            occ0 = tile_occupancy(x, flat.block, grid_in0)
            y, _ = bsr_megakernel(*args, occ0=occ0, gate=True, valid_b=B,
                                  **kw)
        else:
            y = bsr_megakernel(*args, **kw)
        return y[:B]

    return jax.jit(forward) if jit else forward


def make_fused_measure(
    layers: Sequence[BSRLayer],
    flat: FlatSchedule,
    activations: Sequence[Optional[Callable]],
    backend: str,
    jit: bool = True,
) -> Callable:
    """Instrumented gated fused forward: ``x -> (y, occs)``.

    ``occs[k]`` ([grid_in_k] int32) is the live-row count per input tile of
    layer ``k`` — the exact counts the gated forward's predicates consumed
    (the jnp lowering recomputes them identically; the kernel lowering reads
    layer 0's from the same ``tile_occupancy`` and layers ≥ 1 from the
    megakernel's own occupancy output, so the kernel's padded-row masking is
    observable from the outside).  ``ExecutionPlan.measure_dynamic`` turns
    these into the measured dynamic I/O report.
    """
    layers = list(layers)
    activations = list(activations)
    _check_fusible_activations(activations)
    act = activations[0] if len(activations) > 1 else None
    fact = activations[-1]
    bs = flat.block

    if backend == "jnp":
        segs = _flat_segments(layers, flat, activations)

        def measure_jnp(x):
            h = x
            occs = []
            for rows, cols, blocks, scales, bias, gi, go, a in segs:
                occ = tile_occupancy(h, bs, gi)
                occs.append(occ)
                h = _jnp_segment(h, rows, cols, blocks, bias,
                                 bs, bs, gi, go, a, occ=occ, scales=scales)
            return h, tuple(occs)

        return jax.jit(measure_jnp) if jit else measure_jnp

    grid_ins = [lay.grid_in for lay in layers]

    def measure(x):
        B = x.shape[0]
        occ0 = tile_occupancy(x, bs, grid_ins[0])
        xp = pad_batch(x)
        y, occ = bsr_megakernel(
            xp, flat.blocks, flat.rows, flat.cols, flat.first, flat.last,
            flat.layer_id, flat.hbm_row, flat.out_tile, flat.bias_idx,
            flat.bias_tiles, occ0=occ0, scales=flat.scales,
            n_layers=flat.n_layers,
            block=flat.block,
            grid_out_final=flat.grid_out_final,
            hidden_tiles=flat.hidden_tiles,
            activation=act,
            final_activation=fact,
            interpret=(backend == "interpret"),
            gate=True,
            valid_b=B,
        )
        occs = (occ0,) + tuple(occ[k, :grid_ins[k + 1]]
                               for k in range(flat.n_layers - 1))
        return y[:B], occs

    return jax.jit(measure) if jit else measure


# --------------------------------------------------------------------------- #
# sharded dispatch: per-shard segments + an activation gather per boundary
# --------------------------------------------------------------------------- #

@dataclasses.dataclass
class ShardedSegment:
    """One layer's schedule arrays stacked over the model-axis shards.

    Every shard's schedule is padded to a uniform step count (``shard_map``
    needs equal per-device shapes); padded steps carry zero blocks and route
    to the sink segment (``cols == tps``), so they touch no real output tile.
    ``perm[t]`` maps the layer's canonical output tile ``t`` to its flat
    ``shard * tps + local_pos`` position in the all-gathered activation.
    """

    rows: np.ndarray          # int32 [model, n_max] input tile (full grid)
    cols: np.ndarray          # int32 [model, n_max] local output tile or sink
    blocks: np.ndarray        # [model, n_max, bm, bn] in the storage dtype
    bias: np.ndarray          # float32 [model, tps * bn]
    perm: np.ndarray          # int32 [grid_out_full]
    grid_in: int              # full input grid of this layer
    tps: int                  # output tiles per shard
    block_m: int              # input-tile size
    block_n: int              # output-tile size
    activation: Optional[Callable]
    # quantized weight stream: per-block f32 dequant scales (None for f32;
    # padded sink steps carry scale 1.0 so they dequantize to exact zero)
    scales: Optional[np.ndarray] = None   # float32 [model, n_max]


def _shard_layer(h, seg: ShardedSegment, rows, cols, blocks, bias,
                 occ=None, scales=None):
    """One shard's slice of one layer over the full gathered activation."""
    return _jnp_segment(h, rows, cols, blocks, bias, seg.block_m, seg.block_n,
                        seg.grid_in, seg.tps, seg.activation, pad_segments=1,
                        occ=occ, scales=scales)


def _reassemble(gathered, seg: ShardedSegment):
    """[model, B, tps*bn] shard outputs -> [B, full] canonical tile order."""
    m, B, _ = gathered.shape
    tiles = gathered.reshape(m, B, seg.tps, seg.block_n).transpose(0, 2, 1, 3)
    tiles = tiles.reshape(m * seg.tps, B, seg.block_n)
    tiles = jnp.take(tiles, jnp.asarray(seg.perm), axis=0)
    return tiles.transpose(1, 0, 2).reshape(B, -1)


def make_sharded_forward(
    segments: Sequence[ShardedSegment],
    model: int,
    data: int,
    jax_mesh=None,
    base_forward: Optional[Callable] = None,
    jit: bool = True,
    gate: bool = False,
) -> Callable:
    """Collective forward over a model×data mesh: x [B, n_in] -> [B, n_out].

    Per layer, each model shard computes its owned output tiles from the
    full (gathered) previous activation, then an all-gather + tile
    permutation reassembles the full hidden state for the next layer.  The
    batch dim is split over ``data`` (``B`` must be divisible by it — the
    plan wrapper pads).

    Lowering: through :func:`repro.compat.shard_map` when ``jax_mesh`` is
    given (one device per mesh slot), else a sequential jnp loop over the
    shard index on this host — the same segment arithmetic, so the two
    lowerings agree bitwise.  A 1-shard model axis does not re-derive
    anything: the per-device body is ``base_forward`` — the very forward the
    unsharded plan builders produced — which is what makes the single-device
    path the 1×1-mesh special case rather than a parallel code path.

    With ``gate=True`` and ``model > 1`` the forward takes ``(x, valid)``:
    ``valid`` ([B] bool) marks the real batch rows, because the sharded plan
    pads the batch to the data-axis multiple *outside* this trace, and
    occupancy must be computed over real rows only.  Every shard computes
    the same occupancy from the same gathered activation, so gating composes
    with per-shard schedules without any extra collective.  (``model == 1``
    keeps the ``(x)`` signature: the base forward gates internally.)
    """
    if model == 1 and base_forward is None:
        raise ValueError("model=1 requires the base (unsharded) forward")

    if model == 1:
        if jax_mesh is None:
            return jax.jit(base_forward) if jit else base_forward
        from jax.sharding import PartitionSpec as P

        fn = compat_shard_map(base_forward, jax_mesh,
                              in_specs=P("data", None),
                              out_specs=P("data", None))
        return jax.jit(fn) if jit else fn

    segments = list(segments)
    quant = any(seg.scales is not None for seg in segments)
    stride = 5 if quant else 4
    arrs = []
    for seg in segments:
        arrs.extend([seg.rows, seg.cols, seg.blocks, seg.bias])
        if quant:
            arrs.append(seg.scales)

    if jax_mesh is None:
        arrs = [jnp.asarray(a) for a in arrs]
    else:
        from jax.sharding import NamedSharding, PartitionSpec as P

        # place each shard's slice on its own device once, here, instead of
        # shipping the whole stack from one device on every call
        per_shard = NamedSharding(jax_mesh, P("model"))
        arrs = [jax.device_put(a, per_shard) for a in arrs]

        def device_fn(x, valid, *flat):
            h = x
            for k, seg in enumerate(segments):
                vals = flat[stride * k:stride * k + stride]
                rows, cols, blocks, bias = vals[:4]
                scales = vals[4][0] if quant else None
                occ = tile_occupancy(h, seg.block_m, seg.grid_in,
                                     valid=valid) if gate else None
                y = _shard_layer(h, seg, rows[0], cols[0], blocks[0],
                                 bias[0], occ=occ, scales=scales)
                g = jax.lax.all_gather(y, "model")
                h = _reassemble(g, seg)
            return h

        if gate:
            fn = compat_shard_map(
                device_fn, jax_mesh,
                in_specs=(P("data", None), P("data"))
                + (P("model"),) * len(arrs),
                out_specs=P("data", None),
            )

            def forward(x, valid):
                return fn(x, valid, *arrs)
        else:
            def device_fn_ungated(x, *flat):
                return device_fn(x, None, *flat)

            fn = compat_shard_map(
                device_fn_ungated, jax_mesh,
                in_specs=(P("data", None),) + (P("model"),) * len(arrs),
                out_specs=P("data", None),
            )

            def forward(x):
                return fn(x, *arrs)

        return jax.jit(forward) if jit else forward

    def forward_loop(x, valid=None):
        h = x
        for k, seg in enumerate(segments):
            vals = arrs[stride * k:stride * k + stride]
            rows, cols, blocks, bias = vals[:4]
            scales = vals[4] if quant else None
            # one occupancy per layer: every shard reads the same gathered
            # activation, so the mask is shared across the shard loop
            occ = tile_occupancy(h, seg.block_m, seg.grid_in,
                                 valid=valid) if gate else None
            ys = [_shard_layer(h, seg, rows[s], cols[s], blocks[s], bias[s],
                               occ=occ,
                               scales=None if scales is None else scales[s])
                  for s in range(model)]
            h = _reassemble(jnp.stack(ys), seg)
        return h

    if not gate:
        def forward_ungated(x):
            return forward_loop(x)
        return jax.jit(forward_ungated) if jit else forward_ungated
    return jax.jit(forward_loop) if jit else forward_loop
