"""Scheduled block-sparse matmul — the paper's contribution as a TPU kernel.

``y = act(x @ W + b)`` where W is block-sparse (BSR).  The Pallas grid *is* the
paper's topological order of the connections: one grid step per nonzero weight
block, executed in the (reordered) schedule produced by
``repro.core.blocksparse.schedule_arrays``.

I/O behaviour (the paper's model realized in hardware):
  * the weight block of step g streams HBM->VMEM exactly once        (W reads);
  * the input tile x[:, rows[g]] is fetched only when ``rows[g]`` differs from
    ``rows[g-1]`` — Pallas keeps the block in VMEM across grid steps whose
    index_map result is unchanged                     (input-neuron reads);
  * the f32 accumulator tile lives in VMEM scratch for the *contiguous* run of
    steps sharing ``cols[g]`` (Theorem-1 grouped order), is written back once
    per output tile                                   (writes = S exactly).

The schedule MUST be contiguous-by-output (checked in ops.py) — that is
precisely the Theorem-1 2-optimal family the paper proves sufficient; within
it, Connection Reordering minimizes the input-tile re-fetches.

Scalar-prefetch arrays feed the index maps:
  rows[g], cols[g] — input/output tile of step g,
  first[g]         — 1 iff step g is the first visiting its output tile
                     (zero-initialize the accumulator),
  last[g]          — 1 iff step g is the last (add bias, activate, emit).
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import tpu_compiler_params


def _kernel(
    # scalar prefetch
    rows_ref, cols_ref, first_ref, last_ref,
    # inputs: x, w, bias [, scale when quant] / outputs / scratch
    x_ref, w_ref, b_ref,
    *rest,
    activation: Optional[Callable],
    quant: bool,
):
    if quant:
        s_ref, o_ref, acc_ref = rest
    else:
        o_ref, acc_ref = rest
    g = pl.program_id(0)

    @pl.when(first_ref[g] == 1)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # dequant fused right before the dot: the block streamed HBM->VMEM in
    # the narrow dtype; only the VMEM-resident copy is widened
    w = w_ref[0]
    if quant:
        w = w.astype(jnp.float32) * s_ref[g]
    acc_ref[...] += jnp.dot(
        x_ref[...], w, preferred_element_type=jnp.float32
    )

    @pl.when(last_ref[g] == 1)
    def _emit():
        y = acc_ref[...] + b_ref[...].astype(jnp.float32)
        if activation is not None:
            y = activation(y)
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("grid_out", "activation", "interpret"),
)
def bsr_matmul(
    x: jnp.ndarray,        # [B, n_in]
    blocks: jnp.ndarray,   # [nnz, bm, bn] scheduled order
    rows: jnp.ndarray,     # int32 [nnz]
    cols: jnp.ndarray,     # int32 [nnz]
    first: jnp.ndarray,    # int32 [nnz]
    last: jnp.ndarray,     # int32 [nnz]
    bias: jnp.ndarray,     # [n_out]
    grid_out: int,
    activation: Optional[Callable] = None,
    interpret: bool = False,
    scales: Optional[jnp.ndarray] = None,  # f32 [nnz] dequant (quantized)
) -> jnp.ndarray:
    """Run the scheduled BSR matmul.  See module docstring for the schedule contract."""
    B, n_in = x.shape
    nnz, bm, bn = blocks.shape
    n_out = grid_out * bn
    if n_in % bm:
        raise ValueError("n_in must be a multiple of the block size")
    quant = scales is not None

    in_specs = [
        # input tile: revisits keep it in VMEM while rows[g] is unchanged
        pl.BlockSpec((B, bm), lambda g, rows, cols, first, last: (0, rows[g])),
        # weight block: streamed, one per step
        pl.BlockSpec((1, bm, bn), lambda g, rows, cols, first, last: (g, 0, 0)),
        # bias tile of the current output tile
        pl.BlockSpec((1, bn), lambda g, rows, cols, first, last: (0, cols[g])),
    ]
    if quant:
        # per-block dequant scales: the whole [nnz] f32 vector SMEM-resident
        # (a (1, 1) block of an [nnz, 1] array breaks the TPU (8, 128)
        # block-shape rule); step g reads ``s_ref[g]``
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(nnz,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (B, bn), lambda g, rows, cols, first, last: (0, cols[g])
        ),
        scratch_shapes=[pltpu.VMEM((B, bn), jnp.float32)],
    )
    fn = pl.pallas_call(
        functools.partial(_kernel, activation=activation, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, n_out), x.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )
    args = (rows, cols, first, last, x, blocks, bias.reshape(1, -1))
    if quant:
        args += (scales,)
    return fn(*args)


# --------------------------------------------------------------------------- #
# the whole-network megakernel
# --------------------------------------------------------------------------- #

def _megakernel(
    # scalar prefetch (``occ0_ref`` is appended when gating is on)
    layer_ref, rows_ref, cols_ref, first_ref, last_ref,
    hbm_row_ref, out_tile_ref, bias_idx_ref,
    # inputs / outputs / scratch (layout depends on ``gate``; see below)
    *rest,
    n_layers: int,
    activation: Optional[Callable],
    final_activation: Optional[Callable],
    gate: bool,
    quant: bool,
    valid_b: int,
):
    """One grid step per nonzero block of ANY layer, in whole-net Theorem-1
    order.  The hidden state ping-pongs between two VMEM buffers across layer
    boundaries (layer k reads h[(k-1) % 2], writes h[k % 2]); activations
    never touch HBM between layers.  Weight blocks stream through the Pallas
    pipeline, which double-buffers the ``w_ref`` fetch of step g+1 behind the
    multiply of step g.

    With ``gate=True`` the kernel additionally predicates every
    multiply-accumulate on runtime tile occupancy: a step whose input tile
    holds no nonzero activation in any of the first ``valid_b`` batch rows
    skips its dot (the skipped contribution is exactly ±0, so outputs are
    bit-identical) while everything else — accumulator init, epilogues, the
    streamed ``w_ref`` fetch of the next step — proceeds unchanged, so the
    double-buffered weight pipeline never stalls.  Layer-0 occupancy arrives
    precomputed as the ``occ0_ref`` scalar-prefetch array; hidden-layer
    occupancy is produced *by the kernel itself*: each non-final epilogue
    counts the valid rows with a nonzero in the tile it just activated and
    records the count in the ``occ_ref`` output (constant index map, so the
    buffer is readable across grid steps — the flat schedule guarantees all
    of layer k's epilogues precede any layer k+1 step).  Rows past
    ``valid_b`` are engine batch padding and are excluded from the counts:
    non-odd activation epilogues (sigmoid-style) turn padded zero rows
    nonzero, which must not make a dead tile look live in the measured
    occupancy.

    With ``quant=True`` the streamed ``w_ref`` block is stored in a narrow
    dtype (bf16/fp8) and an extra ``s_ref`` input carries every block's f32
    scale as one SMEM-resident vector, read at ``s_ref[g]``; dequant
    (``astype(f32) * scale``) is fused right before the dot, so only the
    VMEM-resident copy is ever widened — HBM traffic stays at the narrow
    width."""
    if gate and quant:
        (occ0_ref, x_ref, w_ref, b_ref, s_ref, o_ref, occ_ref,
         acc_ref, h0_ref, h1_ref) = rest
    elif gate:
        (occ0_ref, x_ref, w_ref, b_ref, o_ref, occ_ref,
         acc_ref, h0_ref, h1_ref) = rest
    elif quant:
        x_ref, w_ref, b_ref, s_ref, o_ref, acc_ref, h0_ref, h1_ref = rest
    else:
        x_ref, w_ref, b_ref, o_ref, acc_ref, h0_ref, h1_ref = rest
    g = pl.program_id(0)
    lid = layer_ref[g]
    r = rows_ref[g]
    w = w_ref[0]
    if quant:
        w = w.astype(jnp.float32) * s_ref[g]

    @pl.when(first_ref[g] == 1)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if gate:
        # occupancy of this step's input tile (clamped reads: the occ0 /
        # occ_ref rows not addressed by this layer are never selected)
        alive = occ0_ref[jnp.minimum(r, occ0_ref.shape[0] - 1)] > 0
        if n_layers > 1:
            prev = occ_ref[jnp.maximum(lid - 1, 0),
                           jnp.minimum(r, occ_ref.shape[1] - 1)]
            alive = jnp.where(lid == 0, alive, prev > 0)
    else:
        alive = True

    # multiply-accumulate from this step's input tile (skipped when gating
    # proves the tile dead — the contribution would be exactly zero)
    @pl.when((lid == 0) & alive)
    def _from_hbm():
        acc_ref[...] += jnp.dot(
            x_ref[...], w, preferred_element_type=jnp.float32
        )

    if n_layers > 1:
        @pl.when((lid > 0) & (lid % 2 == 1) & alive)
        def _from_h0():
            acc_ref[...] += jnp.dot(
                h0_ref[r], w, preferred_element_type=jnp.float32
            )

        @pl.when((lid > 0) & (lid % 2 == 0) & alive)
        def _from_h1():
            acc_ref[...] += jnp.dot(
                h1_ref[r], w, preferred_element_type=jnp.float32
            )

    # epilogue on the last visit of the current output tile
    is_final = lid == n_layers - 1

    @pl.when((last_ref[g] == 1) & is_final)
    def _emit():
        y = acc_ref[...] + b_ref[0].astype(jnp.float32)
        if final_activation is not None:
            y = final_activation(y)
        o_ref[...] = y.astype(o_ref.dtype)

    if n_layers > 1:
        c = cols_ref[g]

        def _stash(h_ref):
            y = acc_ref[...] + b_ref[0].astype(jnp.float32)
            if activation is not None:
                y = activation(y)
            h_ref[c] = y
            if gate:
                row = jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
                live = jnp.any((y != 0.0) & (row < valid_b),
                               axis=1, keepdims=True)
                occ_ref[lid, c] = jnp.sum(live.astype(jnp.int32))

        @pl.when((last_ref[g] == 1) & ~is_final & (lid % 2 == 0))
        def _stash_h0():
            _stash(h0_ref)

        @pl.when((last_ref[g] == 1) & ~is_final & (lid % 2 == 1))
        def _stash_h1():
            _stash(h1_ref)


@functools.partial(
    jax.jit,
    static_argnames=("n_layers", "block", "grid_out_final", "hidden_tiles",
                     "activation", "final_activation", "interpret",
                     "gate", "valid_b"),
)
def bsr_megakernel(
    x: jnp.ndarray,           # [B, n_in]
    blocks: jnp.ndarray,      # [nnz_total, bs, bs] flat scheduled order
    rows: jnp.ndarray,        # int32 [nnz_total] layer-local input tile
    cols: jnp.ndarray,        # int32 [nnz_total] layer-local output tile
    first: jnp.ndarray,       # int32 [nnz_total]
    last: jnp.ndarray,        # int32 [nnz_total]
    layer_id: jnp.ndarray,    # int32 [nnz_total]
    hbm_row: jnp.ndarray,     # int32 [nnz_total] x-BlockSpec index
    out_tile: jnp.ndarray,    # int32 [nnz_total] out-BlockSpec index
    bias_idx: jnp.ndarray,    # int32 [nnz_total] bias-tile index
    bias_tiles: jnp.ndarray,  # [total_out_tiles, bs]
    occ0: Optional[jnp.ndarray] = None,  # int32 [grid_in_0] (gate only)
    scales: Optional[jnp.ndarray] = None,  # f32 [nnz_total] dequant (quant)
    n_layers: int = 1,
    block: int = 0,
    grid_out_final: int = 0,
    hidden_tiles: int = 1,
    activation: Optional[Callable] = None,
    final_activation: Optional[Callable] = None,
    interpret: bool = False,
    gate: bool = False,
    valid_b: int = 0,
):
    """Run a whole multi-layer net as ONE ``pallas_call``.

    The grid is the flat cross-layer schedule (``kernels.ops.FlatSchedule``);
    see ``_megakernel`` for the VMEM residency story.  The batch dimension
    must already be padded to the sublane multiple (the engine does this).

    With ``gate=True`` the call takes ``occ0`` (the per-input-tile live-row
    counts of ``x``, over its first ``valid_b`` rows — rows past that are
    engine padding) as a ninth scalar-prefetch array and returns
    ``(y, occ)`` where ``occ[k, t]`` is the kernel-measured live-row count
    of hidden activation ``k``'s tile ``t`` — the very counts the gating
    predicates consumed, exported so dynamic I/O is measurable (and the
    padded-row exclusion testable) from outside the kernel.
    """
    B, n_in = x.shape
    nnz = blocks.shape[0]
    bs = block
    n_out = grid_out_final * bs
    if n_in % bs:
        raise ValueError("n_in must be a multiple of the block size")
    quant = scales is not None

    in_specs = [
        # input tile: only layer-0 steps move this index; afterwards it
        # is frozen, so the block stays in VMEM untouched
        pl.BlockSpec((B, bs), lambda g, *s: (0, s[5][g])),
        # weight block of step g: streamed, double-buffered by the
        # Pallas pipeline (gated no-op steps still advance it)
        pl.BlockSpec((1, bs, bs), lambda g, *s: (g, 0, 0)),
        # bias tile of the current output tile (any layer), laid out
        # [T, 1, bs] so the block's last two dims equal the array's (a
        # (1, bs) block of [T, bs] breaks the TPU (8, 128) block-shape rule)
        pl.BlockSpec((1, 1, bs), lambda g, *s: (s[7][g], 0, 0)),
    ]
    if quant:
        # per-block dequant scales: the whole [nnz] f32 vector SMEM-resident
        # across the grid; step g reads ``s_ref[g]``
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))

    # index maps take (g, *scalar_prefetch); variadic so the same lambdas
    # serve both the 8-array and the gated 9-array prefetch layout
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=9 if gate else 8,
        grid=(nnz,),
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((B, bs), lambda g, *s: (0, s[6][g])),
            # measured hidden occupancy: whole array SMEM-resident across
            # every grid step (written by epilogues, read by later layers)
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ) if gate else pl.BlockSpec((B, bs), lambda g, *s: (0, s[6][g])),
        scratch_shapes=[
            pltpu.VMEM((B, bs), jnp.float32),                  # accumulator
            pltpu.VMEM((hidden_tiles, B, bs), jnp.float32),    # hidden ping
            pltpu.VMEM((hidden_tiles, B, bs), jnp.float32),    # hidden pong
        ],
    )
    out_shape = jax.ShapeDtypeStruct((B, n_out), x.dtype)
    if gate:
        out_shape = (out_shape,
                     jax.ShapeDtypeStruct((max(1, n_layers - 1),
                                           hidden_tiles), jnp.int32))
    fn = pl.pallas_call(
        functools.partial(
            _megakernel,
            n_layers=n_layers,
            activation=activation,
            final_activation=final_activation,
            gate=gate,
            quant=quant,
            valid_b=valid_b,
        ),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )
    prefetch = (layer_id, rows, cols, first, last, hbm_row, out_tile,
                bias_idx)
    if gate:
        prefetch += (occ0,)
    args = (x, blocks, bias_tiles.reshape(bias_tiles.shape[0], 1, bs))
    if quant:
        args += (scales,)
    return fn(*prefetch, *args)
