"""Pure-jnp oracles for the Pallas kernels (the correctness ground truth)."""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np


def bsr_to_dense(rows, cols, blocks, grid_in: int, grid_out: int) -> jnp.ndarray:
    """Scatter BSR blocks into the dense [n_in, n_out] weight matrix."""
    bm, bn = blocks.shape[1], blocks.shape[2]
    w = jnp.zeros((grid_in * bm, grid_out * bn), dtype=blocks.dtype)
    for r, c, b in zip(np.asarray(rows), np.asarray(cols), blocks):
        w = w.at[int(r) * bm:(int(r) + 1) * bm, int(c) * bn:(int(c) + 1) * bn].set(b)
    return w


def bsr_matmul_ref(
    x: jnp.ndarray,
    rows: np.ndarray,
    cols: np.ndarray,
    blocks: jnp.ndarray,
    bias: jnp.ndarray,
    grid_in: int,
    grid_out: int,
    activation: Optional[Callable] = None,
) -> jnp.ndarray:
    """Oracle: y = act(x @ dense(W) + b), accumulated in float32.

    ``Precision.HIGHEST`` keeps the dot in full float32 on every backend: a
    TPU runs a default-precision f32 dot through bf16 passes."""
    w = bsr_to_dense(rows, cols, blocks, grid_in, grid_out)
    y = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
    y = y + bias.astype(jnp.float32)
    if activation is not None:
        y = activation(y)
    return y.astype(x.dtype)


def moe_gemm_ref(
    x: jnp.ndarray,          # [tokens, d]
    w_up: jnp.ndarray,       # [experts, d, f]
    w_down: jnp.ndarray,     # [experts, f, d]
    assign: jnp.ndarray,     # [tokens, k] expert ids
    gates: jnp.ndarray,      # [tokens, k]
    activation: Callable,
) -> jnp.ndarray:
    """Oracle for the grouped expert FFN: sum_k g_k * FFN_{e_k}(x)."""
    x32 = x.astype(jnp.float32)
    out = jnp.zeros_like(x32)
    for k in range(assign.shape[1]):
        e = assign[:, k]
        up = jnp.einsum("td,tdf->tf", x32, w_up.astype(jnp.float32)[e])
        h = activation(up)
        dn = jnp.einsum("tf,tfd->td", h, w_down.astype(jnp.float32)[e])
        out = out + gates[:, k:k + 1].astype(jnp.float32) * dn
    return out.astype(x.dtype)
