"""Plain float32 reference of a block-magnitude-pruned FFN block.

    y = act(x @ W1 + b1) @ W2 + b2

with each W kept only on its top ``density`` share of ``block x block``
tiles by Frobenius mass, and every product at ``Precision.HIGHEST`` (on a
TPU the default precision rounds f32 operands to bf16).  It imports nothing
of the program: it prunes the benchmark's dense matrices by its own code,
so a program that kept other blocks than the configuration says differs
from it.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

ACTIVATIONS = {
    # tanh form, as the program's "gelu" epilogue; BERT itself uses the erf
    # form (noted in the configuration)
    "gelu": lambda h: jax.nn.gelu(h, approximate=True),
    # Nemotron's relu2
    "squared_relu": lambda h: jnp.square(jnp.maximum(h, 0.0)),
}


def block_mask(w: np.ndarray, block: int, density: float) -> np.ndarray:
    """Boolean ``[n_in / block, n_out / block]``: the tiles kept."""
    gi, go = w.shape[0] // block, w.shape[1] // block
    tiles = w.reshape(gi, block, go, block).astype(np.float64)
    mass = np.sqrt(np.einsum("ibjc,ibjc->ij", tiles, tiles))
    k = max(1, int(round(density * gi * go)))
    return mass >= np.sort(mass, axis=None)[-k]


def prune(config: dict, weights: Sequence[np.ndarray]
          ) -> Tuple[List[np.ndarray], int]:
    """The dense matrices with every dropped tile zeroed, and the number of
    tiles kept over all layers."""
    block, density = config["block"], config["density"]
    out, nnz = [], 0
    for w in weights:
        keep = block_mask(w, block, density)
        nnz += int(keep.sum())
        full = np.repeat(np.repeat(keep, block, axis=0), block, axis=1)
        out.append(np.where(full, w, 0.0).astype(np.float32))
    return out, nnz


def forward(config: dict, weights: Sequence[np.ndarray],
            biases: Sequence[np.ndarray], xs: np.ndarray,
            rows_per_step: int = 1024) -> np.ndarray:
    """The reference output for ``xs`` ``[n, hidden]`` through the pruned
    ``weights`` (from :func:`prune`), in blocks of rows."""
    act = ACTIVATIONS[config["activation"]]
    ws = [jnp.asarray(w) for w in weights]
    bs = [jnp.asarray(b, jnp.float32) for b in biases]
    hi = jax.lax.Precision.HIGHEST

    @jax.jit
    def step(x, ws, bs):
        h = x
        for k, (w, b) in enumerate(zip(ws, bs)):
            h = jnp.dot(h, w, precision=hi) + b
            if k < len(ws) - 1:
                h = act(h)
        return h

    out = []
    for i in range(0, len(xs), rows_per_step):
        part = xs[i:i + rows_per_step]
        pad = rows_per_step - len(part)
        y = step(jnp.asarray(np.pad(part, ((0, pad), (0, 0)))), ws, bs)
        out.append(np.asarray(y)[:len(part)])
    return np.concatenate(out)


def max_err_over_absmax(y: np.ndarray, ref: np.ndarray) -> float:
    """Widest gap to the reference over the reference's absmax."""
    return float(np.max(np.abs(y - ref)) / np.max(np.abs(ref)))
