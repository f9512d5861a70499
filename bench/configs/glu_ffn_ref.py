"""Plain float32 reference of a block-magnitude-pruned gated FFN block.

    y = (act(x @ Wg + bg) * (x @ Wu + bu)) @ Wd + bd

Gate and up keep one set of ``block x block`` tiles, the top ``density``
share of (input tile, hidden tile) positions by their joint mass
``sqrt(|G_ij|^2 + |U_ij|^2)``; down keeps its own top share by Frobenius
mass.  Every product runs at ``Precision.HIGHEST`` (on a TPU the default
precision rounds f32 operands to bf16).  It imports nothing of the
program: it prunes the benchmark's dense matrices by its own code, so a
program that kept other blocks than the configuration says differs from
it.  ``prune``, ``forward`` and ``max_err_over_absmax`` take what those of
``sparse_ffn_ref.py`` take.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

ACTIVATIONS = {"silu": jax.nn.silu}


def tile_mass(w: np.ndarray, block: int) -> np.ndarray:
    """Frobenius mass of each ``block x block`` tile, float64."""
    gi, go = w.shape[0] // block, w.shape[1] // block
    tiles = w.reshape(gi, block, go, block).astype(np.float64)
    return np.sqrt(np.einsum("ibjc,ibjc->ij", tiles, tiles))


def top_share(mass: np.ndarray, density: float) -> np.ndarray:
    """Boolean mask of the top ``density`` share of tiles by ``mass``."""
    k = max(1, int(round(density * mass.size)))
    return mass >= np.sort(mass, axis=None)[-k]


def masks(config: dict, weights: Sequence[np.ndarray]) -> List[np.ndarray]:
    """The tiles kept of ``Wg``, ``Wu`` (one mask) and ``Wd``."""
    block, density = config["block"], config["density"]
    wg, wu, wd = weights
    joint = np.sqrt(tile_mass(wg, block) ** 2 + tile_mass(wu, block) ** 2)
    pair = top_share(joint, density)
    return [pair, pair, top_share(tile_mass(wd, block), density)]


def prune(config: dict, weights: Sequence[np.ndarray]
          ) -> Tuple[List[np.ndarray], int]:
    """``[Wg, Wu, Wd]`` with every dropped tile zeroed, and the number of
    tiles kept over the three."""
    block = config["block"]
    out, nnz = [], 0
    for w, keep in zip(weights, masks(config, weights)):
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
        (wg, wu, wd), (bg, bu, bd) = ws, bs
        gate = act(jnp.dot(x, wg, precision=hi) + bg)
        up = jnp.dot(x, wu, precision=hi) + bu
        return jnp.dot(gate * up, wd, precision=hi) + bd

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
