"""Compile the main-path kernels for a described TPU v5e chip.

No chip is needed: the TPU compiler that ships with jaxlib compiles for a
topology that is described, not attached.  This catches what interpret mode
cannot — block shapes the Mosaic lowering refuses and kernels that need more
VMEM than the chip allows — at the BERT-large encoder FFN widths
(1024 -> 4096 -> 1024, block 128, gelu) with the server's largest bucket
(B = 32).  Nothing runs, so nothing here says anything about results or
times.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.bsr_matmul import bsr_matmul, bsr_megakernel
from repro.kernels.ops import BF16_DTYPE, FP8_DTYPE

N_IN, N_HID, N_OUT, BS, B = 1024, 4096, 1024, 128, 32
# scheduled steps of the density-0.1 net, rounded up (26 + 26 blocks plus
# bias-patch steps); only the grid length depends on it
NNZ = 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs go to /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip; keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _megakernel_case(sh, wdt, gate):
    i32 = jnp.int32
    kw = dict(n_layers=2, block=BS, grid_out_final=N_OUT // BS,
              hidden_tiles=N_HID // BS, activation=jax.nn.gelu,
              final_activation=None, gate=gate, valid_b=B if gate else 0)
    # x, blocks, the eight schedule arrays, bias tiles
    args = [_spec((B, N_IN), jnp.float32, sh),
            _spec((NNZ, BS, BS), wdt, sh),
            *[_spec((NNZ,), i32, sh) for _ in range(8)],
            _spec(((N_HID + N_OUT) // BS, BS), jnp.float32, sh)]
    extra = {"occ0": _spec((N_IN // BS,), i32, sh),
             "scales": _spec((NNZ,), jnp.float32, sh)}
    names = (["occ0"] if gate else []) + \
        (["scales"] if wdt != jnp.float32 else [])
    n = len(args)

    def fn(*a):
        return bsr_megakernel(*a[:n], **dict(zip(names, a[n:])), **kw)

    return fn, args + [extra[k] for k in names]


def _layered_case(sh, wdt):
    i32 = jnp.int32
    args = [_spec((B, N_IN), jnp.float32, sh),
            _spec((NNZ, BS, BS), wdt, sh),
            *[_spec((NNZ,), i32, sh) for _ in range(4)],
            _spec((N_HID,), jnp.float32, sh),
            _spec((NNZ,), jnp.float32, sh)]

    def fn(x, blocks, rows, cols, first, last, bias, scales):
        return bsr_matmul(x, blocks, rows, cols, first, last, bias,
                          grid_out=N_HID // BS, activation=jax.nn.gelu,
                          scales=scales)

    return fn, args


CASES = {
    "megakernel-f32": lambda sh: _megakernel_case(sh, jnp.float32, False),
    "megakernel-f32-gated": lambda sh: _megakernel_case(sh, jnp.float32, True),
    "megakernel-bf16": lambda sh: _megakernel_case(sh, BF16_DTYPE, False),
    "megakernel-fp8": lambda sh: _megakernel_case(sh, FP8_DTYPE, False),
    "layered-bf16": lambda sh: _layered_case(sh, BF16_DTYPE),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_main_path_kernel_compiles_for_v5e(one_chip, case):
    fn, args = CASES[case](one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
