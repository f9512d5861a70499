"""A gated unit, ``y = (act(x Wg + bg) * (x Wu + bu)) Wd + bd``, through the
engine against the plain reference ``kernels.ref.glu_ffn_ref``.

At 256 -> 2 x 512 -> 256 with 128-tiles (2 input, 4 hidden, 2 output
tiles), seeded weights, on the CPU backends: the ``jnp`` segment pass and
the megakernel in interpret mode, f32 and bf16 weight streams, with and
without biases, batches the kernel pads, a hidden tile no kept pair
reaches, and the plan set's buckets.  Then the block DAG and its
Theorem-1 order, the joint pruning, and the paths that refuse a gated
block.

Tolerances, over the reference's absmax:
  * f32 weights: 1e-5 — the same f32 products summed in another order
    (the schedule's block order, the kernel's tile accumulators);
  * bf16 weights: 1e-2 — each weight rounded to bf16 (8-bit mantissa, a
    relative error up to 2^-9) over sums of 256 to 512 products reads
    about 3e-3 here.
Dropping the gate's activation or the product moves the output by far
more than either (``test_the_tolerances_catch_a_dropped_gate_or_product``).
"""

import jax
import numpy as np
import pytest

from repro.core.blocksparse import GLUBlock, to_block_ffnn, to_bsr
from repro.engine import Engine, Mesh
from repro.kernels import ref
from repro.serving import BucketedPlanSet
from repro.sparse import prune_glu

N_IN, N_HID, BS = 256, 512, 128
TOL = {"f32": 1e-5, "bf16": 1e-2}
BACKENDS = ("jnp", "interpret")


def _dense(seed, bias=True):
    rng = np.random.default_rng(seed)
    ws = [0.05 * rng.standard_normal(s).astype(np.float32)
          for s in ((N_IN, N_HID), (N_IN, N_HID), (N_HID, N_IN))]
    bs = [(0.1 * rng.standard_normal(n) if bias else np.zeros(n)).astype(
        np.float32) for n in (N_HID, N_HID, N_IN)]
    return ws, bs


def _unreached_block(seed, bias=True):
    """Gate and up keep every (input, hidden) tile pair but those of hidden
    tile 2; down keeps all its tiles, so the output reads that tile's
    patched value ``act(bg) * bu``, made large enough to show."""
    ws, bs = _dense(seed, bias)
    if bias:
        for b in bs[:2]:
            b[2 * BS:3 * BS] += 1.0
    mask = np.ones((N_IN // BS, N_HID // BS), bool)
    mask[:, 2] = False
    block = GLUBlock(gate=to_bsr(ws[0], BS, BS, bias=bs[0], mask=mask),
                     up=to_bsr(ws[1], BS, BS, bias=bs[1], mask=mask),
                     down=to_bsr(ws[2], BS, BS, bias=bs[2]))
    return block, bs


def _reference(block, biases, x, activation=jax.nn.silu):
    dense = [lay.to_dense() for lay in block.layers]
    return np.asarray(ref.glu_ffn_ref(x, dense, biases, activation))


def _rel_err(y, want):
    return float(np.max(np.abs(np.asarray(y) - want)) / np.max(np.abs(want)))


def _x(seed, rows):
    return np.random.default_rng([seed, 7]).standard_normal(
        (rows, N_IN)).astype(np.float32)


@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no-bias"])
@pytest.mark.parametrize("wdt", sorted(TOL))
@pytest.mark.parametrize("backend", BACKENDS)
def test_gated_block_matches_the_reference(backend, wdt, bias):
    ws, bs = _dense(3, bias)
    block = prune_glu(ws, bs, 0.5, BS)
    plan = Engine(backend=backend, activation="silu",
                  weight_dtype=wdt).compile(block)
    assert plan.fused and plan.fallback_reason is None
    assert plan.flat.glu and plan.flat.n_layers == 2
    x = _x(3, 5)                       # the kernel pads 5 rows to 8
    assert _rel_err(plan(x), _reference(block, bs, x)) <= TOL[wdt]


@pytest.mark.parametrize("wdt", sorted(TOL))
@pytest.mark.parametrize("backend", BACKENDS)
def test_a_hidden_tile_no_pair_reaches_is_patched(backend, wdt):
    block, bs = _unreached_block(4)
    plan = Engine(backend=backend, activation="silu",
                  weight_dtype=wdt).compile(block)
    assert plan.flat.patched_steps == 2            # one gate/up pair
    assert plan.flat.glu_pairs == 2 * 3 + 1
    x = _x(4, 3)
    want = _reference(block, bs, x)
    assert _rel_err(plan(x), want) <= TOL[wdt]
    # the patched tile's value reaches the output: without it the answer
    # would differ by more than the tolerance
    no_patch = GLUBlock(block.gate, block.up, to_bsr(
        block.down.to_dense() * (np.arange(N_HID) // BS != 2)[:, None],
        BS, BS, bias=bs[2]))
    assert _rel_err(_reference(no_patch, bs, x), want) > 10 * TOL[wdt]


@pytest.mark.parametrize("backend", BACKENDS)
def test_gated_block_through_the_plan_set(backend):
    ws, bs = _dense(5)
    block = prune_glu(ws, bs, 0.5, BS)
    plans = BucketedPlanSet.compile(
        block, engine=Engine(backend=backend, activation="silu",
                             weight_dtype="bf16"), max_batch=8)
    assert (plans.n_in, plans.n_out) == (N_IN, N_IN)
    x = _x(5, 13)                      # a full chunk of 8, then 5 in 8
    y = plans(x)
    assert y.shape == (13, N_IN)
    assert _rel_err(y, _reference(block, bs, x)) <= TOL["bf16"]
    assert plans.bucket_calls[8] == 2


def test_fp8_keeps_a_scale_per_gate_up_and_down_block():
    """Each gate, up and down block carries its own dequant scale, paired
    with its step; both backends dequantize to the same values."""
    ws, bs = _dense(13)
    block = prune_glu(ws, bs, 0.5, BS)
    plans = {b: Engine(backend=b, activation="silu",
                       weight_dtype="fp8").compile(block) for b in BACKENDS}
    flat = plans["jnp"].flat
    gate, up, down = plans["jnp"].schedules
    np.testing.assert_array_equal(np.asarray(flat.scales), np.concatenate([
        np.stack([gate.scales, up.scales], axis=1).ravel(), down.scales]))
    real = np.asarray(flat.scales)[np.asarray(flat.scales) != 1.0]
    assert len(set(real.tolist())) == len(real) == \
        flat.nnz - flat.patched_steps      # patch blocks dequantize by 1
    x = _x(13, 4)
    y = {b: np.asarray(p(x)) for b, p in plans.items()}
    np.testing.assert_allclose(y["interpret"], y["jnp"], rtol=0, atol=1e-5)
    # e4m3 keeps 3 mantissa bits: a relative error up to 2^-4 per weight
    assert _rel_err(y["jnp"], _reference(block, bs, x)) <= 0.1


def test_the_tolerances_catch_a_dropped_gate_or_product():
    ws, bs = _dense(6)
    block = prune_glu(ws, bs, 0.5, BS)
    x = _x(6, 8)
    want = _reference(block, bs, x)
    dense = [lay.to_dense() for lay in block.layers]
    linear_gate = _reference(block, bs, x, activation=None)
    up_only = (x @ dense[1] + bs[1]) @ dense[2] + bs[2]
    gate_only = jax.nn.silu(x @ dense[0] + bs[0]) @ dense[2] + bs[2]
    for wrong in (linear_gate, up_only, np.asarray(gate_only)):
        assert _rel_err(wrong, want) > 10 * max(TOL.values())


def test_prune_glu_keeps_one_joint_mask():
    ws, bs = _dense(7)
    block = prune_glu(ws, bs, 0.25, BS)
    g, u, d = block.layers
    np.testing.assert_array_equal(g.rows, u.rows)
    np.testing.assert_array_equal(g.cols, u.cols)
    gi, gh = N_IN // BS, N_HID // BS
    assert g.nnz_blocks == round(0.25 * gi * gh)
    assert d.nnz_blocks == round(0.25 * gh * gi)

    def mass(w):
        t = w.reshape(w.shape[0] // BS, BS, w.shape[1] // BS, BS)
        return np.sqrt((t.astype(np.float64) ** 2).sum(axis=(1, 3)))

    joint = np.hypot(mass(ws[0]), mass(ws[1]))
    kept = np.zeros_like(joint, bool)
    kept[g.rows, g.cols] = True
    assert joint[kept].min() > joint[~kept].max()
    np.testing.assert_array_equal(g.to_dense() != 0,
                                  np.repeat(np.repeat(kept, BS, 0), BS, 1))


def test_block_dag_pairs_gate_and_up_in_theorem1_order():
    block = prune_glu(*_dense(8), 0.5, BS)
    bffnn = to_block_ffnn(block)
    assert bffnn.glu and bffnn.layers == block.layers
    gi, gh = N_IN // BS, N_HID // BS
    net = bffnn.net
    assert net.N == gi + gh + N_IN // BS and net.W == 3 * block.gate.nnz_blocks
    order = net.theorem1_order()
    assert net.is_topological_connection_order(order)
    lay = bffnn.conn_layer[order]
    pairs = order[:2 * block.gate.nnz_blocks]
    # every hidden tile's connections are contiguous, gate then up of one
    # input tile side by side, and down after all of them
    assert (lay[:len(pairs)] == np.tile([0, 1], len(pairs) // 2)).all()
    assert (lay[len(pairs):] == 2).all()
    src, dst = net.src[pairs], net.dst[pairs]
    assert (src[0::2] == src[1::2]).all() and (dst[0::2] == dst[1::2]).all()
    assert (np.diff(dst) >= 0).all()
    np.testing.assert_array_equal(bffnn.conn_block[pairs][0::2],
                                  bffnn.conn_block[pairs][1::2])


def test_gate_and_up_must_share_a_mask():
    ws, bs = _dense(9)
    block = prune_glu(ws, bs, 0.5, BS)
    up = to_bsr(ws[1], BS, BS, density=0.25, bias=bs[1])
    with pytest.raises(ValueError, match="share one tile mask"):
        GLUBlock(block.gate, up, block.down)


@pytest.mark.parametrize("path,engine,kw,match", [
    ("layered", dict(fuse=False), {}, "layered per-layer path"),
    ("gated", dict(gate=True), {}, r"occupancy gating \(gate=True\)"),
    ("sharded", {}, dict(mesh=Mesh(1, 1)), "sharded plans"),
    ("per-layer activations", dict(activation=["silu", "silu"]), {},
     "one activation"),
])
def test_paths_without_a_gated_block_refuse_it(path, engine, kw, match):
    block = prune_glu(*_dense(10), 0.5, BS)
    with pytest.raises(ValueError, match=match) as err:
        Engine(backend="jnp", **{"activation": "silu", **engine}).compile(
            block, **kw)
    assert "gated (GLU) block" in str(err.value)


def test_the_plan_store_refuses_a_gated_block(tmp_path):
    from repro.serving.plancache import PlanStore
    block = prune_glu(*_dense(11), 0.5, BS)
    with pytest.raises(ValueError, match="gated \\(GLU\\) block"):
        BucketedPlanSet.compile(block, engine=Engine(backend="jnp"),
                                max_batch=2,
                                plan_store=PlanStore(str(tmp_path)))


def test_the_pack_counts_reach_the_plan_attributes():
    from repro.obs import Tracer, plan_io_attrs
    block, _ = _unreached_block(12)
    tracer = Tracer()
    plan = Engine(backend="jnp", activation="silu",
                  tracer=tracer).compile(block)
    (pack,) = [s for s in tracer.spans() if s.name == "compile.pack"]
    assert pack.attrs["glu_pairs"] == plan.flat.glu_pairs == 7
    assert pack.attrs["patched_steps"] == plan.flat.patched_steps == 2
    attrs = plan_io_attrs(plan)
    assert (attrs["glu_pairs"], attrs["patched_steps"]) == (7, 2)
    assert "(GLU)" in plan.describe()
