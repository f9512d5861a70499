"""Transformer substrate: GQA attention (flash-chunked), MLPs, MoE.

Attention uses a two-level chunked online-softmax (pure-JAX flash) so the
[S, S] score matrix never materializes — required to fit 16 GB/chip at 32k
sequence length.  MoE ships two dispatch implementations:

  * ``dense``: sort/scatter dispatch under plain pjit — the baseline; SPMD
    inserts the collectives (observed as all-gathers in the dry-run HLO);
  * ``a2a``: shard_map expert-parallel dispatch with explicit all_to_all —
    the beyond-paper optimization evaluated in EXPERIMENTS.md §Perf.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.compat import axis_size, get_abstract_mesh

from .common import ACTIVATIONS, apply_rope, dense_init, rms_norm, split_keys
from .config import ModelConfig
from .sharding import div_or_none, dp, shard, tp


# =============================================================================
# bf16-wire row-parallel matmul (§Perf hillclimb B)
# =============================================================================

def row_parallel_matmul(h: jnp.ndarray, w: jnp.ndarray, cfg: ModelConfig):
    """y[B,S,d] = h[B,S,n] @ w[n,d] with n TP-sharded.

    With ``cfg.bf16_reduce`` the cross-chip partial-sum reduction happens on
    bf16 values (per-shard accumulation stays f32 inside the dot): XLA's
    default plan all-reduces the pre-downcast f32 accumulators, doubling the
    wire bytes of every row-parallel matmul — measured as 96/101 GiB of the
    collective traffic on the codeqwen train_4k cell (EXPERIMENTS.md §Perf)."""
    if not cfg.bf16_reduce or tp() is None:
        return jnp.einsum("bsn,nd->bsd", h, w)
    from repro.compat import shard_map

    mesh = get_abstract_mesh()
    if mesh is None or mesh.empty or tp() not in mesh.axis_names:
        return jnp.einsum("bsn,nd->bsd", h, w)
    tp_axis = tp()
    dp_spec = dp()

    def local(hl, wl):
        part = jnp.einsum("bsn,nd->bsd", hl, wl,
                          preferred_element_type=jnp.float32)
        return jax.lax.psum(part.astype(jnp.bfloat16), tp_axis)

    fn = shard_map(local, mesh=mesh,
                   in_specs=(P(dp_spec, None, tp_axis), P(tp_axis, None)),
                   out_specs=P(dp_spec, None, None))

    # custom VJP: the backward needs NO collective — dy is replicated over tp,
    # so dh = dy @ w^T is tp-sharded locally and dw = h^T dy is shard-local.
    # (shard_map's conservative transpose would insert a second f32 psum of
    # the cotangent, which *regressed* the collective term; see §Perf B2.)
    @jax.custom_vjp
    def rp(hh, ww):
        return fn(hh, ww).astype(hh.dtype)

    def rp_fwd(hh, ww):
        return rp(hh, ww), (hh, ww)

    def rp_bwd(res, dy):
        hh, ww = res
        dh = jnp.einsum("bsd,nd->bsn", dy, ww).astype(hh.dtype)
        dw = jnp.einsum("bsn,bsd->nd", hh, dy,
                        preferred_element_type=jnp.float32).astype(ww.dtype)
        return dh, dw

    rp.defvjp(rp_fwd, rp_bwd)
    return rp(h, w)


# =============================================================================
# int8 KV cache (§Perf hillclimb C)
# =============================================================================

def kv_quantize(x: jnp.ndarray):
    """Per-(token, head) symmetric int8: x [B,S,K,hd] -> (int8, f32 scale)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                    keepdims=True) / 127.0 + 1e-8
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.float32)


# =============================================================================
# Attention
# =============================================================================

def init_attention(key, cfg: ModelConfig, dtype=jnp.float32) -> Dict:
    d, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = split_keys(key, 4)
    return {
        "wq": dense_init(ks[0], (d, H * hd), dtype=dtype),
        "wk": dense_init(ks[1], (d, K * hd), dtype=dtype),
        "wv": dense_init(ks[2], (d, K * hd), dtype=dtype),
        "wo": dense_init(ks[3], (H * hd, d), dtype=dtype),
    }


def _flash(q, k, v, *, causal: bool, chunk: int, q_offset=0):
    """Two-level chunked attention with online softmax.

    q: [B, Sq, K, G, hd]; k, v: [B, Sk, K, hd].  Returns [B, Sq, K, G, hd].
    Scores are computed blockwise in f32; peak live score block is
    [B, K, G, cq, ck] instead of [B, H, Sq, Sk].
    """
    B, Sq, K, G, hd = q.shape
    Sk = k.shape[1]
    Sq_orig, Sk_orig = Sq, Sk
    cq = min(chunk, Sq)
    ck = min(chunk, Sk)
    if Sq % cq:
        pad = cq - Sq % cq
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
        Sq += pad
    if Sk % ck:
        pad = ck - Sk % ck
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Sk += pad
    nq, nk = Sq // cq, Sk // ck
    scale = 1.0 / np.sqrt(hd)
    qc = q.reshape(B, nq, cq, K, G, hd).transpose(1, 0, 2, 3, 4, 5)
    kc = k.reshape(B, nk, ck, K, hd)
    vc = v.reshape(B, nk, ck, K, hd)

    def q_body(_, qi_idx):
        qi, iq = qi_idx
        m0 = jnp.full((B, K, G, cq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, K, G, cq), jnp.float32)
        a0 = jnp.zeros((B, cq, K, G, hd), jnp.float32)

        def kv_body(carry, jk):
            m, l, acc = carry
            kj = jax.lax.dynamic_index_in_dim(kc, jk, 1, keepdims=False)
            vj = jax.lax.dynamic_index_in_dim(vc, jk, 1, keepdims=False)
            s = jnp.einsum("bqkgh,bskh->bkgqs", qi, kj,
                           preferred_element_type=jnp.float32) * scale
            kpos = jk * ck + jnp.arange(ck)
            if causal:
                qpos = q_offset + iq * cq + jnp.arange(cq)
                mask = (qpos[:, None] >= kpos[None, :]) & (kpos < Sk_orig)[None]
                s = jnp.where(mask[None, None, None], s, -jnp.inf)
            elif Sk != Sk_orig:
                s = jnp.where((kpos < Sk_orig)[None, None, None, None], s,
                              -jnp.inf)
            blk_max = jnp.max(s, axis=-1)
            m_new = jnp.maximum(m, blk_max)
            # guard fully-masked rows (m_new == -inf)
            m_safe = jnp.where(jnp.isinf(m_new), 0.0, m_new)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(jnp.isinf(m_new)[..., None], 0.0, p)
            corr = jnp.exp(jnp.where(jnp.isinf(m), 0.0, m) - m_safe)
            corr = jnp.where(jnp.isinf(m), jnp.where(jnp.isinf(m_new), 1.0, 0.0), corr)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqs,bskh->bqkgh", p, vj,
                            preferred_element_type=jnp.float32)
            acc_new = acc * corr.transpose(0, 3, 1, 2)[..., None] + pv
            return (m_new, l_new, acc_new), None

        (m, l, acc), _ = jax.lax.scan(kv_body, (m0, l0, a0), jnp.arange(nk))
        lsafe = jnp.maximum(l, 1e-20)
        out = acc / lsafe.transpose(0, 3, 1, 2)[..., None]
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_body, None, (qc, jnp.arange(nq)))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, K, G, hd)
    return out[:, :Sq_orig]


def attention(
    params: Dict,
    x: jnp.ndarray,                 # [B, S, d]
    positions: jnp.ndarray,         # [B, S]
    cfg: ModelConfig,
    causal: bool = True,
    cache: Optional[Dict] = None,   # {"k": [B, S, K, hd], "v": ..., "pos": int32}
    kv_from: Optional[jnp.ndarray] = None,  # cross-attention source [B, Skv, d]
    cross: bool = False,
) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """GQA attention.  With ``cache`` and S==1 runs one decode step.

    ``cross=True`` marks cross-attention: no rope, never causal, and the KV
    pair comes from ``kv_from`` (or from a *static* cache {"k","v"} computed
    once from the encoder output).  Returns (output [B, S, d], cache or None).
    """
    B, S, d = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    G = H // K
    q = jnp.einsum("bsd,dn->bsn", x, params["wq"]).reshape(B, S, H, hd)
    if not cross:
        q = apply_rope(q, positions, cfg.rope_theta)
    kv_axis = div_or_none(K, tp())

    if cross and cache is not None and "k" in cache:
        k, v = cache["k"], cache["v"]          # static source cache
    else:
        kv_src = x if kv_from is None else kv_from
        Skv = kv_src.shape[1]
        k = jnp.einsum("bsd,dn->bsn", kv_src, params["wk"]).reshape(B, Skv, K, hd)
        v = jnp.einsum("bsd,dn->bsn", kv_src, params["wv"]).reshape(B, Skv, K, hd)
        if not cross:
            kpos = positions if S == Skv else positions[:, -Skv:]
            k = apply_rope(k, kpos, cfg.rope_theta)

    if not cross and cache is not None and "pos" in cache and S == 1:
        # ---- self-attention decode: append to cache, attend over window -----
        pos = cache["pos"]
        quant = "k_scale" in cache
        if quant:
            k8, ks = kv_quantize(k)
            v8, vs = kv_quantize(v)
            ck = jax.lax.dynamic_update_slice(cache["k"], k8, (0, pos, 0, 0))
            cv = jax.lax.dynamic_update_slice(cache["v"], v8, (0, pos, 0, 0))
            cks = jax.lax.dynamic_update_slice(cache["k_scale"], ks,
                                               (0, pos, 0, 0))
            cvs = jax.lax.dynamic_update_slice(cache["v_scale"], vs,
                                               (0, pos, 0, 0))
        else:
            ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, pos, 0, 0))
            cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, pos, 0, 0))
        ck = shard(ck, dp(), tp(), None, None)
        cv = shard(cv, dp(), tp(), None, None)
        qg = q.reshape(B, 1, K, G, hd)
        if quant:
            # fold scales outside the int8 einsums: s = (q·k8)·scale_k,
            # o = (p·scale_v)·v8 — the dequantized cache never materializes.
            s = jnp.einsum("bqkgh,bskh->bkgqs", qg.astype(jnp.float32),
                           ck.astype(jnp.float32),
                           preferred_element_type=jnp.float32)
            s = s * cks[..., 0].transpose(0, 2, 1)[:, :, None, None, :]
            s = s / np.sqrt(hd)
        else:
            s = jnp.einsum("bqkgh,bskh->bkgqs", qg, ck,
                           preferred_element_type=jnp.float32) / np.sqrt(hd)
        s = shard(s, dp(), None, None, None, tp())
        span = ck.shape[1]
        valid = jnp.arange(span)[None] <= pos
        s = jnp.where(valid[:, None, None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if quant:
            p = p * cvs[..., 0].transpose(0, 2, 1)[:, :, None, None, :]
            o = jnp.einsum("bkgqs,bskh->bqkgh", p, cv.astype(jnp.float32),
                           preferred_element_type=jnp.float32)
        else:
            o = jnp.einsum("bkgqs,bskh->bqkgh", p, cv,
                           preferred_element_type=jnp.float32)
        o = o.astype(x.dtype).reshape(B, 1, H * hd)
        out = jnp.einsum("bsn,nd->bsd", o, params["wo"])
        new_cache = {"k": ck, "v": cv, "pos": pos + 1}
        if quant:
            new_cache.update(k_scale=cks, v_scale=cvs)
        return out, new_cache

    if cross and S == 1:
        # ---- cross-attention decode against the static source cache ---------
        qg = q.reshape(B, 1, K, G, hd)
        s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k,
                       preferred_element_type=jnp.float32) / np.sqrt(hd)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgqs,bskh->bqkgh", p, v, preferred_element_type=jnp.float32)
        o = o.astype(x.dtype).reshape(B, 1, H * hd)
        return jnp.einsum("bsn,nd->bsd", o, params["wo"]), cache

    # ---- full attention (train / prefill) ----------------------------------
    qg = q.reshape(B, S, K, G, hd)
    qg = shard(qg, dp(), None, kv_axis, None, None)
    k = shard(k, dp(), None, kv_axis, None)
    v = shard(v, dp(), None, kv_axis, None)
    o = _flash(qg, k, v, causal=causal and not cross, chunk=cfg.attn_chunk)
    o = o.reshape(B, S, H * hd)
    out = row_parallel_matmul(o, params["wo"], cfg)
    out_cache = None
    if cache is not None and not cross:
        if cfg.kv_quant:
            k8, ks = kv_quantize(k)
            v8, vs = kv_quantize(v)
            out_cache = {"k": k8, "v": v8, "k_scale": ks, "v_scale": vs,
                         "pos": jnp.asarray(S, jnp.int32)}
        else:
            out_cache = {"k": k, "v": v, "pos": jnp.asarray(S, jnp.int32)}
    elif cache is not None:
        out_cache = {"k": k, "v": v}
    return out, out_cache


def make_cache(cfg: ModelConfig, batch: int, length: int, dtype=jnp.bfloat16) -> Dict:
    K, hd = cfg.n_kv_heads, cfg.hd
    if cfg.kv_quant:
        return {
            "k": jnp.zeros((batch, length, K, hd), jnp.int8),
            "v": jnp.zeros((batch, length, K, hd), jnp.int8),
            "k_scale": jnp.zeros((batch, length, K, 1), jnp.float32),
            "v_scale": jnp.zeros((batch, length, K, 1), jnp.float32),
            "pos": jnp.asarray(0, jnp.int32),
        }
    return {
        "k": jnp.zeros((batch, length, K, hd), dtype),
        "v": jnp.zeros((batch, length, K, hd), dtype),
        "pos": jnp.asarray(0, jnp.int32),
    }


# =============================================================================
# Dense MLP
# =============================================================================

def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None, dtype=jnp.float32) -> Dict:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    ks = split_keys(key, 3)
    p = {
        "up": dense_init(ks[0], (d, f), dtype=dtype),
        "down": dense_init(ks[1], (f, d), dtype=dtype),
    }
    if cfg.activation == "swiglu":
        p["gate"] = dense_init(ks[2], (d, f), dtype=dtype)
    return p


def mlp(params: Dict, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    up = jnp.einsum("bsd,df->bsf", x, params["up"])
    up = shard(up, dp(), None, tp())
    if cfg.activation == "swiglu":
        gate = jnp.einsum("bsd,df->bsf", x, params["gate"])
        h = jax.nn.silu(gate) * up
    else:
        h = ACTIVATIONS[cfg.activation](up)
    out = row_parallel_matmul(h, params["down"], cfg)
    return shard(out, dp(), None, None)


# =============================================================================
# Mixture of Experts
# =============================================================================

def init_moe(key, cfg: ModelConfig, dtype=jnp.float32) -> Dict:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = split_keys(key, 5)
    p = {
        "router": dense_init(ks[0], (d, E), dtype=jnp.float32),
        "up": dense_init(ks[1], (E, d, f), in_axis=1, dtype=dtype),
        "down": dense_init(ks[2], (E, f, d), in_axis=1, dtype=dtype),
    }
    if cfg.activation == "swiglu":
        p["gate"] = dense_init(ks[3], (E, d, f), in_axis=1, dtype=dtype)
    if cfg.n_shared_experts:
        sub = dataclass_replace_dff(cfg, cfg.n_shared_experts * cfg.d_ff)
        p["shared"] = init_mlp(ks[4], sub, dtype=dtype)
    return p


def dataclass_replace_dff(cfg: ModelConfig, f: int) -> ModelConfig:
    import dataclasses
    return dataclasses.replace(cfg, d_ff=f)


def _capacity(tokens: int, cfg: ModelConfig) -> int:
    c = int(np.ceil(tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts))
    return max(8, -(-c // 8) * 8)  # round up to a multiple of 8


def _route(params, xf, cfg: ModelConfig):
    """Router: returns (gates [T,k], experts [T,k], aux_loss scalar)."""
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eids = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    # Switch-style load-balance loss
    E = cfg.n_experts
    me = jnp.mean(probs, axis=0)                                   # [E]
    ce = jnp.mean(jax.nn.one_hot(eids[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(me * ce)
    return gates, eids, aux


def _expert_ffn(params, xg: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """xg: [E, C, d] -> [E, C, d] through each expert's FFN."""
    up = jnp.einsum("ecd,edf->ecf", xg, params["up"])
    if cfg.activation == "swiglu":
        gate = jnp.einsum("ecd,edf->ecf", xg, params["gate"])
        h = jax.nn.silu(gate) * up
    else:
        h = ACTIVATIONS[cfg.activation](up)
    return jnp.einsum("ecf,efd->ecd", h, params["down"])


def moe_dense(params: Dict, x: jnp.ndarray, cfg: ModelConfig):
    """Sort/scatter top-k dispatch under plain pjit (baseline).

    Static shapes throughout; overflow beyond expert capacity is dropped
    (standard capacity-factor semantics).
    """
    B, S, d = x.shape
    T = B * S
    xf = x.reshape(T, d)
    gates, eids, aux = _route(params, xf, cfg)
    k, E = cfg.top_k, cfg.n_experts
    C = _capacity(T, cfg)

    flat_e = eids.reshape(-1)                                # [T*k]
    sidx = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[sidx]
    first_occ = jnp.searchsorted(sorted_e, sorted_e, side="left")
    rank = jnp.arange(T * k) - first_occ
    keep = rank < C
    slot = jnp.where(keep, sorted_e * C + rank, E * C)       # E*C = drop bin
    tok = sidx // k

    xg = jnp.zeros((E * C + 1, d), x.dtype).at[slot].set(xf[tok])
    yg = _expert_ffn(params, xg[:-1].reshape(E, C, d), cfg)
    yg = shard(yg, tp(), None, None)
    y_sorted = jnp.concatenate([yg.reshape(E * C, d),
                                jnp.zeros((1, d), yg.dtype)])[slot]
    gsel = gates.reshape(-1)[sidx]
    contrib = y_sorted * gsel[:, None].astype(y_sorted.dtype)
    y = jnp.zeros((T, d), contrib.dtype).at[tok].add(contrib)
    y = y.astype(x.dtype)
    if cfg.n_shared_experts:
        y = y + mlp(params["shared"], x, cfg).reshape(T, d)
    return shard(y.reshape(B, S, d), dp(), None, None), aux


def moe_a2a(params: Dict, x: jnp.ndarray, cfg: ModelConfig, mesh):
    """shard_map expert-parallel dispatch with explicit all_to_all (optimized).

    Activations are *sequence-sharded* over the ``model`` axis on entry
    (GShard-style), so every token is dispatched exactly once — with plain
    dp sharding the token stream is replicated over ``model`` and each TP
    rank would redundantly compute every expert slot.  Only the capacity
    buffers cross the ``model`` axis (2 all_to_alls).  For S == 1 (decode)
    the sequence cannot be sharded; dispatch is then replicated over
    ``model`` (identical results per rank, negligible at one token).
    """
    from repro.compat import shard_map

    tp_axis = tp()
    dp_spec = dp()
    E, kk = cfg.n_experts, cfg.top_k
    tp_sz = mesh.shape[tp_axis] if tp_axis in mesh.axis_names else 1
    seq_shard = x.shape[1] % tp_sz == 0 and x.shape[1] >= tp_sz
    seq_axis = tp_axis if seq_shard else None
    mean_axes = (dp_spec,) if isinstance(dp_spec, str) else tuple(dp_spec)
    if seq_shard:
        mean_axes = mean_axes + (tp_axis,)

    def local_fn(x_loc, router, up, gate, down, shared):
        Bl, Sl, d = x_loc.shape
        Tl = Bl * Sl
        xf = x_loc.reshape(Tl, d)
        p_loc = {"router": router, "up": up, "down": down}
        if gate is not None:
            p_loc["gate"] = gate
        gates, eids, aux = _route(p_loc, xf, cfg)
        aux = jax.lax.pmean(aux, mean_axes)
        C = _capacity(Tl, cfg)
        flat_e = eids.reshape(-1)
        sidx = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[sidx]
        rank = jnp.arange(Tl * kk) - jnp.searchsorted(sorted_e, sorted_e, "left")
        keep = rank < C
        slot = jnp.where(keep, sorted_e * C + rank, E * C)
        tok = sidx // kk
        xg = jnp.zeros((E * C + 1, d), x_loc.dtype).at[slot].set(xf[tok])
        xg = xg[:-1].reshape(E, C, d)
        ep = axis_size(tp_axis)
        # [E, C, d] -a2a-> [E/ep, ep*C, d]: local slots for this shard's experts
        xg = jax.lax.all_to_all(xg, tp_axis, split_axis=0, concat_axis=1,
                                tiled=True)
        p_exp = {"up": up, "down": down}
        if gate is not None:
            p_exp["gate"] = gate
        yg = _expert_ffn(p_exp, xg, cfg)
        # reverse: [E/ep, ep*C, d] -a2a-> [E, C, d]
        yg = jax.lax.all_to_all(yg, tp_axis, split_axis=1, concat_axis=0,
                                tiled=True)
        yg = yg.reshape(E * C, d)
        y_sorted = jnp.concatenate([yg, jnp.zeros((1, d), yg.dtype)])[slot]
        gsel = gates.reshape(-1)[sidx]
        y = jnp.zeros((Tl, d), jnp.float32).at[tok].add(
            y_sorted.astype(jnp.float32) * gsel[:, None])
        return y.astype(x_loc.dtype).reshape(Bl, Sl, d), aux

    gate = params.get("gate")
    in_specs = (
        P(dp_spec, seq_axis, None), P(), P(tp_axis, None, None),
        P(tp_axis, None, None) if gate is not None else P(),
        P(tp_axis, None, None), P(),
    )
    fn = shard_map(
        local_fn, mesh=mesh,
        in_specs=in_specs,
        out_specs=(P(dp_spec, seq_axis, None), P()),
    )
    y, aux = fn(x, params["router"], params["up"], gate, params["down"], None)
    y = shard(y, dp(), None, None)   # re-gather the sequence for the next block
    if cfg.n_shared_experts:
        y = y + mlp(params["shared"], x, cfg)
    return y, jnp.mean(aux)


def moe(params: Dict, x: jnp.ndarray, cfg: ModelConfig, mesh=None):
    if cfg.moe_impl == "a2a" and mesh is not None:
        return moe_a2a(params, x, cfg, mesh)
    return moe_dense(params, x, cfg)
