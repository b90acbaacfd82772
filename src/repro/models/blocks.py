"""Transformer / SSM blocks: GQA attention, MLA, MoE, Mamba-2 SSD.

Each block provides ``init_<blk>(key, cfg) → params`` (vmap-able for
scan-over-layers stacking) and apply functions for the three execution
modes: train/prefill (full sequence, optionally emitting a KV cache) and
decode (single token against a cache).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.dist.sharding import shard
from .layers import (
    apply_rope,
    chunked_attention,
    decode_attention,
    dense_init,
    rms_norm,
)

Array = jax.Array


# ===================================================================== GQA
def init_attention(key, cfg: ArchConfig, dtype=jnp.bfloat16) -> dict:
    hd = cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], cfg.d_model, cfg.n_heads * hd, dtype),
        "wk": dense_init(ks[1], cfg.d_model, cfg.n_kv_heads * hd, dtype),
        "wv": dense_init(ks[2], cfg.d_model, cfg.n_kv_heads * hd, dtype),
        "wo": dense_init(ks[3], cfg.n_heads * hd, cfg.d_model, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), dtype)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), dtype)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), dtype)
    return p


def _qkv(p: dict, cfg: ArchConfig, x: Array) -> Tuple[Array, Array, Array]:
    b, s, _ = x.shape
    hd = cfg.head_dim
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"])
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"])
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    q = shard(q, ("batch", "heads", "seq", None))
    k = shard(k, ("batch", "kv_heads", "seq", None))
    v = shard(v, ("batch", "kv_heads", "seq", None))
    return q, k, v


def attention_forward(
    p: dict,
    cfg: ArchConfig,
    x: Array,
    *,
    window: Optional[int] = None,
    causal: bool = True,
    q_offset: int = 0,
    return_cache: bool = False,
):
    """Full-sequence attention (train / prefill)."""
    b, s, _ = x.shape
    q, k, v = _qkv(p, cfg, x)
    positions = q_offset + jnp.arange(s)
    q = apply_rope(q, positions[None, None, :], cfg.rope_theta)
    k = apply_rope(k, positions[None, None, :], cfg.rope_theta)
    out = chunked_attention(q, k, v, causal=causal, window=window, q_offset=q_offset)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, -1)
    y = jnp.einsum("bsh,hd->bsd", out, p["wo"])
    if return_cache:
        return y, (k, v)
    return y


def attention_decode(
    p: dict,
    cfg: ArchConfig,
    x: Array,
    cache: Tuple[Array, Array],
    pos: Array,
    *,
    window: Optional[int] = None,
):
    """Single-token decode; pos: scalar.

    Full attention: cache k/v [B, KV, S_max, hd], written at ``pos``.
    Sliding window: RING-BUFFER cache [B, KV, window, hd], written at
    ``pos % window`` (see layers.decode_attention_ring).
    """
    from .layers import decode_attention_ring

    b = x.shape[0]
    q, k_new, v_new = _qkv(p, cfg, x)  # seq dim == 1
    q = apply_rope(q, pos[None, None, None], cfg.rope_theta)
    k_new = apply_rope(k_new, pos[None, None, None], cfg.rope_theta)
    k_cache, v_cache = cache
    ring = window is not None and k_cache.shape[2] == window
    write_at = (pos % window) if ring else pos
    k_cache = jax.lax.dynamic_update_slice_in_dim(
        k_cache, k_new, write_at, axis=2
    )
    v_cache = jax.lax.dynamic_update_slice_in_dim(
        v_cache, v_new, write_at, axis=2
    )
    if ring:
        out = decode_attention_ring(q, k_cache, v_cache, pos, window)
    else:
        out = decode_attention(q, k_cache, v_cache, pos, window=window)
    out = out.transpose(0, 2, 1, 3).reshape(b, 1, -1)
    y = jnp.einsum("bsh,hd->bsd", out, p["wo"])
    return y, (k_cache, v_cache)


# ===================================================================== MLA
def init_mla(key, cfg: ArchConfig, dtype=jnp.bfloat16) -> dict:
    m = cfg.mla
    assert m is not None
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 5)
    return {
        "wq_a": dense_init(ks[0], cfg.d_model, m.q_lora_rank, dtype),
        "q_ln": jnp.zeros((m.q_lora_rank,), dtype),
        "wq_b": dense_init(ks[1], m.q_lora_rank, cfg.n_heads * qk_dim, dtype),
        "wkv_a": dense_init(
            ks[2], cfg.d_model, m.kv_lora_rank + m.qk_rope_head_dim, dtype
        ),
        "kv_ln": jnp.zeros((m.kv_lora_rank,), dtype),
        "wkv_b": dense_init(
            ks[3],
            m.kv_lora_rank,
            cfg.n_heads * (m.qk_nope_head_dim + m.v_head_dim),
            dtype,
        ),
        "wo": dense_init(ks[4], cfg.n_heads * m.v_head_dim, cfg.d_model, dtype),
    }


def _mla_q(p, cfg, x, positions):
    m = cfg.mla
    b, s, _ = x.shape
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    q_lat = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["wq_a"]), p["q_ln"], cfg.norm_eps)
    q = jnp.einsum("bsr,rh->bsh", q_lat, p["wq_b"]).reshape(
        b, s, cfg.n_heads, qk_dim
    ).transpose(0, 2, 1, 3)
    q_nope = q[..., : m.qk_nope_head_dim]
    q_rope = apply_rope(
        q[..., m.qk_nope_head_dim :], positions[None, None, :], cfg.rope_theta
    )
    return q_nope, q_rope


def _mla_latent(p, cfg, x, positions):
    m = cfg.mla
    kv_a = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
    latent = rms_norm(kv_a[..., : m.kv_lora_rank], p["kv_ln"], cfg.norm_eps)
    k_rope = apply_rope(
        kv_a[..., m.kv_lora_rank :][:, None], positions[None, None, :], cfg.rope_theta
    )  # [B, 1, S, rope_dim]
    return latent, k_rope


def mla_forward(
    p: dict,
    cfg: ArchConfig,
    x: Array,
    *,
    q_offset: int = 0,
    return_cache: bool = False,
):
    """MLA train/prefill: expand latent to per-head K/V (compute-optimal at
    long Sq); the decode path uses the absorbed latent-space form instead."""
    m = cfg.mla
    b, s, _ = x.shape
    positions = q_offset + jnp.arange(s)
    q_nope, q_rope = _mla_q(p, cfg, x, positions)
    latent, k_rope = _mla_latent(p, cfg, x, positions)
    kv = jnp.einsum("bsr,rh->bsh", latent, p["wkv_b"]).reshape(
        b, s, cfg.n_heads, m.qk_nope_head_dim + m.v_head_dim
    ).transpose(0, 2, 1, 3)
    k_nope, v = kv[..., : m.qk_nope_head_dim], kv[..., m.qk_nope_head_dim :]
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, cfg.n_heads, s, m.qk_rope_head_dim))],
        axis=-1,
    )
    out = chunked_attention(q, k, v, causal=True, q_offset=q_offset)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, -1)
    y = jnp.einsum("bsh,hd->bsd", out, p["wo"])
    if return_cache:
        # the MLA cache is the LATENT (+ rope key): 576 B/token vs
        # 2·128·128 = 32 KiB/token for full per-head K/V — the sub-linear
        # serve-memory motif (DESIGN.md §4)
        return y, (latent, k_rope[:, 0])
    return y


def mla_decode(p: dict, cfg: ArchConfig, x: Array, cache, pos: Array):
    """Absorbed-form MLA decode: attention runs in the latent space."""
    m = cfg.mla
    b = x.shape[0]
    latent_cache, rope_cache = cache  # [B, S, r], [B, S, rope_dim]
    positions = pos[None]
    q_nope, q_rope = _mla_q(p, cfg, x, positions)  # [B, H, 1, *]
    latent_new, k_rope_new = _mla_latent(p, cfg, x, positions)
    latent_cache = jax.lax.dynamic_update_slice_in_dim(
        latent_cache, latent_new, pos, axis=1
    )
    rope_cache = jax.lax.dynamic_update_slice_in_dim(
        rope_cache, k_rope_new[:, 0], pos, axis=1
    )
    # absorb wkv_b's key half into the query:  q_lat = q_nope @ W_k^T
    wkv_b = p["wkv_b"].reshape(
        m.kv_lora_rank, cfg.n_heads, m.qk_nope_head_dim + m.v_head_dim
    )
    w_k = wkv_b[..., : m.qk_nope_head_dim]  # [r, H, nope]
    w_v = wkv_b[..., m.qk_nope_head_dim :]  # [r, H, v]
    q_lat = jnp.einsum("bhqn,rhn->bhqr", q_nope.astype(jnp.float32), w_k.astype(jnp.float32))
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    logits = (
        jnp.einsum("bhqr,bsr->bhqs", q_lat, latent_cache.astype(jnp.float32))
        + jnp.einsum(
            "bhqe,bse->bhqs",
            q_rope.astype(jnp.float32),
            rope_cache.astype(jnp.float32),
        )
    ) * scale
    mask = jnp.arange(latent_cache.shape[1])[None, None, None, :] <= pos
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    ctx = jnp.einsum("bhqs,bsr->bhqr", probs, latent_cache.astype(jnp.float32))
    out = jnp.einsum("bhqr,rhv->bhqv", ctx, w_v.astype(jnp.float32))  # [B,H,1,v]
    out = out.transpose(0, 2, 1, 3).reshape(b, 1, -1).astype(x.dtype)
    y = jnp.einsum("bsh,hd->bsd", out, p["wo"])
    return y, (latent_cache, rope_cache)


# ===================================================================== MoE
def init_moe(key, cfg: ArchConfig, dtype=jnp.bfloat16) -> dict:
    moe = cfg.moe
    assert moe is not None
    ks = jax.random.split(key, 5)
    scale = 1.0 / math.sqrt(cfg.d_model)
    p = {
        "router": (
            jax.random.normal(ks[0], (cfg.d_model, moe.num_experts), jnp.float32)
            * scale
        ).astype(jnp.float32),
        "gate": (
            jax.random.normal(
                ks[1], (moe.num_experts, cfg.d_model, moe.d_ff_expert), jnp.float32
            )
            * scale
        ).astype(dtype),
        "up": (
            jax.random.normal(
                ks[2], (moe.num_experts, cfg.d_model, moe.d_ff_expert), jnp.float32
            )
            * scale
        ).astype(dtype),
        "down": (
            jax.random.normal(
                ks[3], (moe.num_experts, moe.d_ff_expert, cfg.d_model), jnp.float32
            )
            * scale
        ).astype(dtype),
    }
    return p


def _capacity(cfg: ArchConfig, seq: int) -> int:
    moe = cfg.moe
    c = int(math.ceil(seq * moe.top_k / moe.num_experts * moe.capacity_factor))
    return max(8, min(c, seq))


def moe_dispatch_row(x_row: Array, gates_row: Array, top_k: int, capacity: int):
    """Sort-based dispatch for a single sequence (vmapped over batch).

    Returns (xe [E*C, d], slot_of [S*k], tok_of [S*k], gate_of [S*k],
    keep [S*k]).
    """
    s, e = gates_row.shape
    top_vals, top_idx = jax.lax.top_k(gates_row, top_k)  # [S, k]
    top_vals = jax.nn.softmax(top_vals, axis=-1)  # renormalize over chosen
    flat_expert = top_idx.reshape(-1)  # [S*k]
    flat_gate = top_vals.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(s), top_k)
    order = jnp.argsort(flat_expert)
    sorted_expert = flat_expert[order]
    sorted_tok = flat_tok[order]
    sorted_gate = flat_gate[order]
    starts = jnp.searchsorted(sorted_expert, jnp.arange(e))  # [E]
    pos_in_expert = jnp.arange(s * top_k) - starts[sorted_expert]
    keep = pos_in_expert < capacity
    slot = jnp.where(keep, sorted_expert * capacity + pos_in_expert, 0)
    xe = jnp.zeros((e * capacity, x_row.shape[-1]), x_row.dtype)
    contrib = jnp.where(keep[:, None], x_row[sorted_tok], 0).astype(x_row.dtype)
    xe = xe.at[slot].add(contrib)
    return xe, slot, sorted_tok, sorted_gate, keep


@jax.named_scope("moe_ffn")
def moe_forward(p: dict, cfg: ArchConfig, x: Array) -> Array:
    """Token-choice top-k MoE with per-sequence capacity (GShard-style
    token dropping) and sort-based grouped dispatch.

    Decode (s == 1) uses a GLOBAL cross-batch dispatch instead: the whole
    batch is one dispatch group, so the expert GEMM [E, C, d]×[E, d, f] has
    no batch axis — expert weights can shard over (model × data) without the
    weight all-gather that a batch-axis conflict forces (§Perf-1), and the
    per-step activations are token-sized.
    """
    moe = cfg.moe
    b, s, d = x.shape
    router_logits = jnp.einsum(
        "bsd,de->bse", x.astype(jnp.float32), p["router"]
    )
    gates = jax.nn.softmax(router_logits, axis=-1)

    if s == 1:
        xb = x[:, 0]  # [B, d]
        gb = gates[:, 0]  # [B, E]
        capacity = max(
            4,
            int(
                math.ceil(
                    b * moe.top_k / moe.num_experts * moe.capacity_factor
                )
            ),
        )
        xe, slot, tok, gate_w, keep = moe_dispatch_row(
            xb, gb, moe.top_k, capacity
        )
        xe = xe.reshape(moe.num_experts, capacity, d)
        xe = shard(xe, ("expert", None, None))
        h_gate = jnp.einsum("ecd,edf->ecf", xe, p["gate"])
        h_up = jnp.einsum("ecd,edf->ecf", xe, p["up"])
        h = jax.nn.silu(h_gate.astype(jnp.float32)).astype(x.dtype) * h_up
        h = shard(h, ("expert", None, "expert_mlp"))
        ye = jnp.einsum("ecf,efd->ecd", h, p["down"]).reshape(-1, d)
        vals = ye[slot].astype(jnp.float32) * (gate_w * keep)[:, None]
        y = jnp.zeros((b, d), jnp.float32).at[tok].add(vals)
        return y[:, None].astype(x.dtype)

    capacity = _capacity(cfg, s)

    xe, slot, tok, gate_w, keep = jax.vmap(
        lambda xr, gr: moe_dispatch_row(xr, gr, moe.top_k, capacity)
    )(x, gates)
    xe = xe.reshape(b, moe.num_experts, capacity, d)
    xe = shard(xe, ("batch", "expert", None, None))
    h_gate = jnp.einsum("becd,edf->becf", xe, p["gate"])
    h_up = jnp.einsum("becd,edf->becf", xe, p["up"])
    h = jax.nn.silu(h_gate.astype(jnp.float32)).astype(x.dtype) * h_up
    h = shard(h, ("batch", "expert", None, "expert_mlp"))
    ye = jnp.einsum("becf,efd->becd", h, p["down"])
    ye = ye.reshape(b, moe.num_experts * capacity, d)

    def combine_row(ye_row, slot_row, tok_row, gate_row, keep_row):
        vals = ye_row[slot_row].astype(jnp.float32) * (
            gate_row * keep_row
        )[:, None]
        return jnp.zeros((s, d), jnp.float32).at[tok_row].add(vals)

    y = jax.vmap(combine_row)(ye, slot, tok, gate_w, keep)
    return y.astype(x.dtype)


# ================================================================== Mamba-2
def init_mamba(key, cfg: ArchConfig, dtype=jnp.bfloat16) -> dict:
    """Mamba-2 block with SPLIT projections.

    The reference implementation fuses z/x/B/C/dt into one in_proj and
    slices its output — under tensor parallelism those slices cut the
    sharded output dim at non-shard-aligned offsets and XLA pays a
    collective-permute chain for every piece (measured ≈7.5 GiB/step on
    zamba2-train; EXPERIMENTS.md §Perf-2).  Separate matrices give every
    part a cleanly sharded (or replicated) output dim.
    """
    ssm = cfg.ssm
    assert ssm is not None
    d = cfg.d_model
    di = ssm.d_inner(d)
    nh = ssm.n_heads(d)
    ks = jax.random.split(key, 8)
    return {
        "w_z": dense_init(ks[0], d, di, dtype),
        "w_x": dense_init(ks[1], d, di, dtype),
        "w_B": dense_init(ks[2], d, ssm.d_state, dtype),
        "w_C": dense_init(ks[3], d, ssm.d_state, dtype),
        "w_dt": dense_init(ks[4], d, nh, dtype),
        "conv_x": (
            jax.random.normal(ks[5], (ssm.d_conv, di), jnp.float32) * 0.1
        ).astype(dtype),
        "conv_B": (
            jax.random.normal(ks[6], (ssm.d_conv, ssm.d_state), jnp.float32)
            * 0.1
        ).astype(dtype),
        "conv_C": (
            jax.random.normal(ks[7], (ssm.d_conv, ssm.d_state), jnp.float32)
            * 0.1
        ).astype(dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nh)).astype(jnp.float32),
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "out_proj": dense_init(ks[4], di, d, dtype),
    }


def _causal_conv(x: Array, w: Array, s: int) -> Array:
    """Depthwise causal conv along seq; x [B,S,C], w [d_conv, C]."""
    d_conv = w.shape[0]
    x_pad = jnp.pad(x, ((0, 0), (d_conv - 1, 0), (0, 0)))
    return sum(x_pad[:, i : i + s] * w[i][None, None, :] for i in range(d_conv))


def _ssd_scan(x, dt, A, B, C, chunk: int):
    """Chunked SSD (state-space duality) scan [arXiv:2405.21060 §6].

    x: [b, s, nh, hd]; dt: [b, s, nh]; A: [nh] (negative);
    B, C: [b, s, ds].  Returns y: [b, s, nh, hd].
    """
    b, s, nh, hd = x.shape
    ds = B.shape[-1]
    nc = (s + chunk - 1) // chunk
    pad = nc * chunk - s
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    xc = x.reshape(b, nc, chunk, nh, hd)
    dtc = dt.reshape(b, nc, chunk, nh)
    Bc = B.reshape(b, nc, chunk, ds)
    Cc = C.reshape(b, nc, chunk, ds)

    dA = dtc * A[None, None, None, :]  # [b, nc, q, nh] (negative)
    seg = jnp.cumsum(dA, axis=2)  # cumulative decay within chunk
    total = seg[:, :, -1, :]  # [b, nc, nh]

    # --- intra-chunk (quadratic within chunk, matches attention-form SSD)
    rel = seg[:, :, :, None, :] - seg[:, :, None, :, :]  # [b,nc,qi,qj,nh]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))[None, None, :, :, None]
    # mask BEFORE exp: upper-triangle rel is positive-large → exp overflows
    # to inf and poisons gradients through the where (inf·0 = NaN in vjp)
    rel = jnp.where(causal, rel, -jnp.inf)
    L = jnp.exp(rel)
    scores = jnp.einsum("bcid,bcjd->bcij", Cc, Bc)  # [b,nc,qi,qj]
    M = scores[..., None] * L * dtc[:, :, None, :, :]  # [b,nc,qi,qj,nh]
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", M, xc.astype(jnp.float32))

    # --- per-chunk outgoing state:  S_c = Σ_j exp(total−seg_j)·dt_j·B_j⊗x_j
    decay_out = jnp.exp(total[:, :, None, :] - seg)  # [b,nc,q,nh]
    wx = (decay_out * dtc)[..., None] * xc.astype(jnp.float32)  # [b,nc,q,nh,hd]
    S_c = jnp.einsum("bcqd,bcqhp->bchpd", Bc, wx)  # [b,nc,nh,hd,ds]

    # --- inter-chunk recurrence:  H_c = exp(total_c)·H_{c-1} + S_c
    def scan_fn(H, inputs):
        S_chunk, tot = inputs  # [b,nh,hd,ds], [b,nh]
        H_new = jnp.exp(tot)[:, :, None, None] * H + S_chunk
        return H_new, H  # emit the INCOMING state for this chunk

    H0 = jnp.zeros((b, nh, hd, ds), jnp.float32)
    _, H_in = jax.lax.scan(
        scan_fn,
        H0,
        (S_c.transpose(1, 0, 2, 3, 4), total.transpose(1, 0, 2)),
    )
    H_in = H_in.transpose(1, 0, 2, 3, 4)  # [b,nc,nh,hd,ds]

    # --- inter-chunk contribution:  y_i += exp(seg_i)·C_i·H_in
    decay_in = jnp.exp(seg)  # [b,nc,q,nh]
    y_inter = (
        jnp.einsum("bcqd,bchpd->bcqhp", Cc, H_in) * decay_in[..., None]
    )

    y = (y_intra + y_inter).reshape(b, nc * chunk, nh, hd)
    if pad:
        y = y[:, :s]
    return y


def mamba_forward(
    p: dict,
    cfg: ArchConfig,
    x: Array,
    *,
    return_cache: bool = False,
):
    """Mamba-2 block (train / prefill).

    Cache = (conv_x_state, conv_B_state, conv_C_state, ssm_state)."""
    ssm = cfg.ssm
    b, s, d = x.shape
    di = ssm.d_inner(d)
    nh = ssm.n_heads(d)

    from .layers import bf16_grad

    # bf16_grad: the SSD internals run in f32, so without a boundary the
    # cotangents reaching these projections are f32 and every TP activation-
    # grad all-reduce doubles in size (§Perf-2 follow-up)
    z = bf16_grad(jnp.einsum("bsd,dk->bsk", x, p["w_z"]))
    x_in = bf16_grad(jnp.einsum("bsd,dk->bsk", x, p["w_x"]))
    B_in = bf16_grad(jnp.einsum("bsd,dk->bsk", x, p["w_B"]))
    C_in = bf16_grad(jnp.einsum("bsd,dk->bsk", x, p["w_C"]))
    dt_raw = bf16_grad(jnp.einsum("bsd,dk->bsk", x, p["w_dt"]))

    xc = jax.nn.silu(_causal_conv(x_in, p["conv_x"], s).astype(jnp.float32))
    Bc = jax.nn.silu(_causal_conv(B_in, p["conv_B"], s).astype(jnp.float32))
    Cc = jax.nn.silu(_causal_conv(C_in, p["conv_C"], s).astype(jnp.float32))

    xs = xc.reshape(b, s, nh, ssm.head_dim)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])

    y = _ssd_scan(xs, dt, A, Bc, Cc, ssm.chunk_size)
    y = y + p["D"][None, None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(b, s, di).astype(x.dtype)
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)
    out = jnp.einsum("bsk,kd->bsd", y, p["out_proj"])
    if return_cache:
        # the conv's last d_conv − 1 inputs; a prompt shorter than that is
        # preceded by the zeros the causal conv pads with
        tail = ssm.d_conv - 1
        pad = ((0, 0), (max(tail - s, 0), 0), (0, 0))

        def last(a):
            return jnp.pad(a, pad)[:, -tail:, :]

        cache = (
            last(x_in),
            last(B_in),
            last(C_in),
            _final_state(xs, dt, A, Bc),
        )
        return out, cache
    return out


def _final_state(xs, dt, A, B):
    """Final SSM state  H = Σ_j exp(Σ_{l>j} dA_l)·dt_j·B_j⊗x_j  (f32)."""
    b, s, nh, hd = xs.shape
    dA = dt * A[None, None, :]
    seg = jnp.cumsum(dA, axis=1)
    total = seg[:, -1:, :]
    decay = jnp.exp(total - seg)  # [b,s,nh]
    wx = (decay * dt)[..., None] * xs.astype(jnp.float32)
    return jnp.einsum("bsd,bshp->bhpd", B, wx)  # [b,nh,hd,ds]


def mamba_decode(p: dict, cfg: ArchConfig, x: Array, cache, pos: Array):
    """Single-token Mamba-2 step: O(1) state update (constant memory)."""
    ssm = cfg.ssm
    b, _, d = x.shape
    di = ssm.d_inner(d)
    nh = ssm.n_heads(d)
    cx, cB, cC, ssm_state = cache  # conv tails [b, d_conv-1, *], state f32

    z = jnp.einsum("bsd,dk->bsk", x, p["w_z"])[:, 0]
    x_in = jnp.einsum("bsd,dk->bsk", x, p["w_x"])[:, 0]
    B_in = jnp.einsum("bsd,dk->bsk", x, p["w_B"])[:, 0]
    C_in = jnp.einsum("bsd,dk->bsk", x, p["w_C"])[:, 0]
    dt_raw = jnp.einsum("bsd,dk->bsk", x, p["w_dt"])[:, 0]

    def step_conv(tail, new, w):
        window = jnp.concatenate([tail, new[:, None]], axis=1)  # [b,d_conv,c]
        out = jnp.einsum("bkc,kc->bc", window, w)
        return jax.nn.silu(out.astype(jnp.float32)), window[:, 1:]

    xc, cx = step_conv(cx, x_in, p["conv_x"])
    Bc, cB = step_conv(cB, B_in, p["conv_B"])
    Cc, cC = step_conv(cC, C_in, p["conv_C"])
    xs = xc.reshape(b, nh, ssm.head_dim)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])  # [b,nh]
    A = -jnp.exp(p["A_log"])

    decay = jnp.exp(dt * A[None, :])  # [b,nh]
    ssm_state = decay[:, :, None, None] * ssm_state + jnp.einsum(
        "bd,bhp->bhpd", Bc, dt[..., None] * xs
    )
    y = jnp.einsum("bhpd,bd->bhp", ssm_state, Cc)  # [b,nh,hd]
    y = y + p["D"][None, :, None] * xs
    y = y.reshape(b, di).astype(x.dtype)
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)
    out = jnp.einsum("bk,kd->bd", y, p["out_proj"])[:, None]
    return out, (cx, cB, cC, ssm_state)
