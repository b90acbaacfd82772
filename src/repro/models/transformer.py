"""Model assembly: block-program scan-over-layers, init, forward, decode.

A model's stack is ``block_pattern × pattern_repeats + suffix_blocks``.
The repeated unit is scanned with :func:`jax.lax.scan` over stacked unit
parameters, keeping the HLO O(1) in depth (an 80-layer qwen compiles like a
single unit); heterogeneous stacks (gemma3, zamba2) repeat a heterogeneous
*unit* whose pytree structure is uniform across repeats.  Suffix blocks are
unrolled.  zamba2's shared attention block is a single (non-stacked)
parameter set invoked at every ``shared_attn`` position through per-position
adapters.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.dist.sharding import shard
from . import blocks
from .layers import (
    apply_mlp,
    apply_rope,
    bf16_grad,
    chunked_attention,
    dense_init,
    embed_init,
    init_mlp,
    rms_norm,
)

Array = jax.Array
PyTree = Any


# ---------------------------------------------------------------- per-block
def _init_block(key, cfg: ArchConfig, btype: str, dtype=jnp.bfloat16) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    p: Dict[str, Any] = {"ln1": jnp.zeros((cfg.d_model,), dtype)}
    if btype == "mamba":
        p["mamba"] = blocks.init_mamba(k1, cfg, dtype)
        return p
    if btype == "shared_attn":
        # adapters only; the shared body lives once at the model level
        p["in_adapter"] = dense_init(k1, cfg.d_model, cfg.d_model, dtype)
        p["out_adapter"] = dense_init(k2, cfg.d_model, cfg.d_model, dtype)
        return p
    # attention blocks ("attn" | "local_attn")
    if cfg.mla is not None:
        p["attn"] = blocks.init_mla(k1, cfg, dtype)
    else:
        p["attn"] = blocks.init_attention(k1, cfg, dtype)
    p["ln2"] = jnp.zeros((cfg.d_model,), dtype)
    if cfg.moe is not None:
        p["moe"] = blocks.init_moe(k2, cfg, dtype)
        if cfg.moe.num_shared_experts:
            p["shared_mlp"] = init_mlp(
                k3,
                cfg.d_model,
                cfg.moe.num_shared_experts * cfg.moe.d_ff_shared,
                dtype,
            )
    else:
        p["mlp"] = init_mlp(k2, cfg.d_model, cfg.d_ff, dtype)
    return p


def _init_shared_body(key, cfg: ArchConfig, dtype=jnp.bfloat16) -> dict:
    """zamba2 shared transformer body (attention + MLP), one copy."""
    k1, k2 = jax.random.split(key)
    return {
        "ln1": jnp.zeros((cfg.d_model,), dtype),
        "attn": blocks.init_attention(k1, cfg, dtype),
        "ln2": jnp.zeros((cfg.d_model,), dtype),
        "mlp": init_mlp(k2, cfg.d_model, cfg.d_ff, dtype),
    }


def _ffn(p: dict, cfg: ArchConfig, x: Array) -> Array:
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    if "moe" in p:
        y = blocks.moe_forward(p["moe"], cfg, h)
        if "shared_mlp" in p:
            y = y + apply_mlp(p["shared_mlp"], h)
        return y
    return apply_mlp(p["mlp"], h)


def _apply_block_full(
    p: dict,
    cfg: ArchConfig,
    btype: str,
    x: Array,
    *,
    shared_body: Optional[dict],
    q_offset: int = 0,
    causal: bool = True,
    want_cache: bool,
):
    """Full-sequence (train / prefill) application of one block."""
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    cache = None
    if btype == "mamba":
        if want_cache:
            y, cache = blocks.mamba_forward(p["mamba"], cfg, h, return_cache=True)
        else:
            y = blocks.mamba_forward(p["mamba"], cfg, h)
        x = x + y
    elif btype == "shared_attn":
        assert shared_body is not None
        inner = jnp.einsum("bsd,de->bse", h, p["in_adapter"])
        g = rms_norm(inner, shared_body["ln1"], cfg.norm_eps)
        if want_cache:
            a, cache = blocks.attention_forward(
                shared_body["attn"], cfg, g, q_offset=q_offset, causal=causal,
                return_cache=True,
            )
        else:
            a = blocks.attention_forward(
                shared_body["attn"], cfg, g, q_offset=q_offset, causal=causal
            )
        inner = inner + a
        inner = inner + apply_mlp(
            shared_body["mlp"], rms_norm(inner, shared_body["ln2"], cfg.norm_eps)
        )
        x = x + jnp.einsum("bsd,de->bse", inner, p["out_adapter"])
    else:
        window = cfg.sliding_window if btype == "local_attn" else None
        if cfg.mla is not None:
            if want_cache:
                a, cache = blocks.mla_forward(
                    p["attn"], cfg, h, q_offset=q_offset, return_cache=True
                )
            else:
                a = blocks.mla_forward(p["attn"], cfg, h, q_offset=q_offset)
        else:
            if want_cache:
                a, cache = blocks.attention_forward(
                    p["attn"], cfg, h, window=window, causal=causal,
                    q_offset=q_offset, return_cache=True,
                )
            else:
                a = blocks.attention_forward(
                    p["attn"], cfg, h, window=window, causal=causal,
                    q_offset=q_offset,
                )
        x = x + a
        x = x + _ffn(p, cfg, x)
        x = shard(bf16_grad(x), ("batch", "seq", "embed"))
        return x, cache
    x = shard(bf16_grad(x), ("batch", "seq", "embed"))
    return x, cache


def _apply_block_decode(
    p: dict,
    cfg: ArchConfig,
    btype: str,
    x: Array,
    cache,
    pos: Array,
    *,
    shared_body: Optional[dict],
):
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if btype == "mamba":
        y, cache = blocks.mamba_decode(p["mamba"], cfg, h, cache, pos)
        return x + y, cache
    if btype == "shared_attn":
        assert shared_body is not None
        inner = jnp.einsum("bsd,de->bse", h, p["in_adapter"])
        g = rms_norm(inner, shared_body["ln1"], cfg.norm_eps)
        a, cache = blocks.attention_decode(shared_body["attn"], cfg, g, cache, pos)
        inner = inner + a
        inner = inner + apply_mlp(
            shared_body["mlp"], rms_norm(inner, shared_body["ln2"], cfg.norm_eps)
        )
        return x + jnp.einsum("bsd,de->bse", inner, p["out_adapter"]), cache
    window = cfg.sliding_window if btype == "local_attn" else None
    if cfg.mla is not None:
        a, cache = blocks.mla_decode(p["attn"], cfg, h, cache, pos)
    else:
        a, cache = blocks.attention_decode(
            p["attn"], cfg, h, cache, pos, window=window
        )
    x = x + a
    x = x + _ffn(p, cfg, x)
    return x, cache


# --------------------------------------------------------------- cache init
def _block_cache_shape(cfg: ArchConfig, btype: str, batch: int, max_seq: int):
    """Abstract (shape, dtype) pytree for one block's cache."""
    hd = cfg.head_dim
    dt = jnp.bfloat16
    if btype == "mamba":
        ssm = cfg.ssm
        di = ssm.d_inner(cfg.d_model)
        tail = ssm.d_conv - 1
        return (
            jnp.zeros((batch, tail, di), dt),  # conv_x tail
            jnp.zeros((batch, tail, ssm.d_state), dt),  # conv_B tail
            jnp.zeros((batch, tail, ssm.d_state), dt),  # conv_C tail
            jnp.zeros(
                (batch, ssm.n_heads(cfg.d_model), ssm.head_dim, ssm.d_state),
                jnp.float32,
            ),
        )
    if cfg.mla is not None and btype in ("attn", "local_attn"):
        m = cfg.mla
        return (
            jnp.zeros((batch, max_seq, m.kv_lora_rank), dt),
            jnp.zeros((batch, max_seq, m.qk_rope_head_dim), dt),
        )
    # sliding-window layers keep an O(window) RING buffer, not O(seq)
    # (1024× smaller for gemma3 locals at long_500k; see §Perf)
    seq = min(max_seq, cfg.sliding_window) if btype == "local_attn" else max_seq
    return (
        jnp.zeros((batch, cfg.n_kv_heads, seq, hd), dt),
        jnp.zeros((batch, cfg.n_kv_heads, seq, hd), dt),
    )


def init_cache(cfg: ArchConfig, batch: int, max_seq: int) -> PyTree:
    """Decode caches for the whole stack: {unit: stacked, suffix: list}."""
    reps = cfg.resolved_pattern_repeats

    def unit_cache():
        return {
            f"b{i}": _block_cache_shape(cfg, bt, batch, max_seq)
            for i, bt in enumerate(cfg.block_pattern)
        }

    stacked = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (reps,) + x.shape), unit_cache()
    )
    suffix = [
        _block_cache_shape(cfg, bt, batch, max_seq) for bt in cfg.suffix_blocks
    ]
    return {"unit": stacked, "suffix": suffix}


# -------------------------------------------------------------------- model
def init_model(cfg: ArchConfig, key, dtype=jnp.bfloat16) -> PyTree:
    reps = cfg.resolved_pattern_repeats
    k_embed, k_unit, k_suffix, k_shared, k_head, k_front = jax.random.split(key, 6)

    def init_unit(k):
        ks = jax.random.split(k, len(cfg.block_pattern))
        return {
            f"b{i}": _init_block(ks[i], cfg, bt, dtype)
            for i, bt in enumerate(cfg.block_pattern)
        }

    params: Dict[str, Any] = {
        "embed": {"tokens": embed_init(k_embed, cfg.vocab, cfg.d_model, dtype)},
        "layers": jax.vmap(init_unit)(jax.random.split(k_unit, reps)),
        "suffix": [
            _init_block(k, cfg, bt, dtype)
            for k, bt in zip(
                jax.random.split(k_suffix, max(len(cfg.suffix_blocks), 1)),
                cfg.suffix_blocks,
            )
        ],
        "final_ln": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head, cfg.d_model, cfg.vocab, dtype)
    if "shared_attn" in cfg.block_pattern or "shared_attn" in cfg.suffix_blocks:
        params["shared_body"] = _init_shared_body(k_shared, cfg, dtype)
    if cfg.frontend == "vision_stub":
        params["vision_proj"] = dense_init(k_front, cfg.d_model, cfg.d_model, dtype)
    if cfg.enc_layers:
        params["encoder"] = _init_encoder(k_front, cfg, dtype)
        params["audio_proj"] = dense_init(k_front, cfg.d_model, cfg.d_model, dtype)
        # decoder cross-attention weights per decoder block
        kx = jax.random.split(k_front, reps)

        def init_cross(k):
            return {
                f"b{i}": {
                    "ln_x": jnp.zeros((cfg.d_model,), dtype),
                    "attn": blocks.init_attention(k, cfg, dtype),
                }
                for i in range(len(cfg.block_pattern))
            }

        params["cross"] = jax.vmap(init_cross)(kx)
    return params


def _init_encoder(key, cfg: ArchConfig, dtype=jnp.bfloat16):
    def init_layer(k):
        k1, k2 = jax.random.split(k)
        return {
            "ln1": jnp.zeros((cfg.d_model,), dtype),
            "attn": blocks.init_attention(k1, cfg, dtype),
            "ln2": jnp.zeros((cfg.d_model,), dtype),
            "mlp": init_mlp(k2, cfg.d_model, cfg.d_ff, dtype),
        }

    ks = jax.random.split(key, cfg.enc_layers)
    return {
        "layers": jax.vmap(init_layer)(ks),
        "final_ln": jnp.zeros((cfg.d_model,), dtype),
    }


# ----------------------------------------------------------------- forward
def _embed(cfg: ArchConfig, params, tokens: Array) -> Array:
    x = params["embed"]["tokens"][tokens]
    return shard(x, ("batch", "seq", "embed"))


def _unembed(cfg: ArchConfig, params, x: Array) -> Array:
    x = rms_norm(x, params["final_ln"], cfg.norm_eps)
    w = (
        params["embed"]["tokens"].T
        if cfg.tie_embeddings
        else params["lm_head"]
    )
    logits = jnp.einsum("bsd,dv->bsv", x.astype(jnp.float32), w.astype(jnp.float32))
    return shard(logits, ("batch", "seq", "vocab"))


def backbone_forward(
    cfg: ArchConfig,
    params,
    x: Array,
    *,
    q_offset: int = 0,
    causal: bool = True,
    want_cache: bool = False,
    remat: bool = True,
    cross_ctx: Optional[Tuple[Array, Array]] = None,
):
    """Run the block program over embeddings ``x``.

    Returns (x, caches) where caches is None unless ``want_cache``.
    """
    shared_body = params.get("shared_body")
    pattern = cfg.block_pattern

    def unit_fn(h, unit_inputs):
        unit_p = unit_inputs["p"]
        caches_out = {}
        for i, bt in enumerate(pattern):
            h, c = _apply_block_full(
                unit_p[f"b{i}"], cfg, bt, h,
                shared_body=shared_body, q_offset=q_offset, causal=causal,
                want_cache=want_cache,
            )
            if cross_ctx is not None:
                h = _cross_attend(
                    unit_inputs["cross"][f"b{i}"], cfg, h, cross_ctx
                )
            if want_cache:
                caches_out[f"b{i}"] = c
        return h, (caches_out if want_cache else None)

    if remat == "dots":
        # save matmul outputs, recompute elementwise ops only — trades the
        # full-recompute tax (×4/3 step FLOPs) for modest extra residency
        body = jax.checkpoint(
            unit_fn,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        )
    elif remat:
        body = jax.checkpoint(unit_fn)
    else:
        body = unit_fn
    xs = {"p": params["layers"]}
    if cross_ctx is not None:
        xs["cross"] = params["cross"]
    x, unit_caches = jax.lax.scan(body, x, xs)

    suffix_caches = []
    for p_blk, bt in zip(params["suffix"], cfg.suffix_blocks):
        x, c = _apply_block_full(
            p_blk, cfg, bt, x,
            shared_body=shared_body, q_offset=q_offset, causal=causal,
            want_cache=want_cache,
        )
        suffix_caches.append(c)
    caches = (
        {"unit": unit_caches, "suffix": suffix_caches} if want_cache else None
    )
    return x, caches


def _cross_attend(pc: dict, cfg: ArchConfig, x: Array, ctx_kv) -> Array:
    """Cross-attention (whisper decoder): K/V precomputed from encoder."""
    k, v = ctx_kv
    h = rms_norm(x, pc["ln_x"], cfg.norm_eps)
    b, s, _ = h.shape
    hd = cfg.head_dim
    q = jnp.einsum("bsd,dh->bsh", h, pc["attn"]["wq"]).reshape(
        b, s, cfg.n_heads, hd
    ).transpose(0, 2, 1, 3)
    out = chunked_attention(q, k, v, causal=False)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, -1)
    return x + jnp.einsum("bsh,hd->bsd", out, pc["attn"]["wo"])


def forward_hidden(
    cfg: ArchConfig,
    params,
    tokens: Array,
    *,
    extra: Optional[Dict[str, Array]] = None,
    remat: bool = True,
) -> Array:
    """Train-mode forward up to the final norm (no unembedding)."""
    x = _embed(cfg, params, tokens)
    cross_ctx = None
    if cfg.frontend == "vision_stub":
        vis = jnp.einsum(
            "bnd,de->bne", extra["patch_embeds"], params["vision_proj"]
        ).astype(x.dtype)
        x = jnp.concatenate([vis, x], axis=1)
        x = shard(x, ("batch", "seq", "embed"))
    if cfg.enc_layers:
        enc_out = encoder_forward(cfg, params, extra["frame_embeds"], remat=remat)
        cross_ctx = _encode_cross_kv(cfg, params, enc_out)
    x, _ = backbone_forward(cfg, params, x, remat=remat, cross_ctx=cross_ctx)
    return rms_norm(x, params["final_ln"], cfg.norm_eps)


def forward(
    cfg: ArchConfig,
    params,
    tokens: Array,
    *,
    extra: Optional[Dict[str, Array]] = None,
    remat: bool = True,
) -> Array:
    """Train-mode forward → logits [B, S(+vision), vocab]."""
    x = forward_hidden(cfg, params, tokens, extra=extra, remat=remat)
    w = params["embed"]["tokens"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x.astype(jnp.float32), w.astype(jnp.float32))
    return shard(logits, ("batch", "seq", "vocab"))


def encoder_forward(cfg: ArchConfig, params, frames: Array, *, remat=True) -> Array:
    """Whisper encoder over (stub) frame embeddings [B, T, d]."""
    enc = params["encoder"]
    x = jnp.einsum("btd,de->bte", frames, params["audio_proj"]).astype(
        params["audio_proj"].dtype
    )
    x = shard(x, ("batch", "seq", "embed"))

    def layer_fn(h, p):
        a = blocks.attention_forward(
            p["attn"], cfg, rms_norm(h, p["ln1"], cfg.norm_eps), causal=False
        )
        h = h + a
        h = h + apply_mlp(p["mlp"], rms_norm(h, p["ln2"], cfg.norm_eps))
        return h, None

    body = jax.checkpoint(layer_fn) if remat else layer_fn
    x, _ = jax.lax.scan(body, x, enc["layers"])
    return rms_norm(x, enc["final_ln"], cfg.norm_eps)


def _encode_cross_kv(cfg: ArchConfig, params, enc_out: Array):
    """Precompute cross-attention K/V from encoder output (first block's
    weights; K/V are shared across decoder layers in this implementation —
    an adaptation noted in DESIGN.md)."""
    pc = jax.tree_util.tree_map(lambda x: x[0], params["cross"])["b0"]
    b, t, _ = enc_out.shape
    hd = cfg.head_dim
    k = jnp.einsum("btd,dh->bth", enc_out, pc["attn"]["wk"]).reshape(
        b, t, cfg.n_kv_heads, hd
    ).transpose(0, 2, 1, 3)
    v = jnp.einsum("btd,dh->bth", enc_out, pc["attn"]["wv"]).reshape(
        b, t, cfg.n_kv_heads, hd
    ).transpose(0, 2, 1, 3)
    return k, v


# ------------------------------------------------------------------ decode
def prefill(
    cfg: ArchConfig,
    params,
    tokens: Array,
    *,
    extra: Optional[Dict[str, Array]] = None,
    max_seq: Optional[int] = None,
    remat: bool = True,
):
    """Prefill: forward + emit KV caches padded to ``max_seq``."""
    b, s = tokens.shape
    max_seq = max_seq or s
    x = _embed(cfg, params, tokens)
    cross_ctx = None
    if cfg.frontend == "vision_stub":
        vis = jnp.einsum(
            "bnd,de->bne", extra["patch_embeds"], params["vision_proj"]
        ).astype(x.dtype)
        x = jnp.concatenate([vis, x], axis=1)
    if cfg.enc_layers:
        enc_out = encoder_forward(cfg, params, extra["frame_embeds"], remat=remat)
        cross_ctx = _encode_cross_kv(cfg, params, enc_out)
    x, caches = backbone_forward(
        cfg, params, x, want_cache=True, remat=remat, cross_ctx=cross_ctx
    )
    logits = _unembed(cfg, params, x[:, -1:])
    caches = _pad_caches(cfg, caches, max_seq)
    if cross_ctx is not None:
        caches["cross_kv"] = cross_ctx
    return logits, caches


def prefill_chunk_supported(cfg: ArchConfig) -> bool:
    """True when :func:`prefill_chunk` serves ``cfg``: plain full attention
    in every block (:func:`paged_decode_supported`) and a dense FFN.  A
    MoE FFN stays on the per-token path: a ``w``-token dispatch meets an
    expert capacity that one-token steps never hit."""
    return paged_decode_supported(cfg) and cfg.moe is None


def prefill_chunk(
    cfg: ArchConfig,
    params,
    tokens: Array,  # [w] prompt tokens of one slot
    caches,  # the engine's stacked slot caches (slot axis = n_slots)
    slot: Array,  # scalar int32
    pos0: Array,  # scalar int32 — position of tokens[0]
):
    """Continue one slot's prefill by ``w`` tokens as ONE ``[1, w]``
    forward over its cache, so the weights stream once per chunk.

    Per block, the ``w`` new K/V rows (RoPE at ``pos0 + arange(w)``) are
    written into the slot's cache at ``pos0`` with a dynamic update slice
    on the stacked caches, and the ``w`` queries attend the slot's whole
    ``max_seq`` cache under ``key_pos <= pos0 + i``: the earlier positions
    and causality inside the chunk, softmax in float32 as in
    :func:`decode_step`.  Only the slot's rows move; every other slot and
    every position outside the chunk keep their bits.  Serves the stacks
    :func:`prefill_chunk_supported` admits.
    Returns (last position's logits [V], updated caches).
    """
    w = tokens.shape[0]
    x = _embed(cfg, params, tokens[None])
    positions = (pos0 + jnp.arange(w, dtype=jnp.int32))[None, None, :]

    def attn_block(p, x_in, cache, at):
        """``at`` indexes the slot's ``[KV, max_seq, hd]`` in ``cache``:
        (layer, slot) in the scanned unit, (slot,) in the suffix."""
        h = rms_norm(x_in, p["ln1"], cfg.norm_eps)
        q, k, v = blocks._qkv(p["attn"], cfg, h)  # [1, {H,KV}, w, hd]
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        lead = (1,) * len(at)
        zero = jnp.int32(0)

        def write_then_read(c, new):
            new, start = new.reshape(lead + new.shape[1:]), pos0
            if w == 1 and c.shape[-2] > 1:
                # XLA lays the whole stacked cache out anew around a
                # one-row update (two full copies in, two out); a two-row
                # window whose other row keeps its own bits does not
                start = jnp.minimum(pos0, c.shape[-2] - 2)
                window = new.shape[:-2] + (2, new.shape[-1])
                old = jax.lax.dynamic_slice(c, (*at, zero, start, zero), window)
                is_new = (jnp.arange(2) == pos0 - start)[:, None]
                new = jnp.where(is_new, new, old)
            c = jax.lax.dynamic_update_slice(c, new, (*at, zero, start, zero))
            mine = jax.lax.dynamic_slice(
                c, (*at, zero, zero, zero), lead + c.shape[len(at):]
            )
            return c, mine.reshape((1,) + c.shape[len(at):])

        kc, k_all = write_then_read(cache[0], k)
        vc, v_all = write_then_read(cache[1], v)
        out = chunked_attention(q, k_all, v_all, causal=True, q_offset=pos0)
        out = out.transpose(0, 2, 1, 3).reshape(1, w, -1)
        x_out = x_in + jnp.einsum("bsh,hd->bsd", out, p["attn"]["wo"])
        x_out = x_out + _ffn(p, cfg, x_out)
        return x_out, (kc, vc)

    def unit_fn(carry, inputs):
        h, unit_c = carry
        unit_p, layer = inputs
        new_c = {}
        for i in range(len(cfg.block_pattern)):
            h, new_c[f"b{i}"] = attn_block(
                unit_p[f"b{i}"], h, unit_c[f"b{i}"], (layer, slot)
            )
        return (h, new_c), None

    reps = cfg.resolved_pattern_repeats
    (x, new_unit), _ = jax.lax.scan(
        unit_fn,
        (x, caches["unit"]),
        (params["layers"], jnp.arange(reps, dtype=jnp.int32)),
    )
    new_suffix = []
    for p_blk, c_blk in zip(params["suffix"], caches["suffix"]):
        x, c = attn_block(p_blk, x, c_blk, (slot,))
        new_suffix.append(c)

    logits = _unembed(cfg, params, x[:, -1:])
    return logits[0, 0], {"unit": new_unit, "suffix": new_suffix}


def _pad_caches(cfg: ArchConfig, caches, max_seq: int):
    """Pad prefill K/V (seq axis) out to the decode cache size.

    Unit caches carry a leading scan (repeats) dim; suffix caches don't —
    the seq axis is uniformly ``ndim − 2`` for both K/V and MLA latents.
    """

    def pad_seq(x):
        axis = x.ndim - 2
        pad_n = max_seq - x.shape[axis]
        if pad_n <= 0:
            return x
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad_n)
        return jnp.pad(x, widths)

    def to_ring(x):
        """Fold a full prefill K/V (seq axis) into the ring layout: slot j
        holds the last prefill position p < S with p % window == j."""
        w = cfg.sliding_window
        axis = x.ndim - 2
        s = x.shape[axis]
        if s <= w:
            widths = [(0, 0)] * x.ndim
            widths[axis] = (0, w - s)
            return jnp.pad(x, widths)  # slot j == position j (not wrapped)
        j = jnp.arange(w)
        idx = (s - 1) - ((s - 1 - j) % w)
        return jnp.take(x, idx, axis=axis)

    def pad_kv(c, bt):
        if c is None:
            return None
        if bt == "mamba":
            return c  # conv/ssm states have no seq axis
        if bt == "local_attn" and cfg.mla is None:
            return jax.tree_util.tree_map(to_ring, c)
        return jax.tree_util.tree_map(pad_seq, c)

    unit = {
        f"b{i}": pad_kv(caches["unit"][f"b{i}"], bt)
        for i, bt in enumerate(cfg.block_pattern)
    }
    suffix = [
        pad_kv(c, bt) for c, bt in zip(caches["suffix"], cfg.suffix_blocks)
    ]
    return {"unit": unit, "suffix": suffix}


def decode_step(
    cfg: ArchConfig,
    params,
    tokens: Array,  # [B, 1]
    caches,
    pos: Array,  # scalar int32 — current position
):
    """One decode step; returns (logits [B,1,V], updated caches)."""
    x = _embed(cfg, params, tokens)
    shared_body = params.get("shared_body")
    pattern = cfg.block_pattern
    cross_kv = caches.get("cross_kv")

    def unit_fn(h, inputs):
        unit_p, unit_c = inputs["p"], inputs["c"]
        new_c = {}
        for i, bt in enumerate(pattern):
            h, c = _apply_block_decode(
                unit_p[f"b{i}"], cfg, bt, h, unit_c[f"b{i}"], pos,
                shared_body=shared_body,
            )
            if cross_kv is not None:
                h = _cross_attend(inputs["cross"][f"b{i}"], cfg, h, cross_kv)
            new_c[f"b{i}"] = c
        return h, new_c

    xs = {"p": params["layers"], "c": caches["unit"]}
    if cross_kv is not None:
        xs["cross"] = params["cross"]
    x, new_unit = jax.lax.scan(unit_fn, x, xs)

    new_suffix = []
    for p_blk, c_blk, bt in zip(
        params["suffix"], caches["suffix"], cfg.suffix_blocks
    ):
        x, c = _apply_block_decode(
            p_blk, cfg, bt, x, c_blk, pos, shared_body=shared_body
        )
        new_suffix.append(c)

    logits = _unembed(cfg, params, x)
    new_caches = {"unit": new_unit, "suffix": new_suffix}
    if cross_kv is not None:
        new_caches["cross_kv"] = cross_kv
    return logits, new_caches


# ------------------------------------------------------- paged decode (pool)
def paged_decode_supported(cfg: ArchConfig) -> bool:
    """True when the whole stack is plain full attention — the layout the
    paged-decode Pallas kernel serves.  MLA latents, Mamba states,
    encoder-decoder cross-attention and sliding-window rings keep their
    own cache shapes and stay on the dense vmapped path."""
    stack = list(cfg.block_pattern) + list(cfg.suffix_blocks)
    return (
        cfg.mla is None
        and cfg.ssm is None
        and not cfg.enc_layers
        and cfg.frontend in (None, "none")
        and bool(stack)
        and all(bt == "attn" for bt in stack)
    )


def _quantize_pool_int8(pool: Array):
    """Per-page absmax int8 quantization of a ``[KV, n, P, hd]`` pool view:
    returns (codes int8, scales f32 [KV, n]) in the layout
    :func:`kernels.ops.paged_decode_attention_int8` consumes.  The scale
    floor keeps all-zero (never-written pad) pages from dividing by 0."""
    absmax = jnp.max(jnp.abs(pool), axis=(2, 3))
    scales = jnp.maximum(absmax / 127.0, 1e-8).astype(jnp.float32)
    codes = jnp.round(pool / scales[..., None, None]).astype(jnp.int8)
    return codes, scales


def decode_step_paged(
    cfg: ArchConfig,
    params,
    tokens: Array,  # [B, 1] — compacted active rows (B may be padded)
    caches,  # the engine's per-slot dense caches (slot axis = n_slots)
    poss: Array,  # [B] int32 per-row decode position
    row_slot: Array,  # [B] int32 slot of each row; n_slots for pad rows
    page_table: Array,  # [B, W] int32 pool page ids (width-trimmed)
    seq_lens: Array,  # [B] int32 tokens to attend (pos+1; 0 for pad rows)
    page_src_slot: Array,  # [n_pool] int32 owning slot of each pool page
    page_src_idx: Array,  # [n_pool] int32 logical page index in that slot
    *,
    page_tokens: int,
    interpret: bool,
    int8: bool = False,
):
    """One decode step through :func:`kernels.ops.paged_decode_attention`.

    With ``int8=True`` the gathered pool views are absmax-quantized per
    page row and attention runs through
    :func:`kernels.ops.paged_decode_attention_int8` instead — the f32
    kernel stays available as the differential oracle (``int8=False``).

    The per-slot dense caches remain the storage of truth (COW, tier
    promotion and migration all operate on them); this step materializes
    the *pool view* the kernel wants — ``[KV, n_pool, P, hd]`` — by
    gathering each live pool page from its owning slot via the provenance
    arrays, then runs ONE kernel call per layer over a (B, H, W) grid: the
    kernel maps query head h to kv head h // G itself, so the [B, W] block
    table is shared by every head.  Rows are expected sorted by length
    (descending) and W trimmed to the longest resident request — short
    decodes then never pay DMAs for the long tail.  New-token K/V are
    scatter-written into the slot caches *before* the gather (matching the
    dense path, which attends positions ``<= pos`` inclusive); pad rows
    carry ``row_slot == n_slots`` so their writes drop out-of-bounds.
    In device traces the layer scan runs under the named scope
    ``layers`` and each block's parts under ``kv_write``, ``pool_view``,
    ``attention``, ``wo`` and ``ffn``.
    Returns (logits [B, 1, V], updated caches).
    """
    from repro.kernels import ops as kernel_ops

    x = _embed(cfg, params, tokens)
    B = tokens.shape[0]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    P = page_tokens
    positions = poss[:, None, None]  # [..., s] with s == 1

    def attn_block(p, x_in, cache):
        h = rms_norm(x_in, p["ln1"], cfg.norm_eps)
        ap = p["attn"]
        q, k_new, v_new = blocks._qkv(ap, cfg, h)  # [B, {H,KV}, 1, hd]
        q = apply_rope(q, positions, cfg.rope_theta)
        k_new = apply_rope(k_new, positions, cfg.rope_theta)
        kc, vc = cache  # [n_slots, KV, max_seq, hd]
        with jax.named_scope("kv_write"):
            kc = kc.at[row_slot, :, poss].set(k_new[:, :, 0, :], mode="drop")
            vc = vc.at[row_slot, :, poss].set(v_new[:, :, 0, :], mode="drop")
        # pool view: pad seq to whole pages, gather page provenance
        with jax.named_scope("pool_view"):
            n_slots, _, max_seq, _ = kc.shape
            lp = -(-max_seq // P)
            pad = lp * P - max_seq
            kcp = jnp.pad(kc, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else kc
            vcp = jnp.pad(vc, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else vc
            kcr = kcp.reshape(n_slots, KV, lp, P, hd)
            vcr = vcp.reshape(n_slots, KV, lp, P, hd)
            k_pool = kcr[page_src_slot, :, page_src_idx]  # [n_pool, KV, P, hd]
            v_pool = vcr[page_src_slot, :, page_src_idx]
            k_pool = k_pool.transpose(1, 0, 2, 3)  # [KV, n_pool, P, hd]
            v_pool = v_pool.transpose(1, 0, 2, 3)
            if int8:
                k_codes, k_scales = _quantize_pool_int8(k_pool)
                v_codes, v_scales = _quantize_pool_int8(v_pool)
        qf = q[:, :, 0, :]  # [B, H, hd]
        with jax.named_scope("attention"):
            if int8:
                out = kernel_ops.paged_decode_attention_int8(
                    qf, k_codes, v_codes, k_scales, v_scales,
                    page_table, seq_lens, interpret=interpret,
                )
            else:
                out = kernel_ops.paged_decode_attention(
                    qf, k_pool, v_pool, page_table, seq_lens,
                    interpret=interpret,
                )
        out = out.reshape(B, 1, H * hd)
        with jax.named_scope("wo"):
            y = jnp.einsum("bsh,hd->bsd", out, ap["wo"])
        x_out = x_in + y
        with jax.named_scope("ffn"):
            x_out = x_out + _ffn(p, cfg, x_out)
        return x_out, (kc, vc)

    def unit_fn(h, inputs):
        unit_p, unit_c = inputs["p"], inputs["c"]
        new_c = {}
        for i in range(len(cfg.block_pattern)):
            h, c = attn_block(unit_p[f"b{i}"], h, unit_c[f"b{i}"])
            new_c[f"b{i}"] = c
        return h, new_c

    # the scan's own ops (slicing each layer's cache out of the stack and
    # writing it back) land under this scope, outside the block's scopes
    with jax.named_scope("layers"):
        x, new_unit = jax.lax.scan(
            unit_fn, x, {"p": params["layers"], "c": caches["unit"]}
        )
    new_suffix = []
    for p_blk, c_blk in zip(params["suffix"], caches["suffix"]):
        x, c = attn_block(p_blk, x, c_blk)
        new_suffix.append(c)

    logits = _unembed(cfg, params, x)
    return logits, {"unit": new_unit, "suffix": new_suffix}
