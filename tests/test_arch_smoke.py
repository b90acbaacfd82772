"""Per-architecture smoke tests (deliverable f).

Every assigned arch instantiates a REDUCED same-family config (small dims,
few experts, tiny vocab) and runs one forward + one train step on CPU,
asserting output shapes and finiteness.  The FULL configs are exercised
only via the dry-run (ShapeDtypeStructs, no allocation).
"""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS
from repro.models import decode_step, forward, init_model, prefill
from repro.optim.adamw import AdamWConfig
from repro.train.train_step import make_train_step

ALL_ARCHS = sorted(ARCHS)


def _batch(cfg, key, b=2, s=16):
    tokens = jax.random.randint(key, (b, s), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": tokens}
    if cfg.frontend == "vision_stub":
        batch["patch_embeds"] = jax.random.normal(
            key, (b, cfg.vision_tokens, cfg.d_model), jnp.bfloat16
        )
    if cfg.enc_layers:
        batch["frame_embeds"] = jax.random.normal(
            key, (b, s * 2, cfg.d_model), jnp.bfloat16
        )
    return batch


@pytest.fixture(scope="module")
def key():
    return jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_shapes_and_finiteness(arch, key):
    cfg = ARCHS[arch].smoke()
    params = init_model(cfg, key)
    batch = _batch(cfg, key)
    extra = {k: v for k, v in batch.items() if k not in ("tokens", "labels")}
    logits = forward(cfg, params, batch["tokens"], extra=extra or None, remat=False)
    b, s = batch["tokens"].shape
    expect_s = s + (cfg.vision_tokens if cfg.frontend == "vision_stub" else 0)
    assert logits.shape == (b, expect_s, cfg.vocab)
    assert bool(jnp.isfinite(logits).all()), f"{arch}: non-finite logits"


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_one_train_step(arch, key):
    cfg = ARCHS[arch].smoke()
    params = init_model(cfg, key)
    from repro.optim import adamw

    opt_state = adamw.init(params)
    step = make_train_step(
        cfg, AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=4), remat=False
    )
    batch = _batch(cfg, key)
    new_params, new_opt, metrics = step(params, opt_state, batch)
    assert bool(jnp.isfinite(metrics["loss"])), f"{arch}: non-finite loss"
    assert int(new_opt.step) == 1
    # parameters actually moved
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max()),
        params,
        new_params,
    )
    assert max(jax.tree_util.tree_leaves(moved)) > 0.0


@pytest.mark.parametrize(
    "arch",
    ["internlm2-1.8b", "gemma3-1b", "qwen1.5-110b", "mamba2-2.7b",
     "zamba2-1.2b", "whisper-base"],
)
def test_prefill_decode_matches_forward(arch, key):
    """Prefill + step-by-step decode must reproduce full-forward logits
    (exact for dense; small bf16/state tolerance for SSM; MoE archs are
    excluded — capacity dropping differs between batch shapes by design)."""
    cfg = ARCHS[arch].smoke()
    params = init_model(cfg, key)
    S, B, GEN = 12, 2, 3
    batch = _batch(cfg, key, b=B, s=S + GEN)
    tokens = batch["tokens"]
    extra = {k: v for k, v in batch.items() if k not in ("tokens", "labels")}
    full = forward(cfg, params, tokens, extra=extra or None, remat=False)
    logits_p, caches = prefill(
        cfg, params, tokens[:, :S], extra=extra or None, max_seq=S + GEN,
        remat=False,
    )
    tol = 0.15 if cfg.ssm is not None else 1e-3
    assert float(jnp.abs(logits_p[:, -1] - full[:, S - 1]).max()) <= tol
    for t in range(GEN):
        logits_d, caches = decode_step(
            cfg, params, tokens[:, S + t : S + t + 1], caches, jnp.int32(S + t)
        )
        err = float(jnp.abs(logits_d[:, 0] - full[:, S + t]).max())
        assert err <= tol, f"{arch} decode step {t}: err {err}"


@pytest.mark.parametrize("prompt", [1, 2])
def test_mamba_prompt_shorter_than_conv(prompt, key):
    """A Mamba prefill shorter than the conv's ``d_conv - 1`` tail leaves
    a full-size conv cache (zeros before the prompt), and decode from it
    reproduces the full forward."""
    cfg = ARCHS["mamba2-2.7b"].smoke()
    assert prompt < cfg.ssm.d_conv - 1
    params = init_model(cfg, key)
    GEN = 3
    tokens = _batch(cfg, key, b=2, s=prompt + GEN)["tokens"]
    full = forward(cfg, params, tokens, remat=False)
    _, caches = prefill(
        cfg, params, tokens[:, :prompt], max_seq=prompt + GEN, remat=False
    )
    for t in range(GEN):
        logits_d, caches = decode_step(
            cfg, params, tokens[:, prompt + t : prompt + t + 1], caches,
            jnp.int32(prompt + t),
        )
        err = float(jnp.abs(logits_d[:, 0] - full[:, prompt + t]).max())
        assert err <= 0.15, f"decode step {t}: err {err}"


def test_all_archs_have_param_counts_near_advertised():
    expected = {
        "granite-moe-3b-a800m": 3.3e9,
        "deepseek-v2-236b": 236e9,
        "internlm2-1.8b": 1.8e9,
        "stablelm-1.6b": 1.6e9,
        "gemma3-1b": 1.0e9,
        "qwen1.5-110b": 111e9,
        "internvl2-26b": 20e9,  # LM backbone of the 26B VLM
        "whisper-base": 0.09e9,
        "mamba2-2.7b": 2.7e9,
        "zamba2-1.2b": 1.2e9,
    }
    for arch, target in expected.items():
        n = ARCHS[arch].param_count()
        assert 0.6 * target <= n <= 1.45 * target, (
            f"{arch}: {n / 1e9:.2f}B vs advertised {target / 1e9:.2f}B"
        )


def test_ring_buffer_wrap(key):
    """Sliding-window ring cache must be EXACT through multiple wraps."""
    import dataclasses

    cfg = dataclasses.replace(ARCHS["gemma3-1b"].smoke(), sliding_window=4)
    params = init_model(cfg, key)
    S, B, GEN = 10, 2, 8  # generation wraps the 4-slot ring twice
    tokens = jax.random.randint(key, (B, S + GEN), 0, cfg.vocab)
    full = forward(cfg, params, tokens, remat=False)
    lp, caches = prefill(cfg, params, tokens[:, :S], max_seq=S + GEN, remat=False)
    assert float(jnp.abs(lp[:, -1] - full[:, S - 1]).max()) < 1e-3
    for t in range(GEN):
        ld, caches = decode_step(
            cfg, params, tokens[:, S + t : S + t + 1], caches, jnp.int32(S + t)
        )
        assert float(jnp.abs(ld[:, 0] - full[:, S + t]).max()) < 1e-3
