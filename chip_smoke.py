"""Bring-up check: the MURS serving engine on one TPU chip, full width.

    python chip_smoke.py

Builds internlm2-1.8b at its published config (24 layers, d_model 2048,
vocab 92544; bf16 weights drawn from a seed, nothing downloaded) through
``repro.launch.serve.build_engine`` with MURS admission, 8 slots of 2048
tokens, and serves 12 requests from two tenants to completion.  Then it
decodes one step through the paged Pallas kernel and through the dense
``decode_step`` on the same cache and compares the logits.

It fails (non-zero exit, no ``ok`` line) unless every request completed,
every token is in ``[0, vocab)``, the kernel ran compiled (not in
interpret mode) on every decode tick, the lowered paged step holds a
``tpu_custom_call``, and the logits agree within the stated tolerance.
The lines before the last are informational, from one run: they are not
benchmark metrics.  The last line is the one JSON object
``{"ok": true, "device": {...}}``.  With no TPU it exits non-zero before
building anything; there is no CPU fallback.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "internlm2-1.8b"
SLOTS, MAX_SEQ = 8, 2048
SEED = 0  # prompts; the weights come from build_engine's PRNGKey(0)
#: two prompt lengths only (each distinct length compiles a prefill): one
#: that fits the 64-token prefill budget in one shot, and one that takes
#: the chunked continuation
SHORT_PROMPT, LONG_PROMPT, NEW_TOKENS = 48, 320, 32
N_REQUESTS = 12  # more than SLOTS, so admission queues


def submit_workload(engine) -> int:
    """Two tenants: A sends short prompts, B long ones, interleaved.
    Prompts are random token ids from ``SEED``; returns the count."""
    import numpy as np

    from repro.serve import Request

    rng = np.random.default_rng(SEED)
    for i in range(N_REQUESTS):
        tenant, n = ("A", SHORT_PROMPT) if i % 2 == 0 else ("B", LONG_PROMPT)
        prompt = rng.integers(0, engine.cfg.vocab, size=n).tolist()
        engine.submit(Request(f"{tenant}{i}", tenant, prompt, NEW_TOKENS))
    return N_REQUESTS


def check_served(engine, report, submitted: int) -> list:
    """What a finished run must show on any backend; returns failures."""
    bad = []
    if report.completed != submitted or report.failed:
        bad.append(
            f"completed {report.completed}/{submitted}, failed {report.failed}"
        )
    vocab = engine.cfg.vocab
    for rid, req in engine.requests.items():
        if len(req.generated) != req.max_new_tokens:
            bad.append(f"{rid}: {len(req.generated)} of "
                       f"{req.max_new_tokens} tokens")
        if any(not 0 <= t < vocab for t in req.generated):
            bad.append(f"{rid}: token outside [0, {vocab})")
    if engine.paged_decode_ticks == 0:
        bad.append("no decode tick went through the paged kernel")
    if engine.paged_decode_ticks != engine.decode_ticks:
        bad.append(f"{engine.decode_ticks - engine.paged_decode_ticks} "
                   f"of {engine.decode_ticks} decode ticks ran dense")
    return bad


def check_compiled(engine) -> list:
    """The paged step must run as a Mosaic kernel, not interpreted."""
    import jax
    import jax.numpy as jnp

    bad = []
    if engine._kernel_interpret:
        bad.append("paged kernel runs in interpret mode")
    shape = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    lowered = engine._decode_paged.lower(
        jax.tree_util.tree_map(shape, engine.params),
        jax.tree_util.tree_map(shape, engine._caches),
        i32(1, 1), i32(1), i32(1), i32(1, 1), i32(1), i32(1), i32(1),
    )
    if "tpu_custom_call" not in lowered.as_text():
        bad.append("lowered paged step holds no tpu_custom_call")
    return bad


def paged_vs_dense(engine):
    """One decode step at position ``SHORT_PROMPT`` through
    ``decode_step_paged`` (pool pages shuffled, so the kernel's table
    indirection is exercised) and through the dense ``decode_step``, on
    the same prefilled cache.  Returns ``(max |Δlogits|, tolerance)``.

    Tolerance: the two paths share every matmul and differ only in the
    attention, whose output each rounds to bf16 once per layer (relative
    error ≤ 2^-8 between them).  To first order those differences add up
    through the residual stream, so the bound is n_layers · 2^-8 of the
    logits' scale (max |logit| of the dense step)."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import decode_step, decode_step_paged

    cfg, params, page = engine.cfg, engine.params, engine.kv.page_tokens
    rng = np.random.default_rng(SEED + 1)
    prompt = rng.integers(0, cfg.vocab, size=SHORT_PROMPT)
    # the engine's own compiled prefill at this length: [1, max_seq] cache
    _, cache = engine._prefill(params, jnp.asarray(prompt, jnp.int32)[None])
    tok = jnp.asarray(rng.integers(0, cfg.vocab, size=(1, 1)), jnp.int32)
    pos = SHORT_PROMPT
    dense, _ = jax.jit(functools.partial(decode_step, cfg))(
        params, tok, cache, jnp.int32(pos)
    )
    n_pages = pos // page + 1
    perm = rng.permutation(n_pages)  # pool page q holds logical page perm[q]
    table = np.argsort(perm)[None].astype(np.int32)  # logical j → pool page
    i32 = lambda *v: jnp.asarray(v, jnp.int32)  # noqa: E731
    paged, _ = jax.jit(
        functools.partial(
            decode_step_paged, cfg, page_tokens=page,
            interpret=engine._kernel_interpret,
        )
    )(
        params, tok, cache, i32(pos), i32(0), jnp.asarray(table),
        i32(pos + 1), jnp.zeros((n_pages,), jnp.int32),
        jnp.asarray(perm, jnp.int32),
    )
    dense = np.asarray(dense, np.float32)
    paged = np.asarray(paged, np.float32)
    if dense.shape != (1, 1, cfg.vocab) or paged.shape != dense.shape:
        raise AssertionError(f"logits shapes {dense.shape} / {paged.shape}")
    if not (np.isfinite(dense).all() and np.isfinite(paged).all()):
        raise AssertionError("non-finite logits")
    tol = cfg.n_layers * 2.0**-8 * float(np.abs(dense).max())
    return float(np.abs(paged - dense).max()), tol


def main() -> int:
    import jax
    from jax import monitoring

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "this check runs on the chip only", file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch import enable_compile_cache
    from repro.launch.serve import build_engine

    cache_dir = enable_compile_cache()
    print(f"info: device_kind {dev.device_kind}; compile cache {cache_dir}")
    compile_s = [0.0]

    def on_event(name, secs, **_):
        if name.startswith("/jax/core/compile/"):
            compile_s[0] += secs

    monitoring.register_event_duration_secs_listener(on_event)
    t0 = time.perf_counter()
    engine = build_engine(ARCH, full=True, slots=SLOTS, max_seq=MAX_SEQ)
    jax.block_until_ready((engine.params, engine._caches))
    setup_s, setup_compile_s = time.perf_counter() - t0, compile_s[0]
    setup_peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    cfg = engine.cfg
    print(f"info: {cfg.name} layers {cfg.n_layers} d_model {cfg.d_model} "
          f"vocab {cfg.vocab}; slots {SLOTS} max_seq {MAX_SEQ}")

    submitted = submit_workload(engine)
    t0, c0 = time.perf_counter(), compile_s[0]
    report = engine.run(max_ticks=4000)
    jax.block_until_ready(engine._caches)
    serve_s = time.perf_counter() - t0
    failures = check_served(engine, report, submitted)
    failures += check_compiled(engine)
    diff, tol = paged_vs_dense(engine)
    if not diff <= tol:
        failures.append(f"paged vs dense max |dlogits| {diff} > {tol}")
    monitoring.unregister_event_duration_listener(on_event)

    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    print("info (one smoke run, not benchmark metrics):")
    print(f"info: setup_s {setup_s} (weights + caches), of which "
          f"compile_s {setup_compile_s}; peak_bytes_in_use {setup_peak}")
    print(f"info: serve_wall_s {serve_s}, of which compile_s "
          f"{compile_s[0] - c0}; ticks {report.ticks}")
    print(f"info: completed {report.completed}/{submitted} failed "
          f"{report.failed} suspensions {engine.suspensions} tokens "
          f"{report.tokens_generated}")
    print(f"info: decode ticks {engine.decode_ticks}, paged "
          f"{engine.paged_decode_ticks}; kernel_interpret "
          f"{engine._kernel_interpret}")
    print(f"info: paged vs dense max |dlogits| {diff} (tolerance {tol})")
    print(f"info: peak_bytes_in_use {peak}")
    if failures:
        for f in failures:
            print(f"chip_smoke: FAIL {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
