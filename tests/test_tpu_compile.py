"""Ahead-of-time compiles for a TPU v5e chip that is described, not attached.

The TPU compiler is installed with jaxlib, so the paged-decode kernels, the
full-width ``decode_step_paged`` step and the chunked-prefill forward are
lowered through Mosaic and XLA:TPU here, with ``JAX_PLATFORMS=cpu``.  That catches what interpret mode
cannot: block shapes the chip's tiling refuses, scalar-prefetch operands
past the 1 MiB of SMEM, a step that does not fit the chip's HBM.  Nothing
runs, so these tests say nothing about results or times.

The topology is described inside a module fixture (never at import time):
only one process may load the TPU library, and every xdist worker imports
this file.  All chip compiles stay in this one file for the same reason.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels.paged_decode import (
    paged_decode_attention,
    paged_decode_attention_int8,
)
from repro.models import (
    decode_step_paged,
    init_cache,
    init_model,
    prefill_chunk,
)

# internlm2-1.8b attention widths and the engine's 16-token pages
H, KV, HD, PAGE = 16, 8, 128, 16
V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means: no chip compiler
        jax.config.update("jax_enable_compilation_cache", was_on)
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()
    mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _full_width(one_chip, cfg, n_slots, max_seq):
    """(params, caches) of ``cfg`` as abstract arrays on one chip."""

    def place(tree):
        return jax.tree_util.tree_map(
            lambda s: _spec(one_chip, s.shape, s.dtype), tree
        )

    params = place(
        jax.eval_shape(lambda: init_model(cfg, jax.random.PRNGKey(0)))
    )
    caches = place(jax.eval_shape(lambda: init_cache(cfg, n_slots, max_seq)))
    return params, caches


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize(
    "rows,width,n_pool",
    # the engine's smoke-run shape, and a batch that fills the chip's KV
    # headroom: the per-head [B·H, W] table of the old layout took all
    # 1 MiB of SMEM at 64 × 256 (bf16) and 1.5 MiB with the int8 scales
    [(8, 128, 1024), (64, 256, 8192)],
    ids=["8x128", "64x256"],
)
def test_paged_kernel_compiles(one_chip, rows, width, n_pool, int8):
    kv_dtype = jnp.int8 if int8 else jnp.bfloat16
    args = [
        _spec(one_chip, (rows, H, HD), jnp.bfloat16),
        _spec(one_chip, (KV, n_pool, PAGE, HD), kv_dtype),
        _spec(one_chip, (KV, n_pool, PAGE, HD), kv_dtype),
    ]
    if int8:
        args += [_spec(one_chip, (KV, n_pool), jnp.float32)] * 2
    args += [
        _spec(one_chip, (rows, width), jnp.int32),
        _spec(one_chip, (rows,), jnp.int32),
    ]
    kernel = paged_decode_attention_int8 if int8 else paged_decode_attention
    compiled = (
        jax.jit(lambda *a: kernel(*a, interpret=False)).lower(*args).compile()
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_step_paged_compiles_at_full_width(one_chip):
    """The engine's paged decode step for full internlm2-1.8b, 8 slots of
    2048 tokens: Mosaic kernel inside, and it fits one chip's HBM."""
    cfg = get_arch("internlm2-1.8b")
    n_slots, max_seq, rows, width, n_pool = 8, 2048, 8, 128, 1024
    params, caches = _full_width(one_chip, cfg, n_slots, max_seq)
    i32 = jnp.int32
    step = jax.jit(
        lambda p, tok, c, poss, slot, tab, lens, src_slot, src_idx: (
            decode_step_paged(
                cfg, p, tok, c, poss, slot, tab, lens, src_slot, src_idx,
                page_tokens=PAGE, interpret=False,
            )
        ),
        donate_argnums=(2,),
    )
    compiled = step.lower(
        params,
        _spec(one_chip, (rows, 1), i32),
        caches,
        _spec(one_chip, (rows,), i32),
        _spec(one_chip, (rows,), i32),
        _spec(one_chip, (rows, width), i32),
        _spec(one_chip, (rows,), i32),
        _spec(one_chip, (n_pool,), i32),
        _spec(one_chip, (n_pool,), i32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < V5E_HBM_BYTES, f"{used / 2**30:.2f} GiB > one v5e's HBM"


@pytest.mark.parametrize("w", [1, 64])
def test_chunk_prefill_compiles_at_full_width(one_chip, w):
    """The engine's chunked-prefill forward for full internlm2-1.8b, 8
    slots of 2048 tokens, at the 64-token chunk width and at one token (a
    one-row write once made XLA lay the whole cache out anew): it fits
    one chip's HBM and copies no whole stacked cache — only the slot's new
    rows move."""
    cfg = get_arch("internlm2-1.8b")
    n_slots, max_seq = 8, 2048
    params, caches = _full_width(one_chip, cfg, n_slots, max_seq)
    i32 = jnp.int32
    chunk = jax.jit(
        lambda p, tok, c, slot, pos0: prefill_chunk(cfg, p, tok, c, slot, pos0),
        donate_argnums=(2,),
    )
    compiled = chunk.lower(
        params,
        _spec(one_chip, (w,), i32),
        caches,
        _spec(one_chip, (), i32),
        _spec(one_chip, (), i32),
    ).compile()
    stacked = caches["unit"]["b0"][0].shape  # [layers, slots, KV, seq, hd]
    whole = re.compile(
        r"%(\S+) = bf16\[" + ",".join(map(str, stacked)) + r"\]\S* (\S+)\("
    )
    copies = [
        m.group(0)
        for m in whole.finditer(compiled.as_text())
        if "copy" in m.group(1) or m.group(2).startswith("copy")
    ]
    assert not copies, f"whole-cache copies: {copies}"
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < V5E_HBM_BYTES, f"{used / 2**30:.2f} GiB > one v5e's HBM"
