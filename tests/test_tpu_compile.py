"""Compile the main-path Pallas kernels and whole model steps at published
widths for a described TPU v5e.

Nothing runs: the chip's compiler (installed with libtpu) compiles for a
topology that is described, not attached.  This catches what interpret mode
cannot see — blocks that break the (8, 128) tiling rule, kernels that ask for
too much VMEM, programs that do not fit the chip's memory — at no chip time.

The topology is described inside a fixture, never at import time: only one
process may load the TPU library, and every test worker imports this file.
"""
import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.models import model as model_mod
from repro.serving.engine import make_decode

V5E_HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it is held by another process
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but can
    # never be read back without the chip; keep these compiles out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(tree, sharding):
    return jax.tree.map(lambda a: _spec(a.shape, a.dtype, sharding), tree)


def _compile_with_kernel(fn, *args, kernel=None):
    """Compile ``fn``; its program must hold a Pallas kernel, and with
    ``kernel`` every one must carry that name (``pallas_call(name=...)``) in
    its op metadata, which the profiler's trace reports."""
    compiled = jax.jit(fn).lower(*args).compile()
    calls = [ln for ln in compiled.as_text().splitlines() if "tpu_custom_call" in ln]
    assert calls, "no Pallas kernel in the program"
    if kernel is not None:
        named = re.compile(rf'op_name="[^"]*/{kernel}/pallas_call"')
        assert all(named.search(ln) for ln in calls), calls
    return compiled


@pytest.mark.parametrize("seq", [200, 2048])
def test_flash_attention_compiles_at_granite_widths(one_chip, seq):
    cfg = get_config("granite-8b")
    hd = cfg.resolved_head_dim
    q = _spec((1, seq, cfg.n_heads, hd), jnp.bfloat16, one_chip)
    kv = _spec((1, seq, cfg.n_kv_heads, hd), jnp.bfloat16, one_chip)
    _compile_with_kernel(
        functools.partial(ops.flash_attention, causal=True, impl="pallas"), q, kv, kv,
        kernel="flash_attention")


def test_decode_attention_compiles_at_granite_widths(one_chip):
    cfg = get_config("granite-8b")
    hd = cfg.resolved_head_dim
    q = _spec((8, cfg.n_heads, hd), jnp.bfloat16, one_chip)
    cache = _spec((8, 2048, cfg.n_kv_heads, hd), jnp.bfloat16, one_chip)
    lengths = _spec((8,), jnp.int32, one_chip)
    _compile_with_kernel(
        functools.partial(ops.decode_attention, impl="pallas"), q, cache, cache, lengths,
        kernel="decode_attention")


@pytest.mark.parametrize("arch", ["granite-8b", "deepseek-7b"])
def test_decode_attention_compiles_on_a_stacked_cache(one_chip, arch):
    """The decode kernel reads one layer of a (L, 16, 2048, KV, hd) stack in
    place; its tiles fit the kernel's VMEM for 8 KV heads and for 32."""
    cfg = get_config(arch)
    hd = cfg.resolved_head_dim
    q = _spec((16, cfg.n_heads, hd), jnp.bfloat16, one_chip)
    cache = _spec((4, 16, 2048, cfg.n_kv_heads, hd), jnp.bfloat16, one_chip)
    lengths = _spec((16,), jnp.int32, one_chip)
    layer = _spec((), jnp.int32, one_chip)
    _compile_with_kernel(
        lambda q, k, v, n, i: ops.decode_attention(q, k, v, n, impl="pallas", layer=i),
        q, cache, cache, lengths, layer, kernel="decode_attention")


def test_ssd_scan_compiles_at_mamba2_widths(one_chip):
    cfg = get_config("mamba2-2.7b")
    s = cfg.ssm
    h, seq = s.n_heads(cfg.d_model), 1024
    x = _spec((1, seq, h, s.head_dim), jnp.bfloat16, one_chip)
    dt = _spec((1, seq, h), jnp.float32, one_chip)
    per_head = _spec((h,), jnp.float32, one_chip)
    bc = _spec((1, seq, s.n_groups, s.d_state), jnp.bfloat16, one_chip)
    _compile_with_kernel(
        functools.partial(ops.ssd_scan, chunk=s.chunk, impl="pallas"),
        x, dt, per_head, bc, bc, per_head)


@pytest.mark.parametrize("phase", ["decode", "prefill"])
def test_granite_step_compiles_at_two_layers(one_chip, phase):
    """One whole granite-8b step, widths as published, depth cut to 2."""
    cfg = dataclasses.replace(get_config("granite-8b"), n_layers=2, attn_impl="pallas")
    batch, max_len = 8, 2048
    params = _on(jax.eval_shape(
        functools.partial(model_mod.init_params, cfg=cfg), jax.random.key(0)), one_chip)
    if phase == "decode":
        cache = _on(jax.eval_shape(
            lambda: model_mod.init_cache(cfg, batch, max_len)), one_chip)
        tokens = _spec((batch,), jnp.int32, one_chip)
        compiled = _compile_with_kernel(
            lambda p, c, t: model_mod.decode_step(p, cfg, c, t), params, cache, tokens,
            kernel="decode_attention")
    else:
        tokens = _spec((1, 1024), jnp.int32, one_chip)
        compiled = _compile_with_kernel(
            lambda p, t: model_mod.prefill(p, cfg, {"tokens": t}, max_len),
            params, tokens, kernel="flash_attention")
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES


def test_engine_decode_writes_the_cache_in_place(one_chip):
    """The engine's decode step at granite widths, depth cut to 4, 16 slots
    of 2048: the donated cache is aliased to the output, and no temporary as
    large as the cache is left (a copy of one layer's slice would fit under
    that bound; a whole-cache select or write-back would not)."""
    cfg = dataclasses.replace(get_config("granite-8b"), n_layers=4, attn_impl="pallas")
    batch, max_len = 16, 2048
    params = _on(jax.eval_shape(
        functools.partial(model_mod.init_params, cfg=cfg), jax.random.key(0)), one_chip)
    cache = _on(jax.eval_shape(lambda: model_mod.init_cache(cfg, batch, max_len)), one_chip)
    compiled = make_decode(cfg).lower(
        params, cache, _spec((batch,), jnp.int32, one_chip),
        _spec((batch,), jnp.bool_, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    cache_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < cache_bytes
