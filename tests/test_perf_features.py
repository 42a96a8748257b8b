"""Perf-feature correctness (EXPERIMENTS.md §Perf levers): each optimized
path must match the baseline numerically."""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models import model as M

KEY = jax.random.key(0)


def test_head_padding_exact_equivalence():
    """Zero-init padded heads: bit-identical forward."""
    cfg = get_config("qwen2-7b").reduced()
    cfg = dataclasses.replace(cfg, n_heads=3, n_kv_heads=3, head_dim=16, d_model=48)
    cfgp = dataclasses.replace(cfg, head_pad_multiple=4)
    params = M.init_params(KEY, cfg)
    paramsp = M.init_params(KEY, cfgp)
    toks = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    l1, _ = M.forward(params, cfg, {"tokens": toks})
    l2, _ = M.forward(paramsp, cfgp, {"tokens": toks})
    assert float(jnp.abs(l1 - l2).max()) == 0.0


def test_head_padding_grads_stay_zero():
    """Padded wo rows receive zero gradient (exact semantics forever)."""
    from repro.training import steps, optimizer as O
    cfg = dataclasses.replace(get_config("qwen2-7b").reduced(),
                              n_heads=3, n_kv_heads=3, head_dim=16, d_model=48,
                              head_pad_multiple=4)
    params = M.init_params(KEY, cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
    opt = O.init_opt_state(params)
    p2, _, _ = steps.train_step(params, opt, batch, cfg=cfg,
                                opt_cfg=O.AdamWConfig(total_steps=5, warmup_steps=1))
    hd = cfg.resolved_head_dim
    pad_rows = np.asarray(p2["blocks"]["attn"]["wo"][:, 3 * hd:, :], np.float32)
    assert np.abs(pad_rows).max() == 0.0


def test_int8_kv_cache_decode_close():
    cfg = get_config("granite-8b").reduced()
    cfg8 = dataclasses.replace(cfg, kv_cache_dtype="int8")
    params = M.init_params(KEY, cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 20), 0, cfg.vocab_size)
    lg, cache = M.prefill(params, cfg, {"tokens": toks}, max_len=40)
    lg8, cache8 = M.prefill(params, cfg8, {"tokens": toks}, max_len=40)
    t = jnp.argmax(lg, -1).astype(jnp.int32)
    d1, c1 = M.decode_step(params, cfg, cache, t)
    d2, c2 = M.decode_step(params, cfg8, cache8, t)
    err = float(jnp.abs(jax.nn.log_softmax(d1) - jax.nn.log_softmax(d2)).max())
    assert err < 0.15, err
    # cache stays quantized across steps
    assert c2["k"][0].dtype == jnp.int8
    t2 = jnp.argmax(d2, -1).astype(jnp.int32)
    d3, _ = M.decode_step(params, cfg8, c2, t2)
    assert not bool(jnp.isnan(d3).any())


def test_sharded_decode_multidevice():
    """shard_map split-KV flash-decode == plain decode on an 8-dev mesh
    (subprocess: needs its own XLA device-count flag)."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses, jax, jax.numpy as jnp
        from repro import compat
        from repro.configs import get_config
        from repro.models import model as M
        from repro.models.model import MeshContext
        mesh = compat.make_mesh((2, 4), ("data", "model"))
        mi = MeshContext(mesh, ("data",), "model", 4, 2)
        cfg = get_config("musicgen-medium").reduced()
        params = M.init_params(jax.random.key(0), cfg)
        emb = jax.random.normal(jax.random.key(2), (2, 12, cfg.d_model), jnp.bfloat16) * 0.02
        lg, cache = M.prefill(params, cfg, {"embeds": emb}, max_len=32)
        tok = jnp.argmax(lg, -1).astype(jnp.int32)
        lg_plain, _ = M.decode_step(params, cfg, cache, tok)
        cfg_sh = dataclasses.replace(cfg, sharded_decode_attn=True)
        lg_shard, _ = M.decode_step(params, cfg_sh, cache, tok, mesh_info=mi)
        err = float(jnp.abs(jax.nn.log_softmax(lg_plain) - jax.nn.log_softmax(lg_shard)).max())
        assert err < 2e-2, err
        # int8 + sharded
        cfg8 = dataclasses.replace(cfg, kv_cache_dtype="int8")
        cfg8s = dataclasses.replace(cfg8, sharded_decode_attn=True)
        lg8, cache8 = M.prefill(params, cfg8, {"embeds": emb}, max_len=32)
        d2, _ = M.decode_step(params, cfg8, cache8, tok)
        d3, _ = M.decode_step(params, cfg8s, cache8, tok, mesh_info=mi)
        err2 = float(jnp.abs(jax.nn.log_softmax(d2) - jax.nn.log_softmax(d3)).max())
        assert err2 < 2e-2, err2
        print("SHARDED_DECODE_OK")
    """)
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=420,
                       cwd=os.path.dirname(os.path.dirname(__file__)) or ".")
    assert "SHARDED_DECODE_OK" in r.stdout, r.stderr[-2000:]


def test_fsdp_specs_cover_all_params():
    """Every FSDP spec shards at most one dim and only divisible dims."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        from repro import compat
        from repro.configs import get_config
        from repro.launch import shardings as sh
        from repro.launch.input_specs import param_structs
        mesh = compat.make_mesh((4, 2), ("data", "model"))
        cfg = get_config("granite-8b").reduced()
        specs = sh.fsdp_param_specs(cfg, mesh)
        structs = param_structs(cfg)
        from jax.sharding import PartitionSpec as P
        def check(st, sp):
            shards = [a for a in sp if a is not None]
            assert len(shards) <= 1
            for i, a in enumerate(sp):
                if a is not None:
                    assert st.shape[i] % 8 == 0, (st.shape, sp)
        jax.tree.map(check, structs, specs, is_leaf=lambda x: isinstance(x, P))
        print("FSDP_SPECS_OK")
    """)
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=300,
                       cwd=os.path.dirname(os.path.dirname(__file__)) or ".")
    assert "FSDP_SPECS_OK" in r.stdout, r.stderr[-2000:]
