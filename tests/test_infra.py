"""Infrastructure tests: checkpoint, data pipeline, HLO analyzer, serving
engine, elastic restore (subprocess with a multi-device CPU mesh)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpoint as ck
from repro.configs import get_config
from repro.data.pipeline import DataConfig, batch_at
from repro.models import model as M


# ======================================================================
# checkpoint
# ======================================================================

def test_checkpoint_roundtrip(tmp_path):
    tree = {
        "a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
        "b": {"c": jnp.ones((4,), jnp.bfloat16) * 1.5,
              "d": jnp.asarray(3, jnp.int32)},
    }
    ck.save(tree, str(tmp_path), 7)
    like = jax.eval_shape(lambda: tree)
    out = ck.restore(str(tmp_path), 7, like)
    assert out["b"]["c"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(out["a"]), np.asarray(tree["a"]))
    np.testing.assert_array_equal(
        np.asarray(out["b"]["c"], np.float32), np.asarray(tree["b"]["c"], np.float32))


def test_checkpoint_atomicity(tmp_path):
    tree = {"x": jnp.zeros((2,))}
    ck.save(tree, str(tmp_path), 1)
    # a crashed write leaves only .tmp — must be ignored
    os.makedirs(tmp_path / "step_9.tmp")
    assert ck.latest_step(str(tmp_path)) == 1


def test_async_checkpointer(tmp_path):
    tree = {"x": jnp.arange(4.0)}
    ac = ck.AsyncCheckpointer(str(tmp_path))
    ac.save(tree, 3)
    ac.wait()
    assert ck.latest_step(str(tmp_path)) == 3


def test_elastic_restore_across_meshes(tmp_path):
    """Save on an 8-device (4,2) mesh, restore onto a (2,2) survivor mesh —
    the elastic re-mesh path after losing half the nodes."""
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import compat
        from repro.checkpoint import checkpoint as ck
        mesh8 = compat.make_mesh((4, 2), ("data", "model"))
        spec = {{"w": P(None, "model")}}
        w = jax.device_put(np.arange(32, dtype=np.float32).reshape(4, 8),
                           NamedSharding(mesh8, spec["w"]))
        ck.save({{"w": w}}, r"{tmp_path}", 1)
        # survivors: 4 devices
        devs = np.array(jax.devices()[:4]).reshape(2, 2)
        mesh4 = jax.sharding.Mesh(devs, ("data", "model"))
        like = jax.eval_shape(lambda: {{"w": w}})
        out = ck.restore(r"{tmp_path}", 1, like, mesh=mesh4, spec_tree=spec)
        assert out["w"].sharding.mesh.shape["model"] == 2
        np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(w))
        print("ELASTIC_OK")
    """)
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, cwd=os.path.dirname(os.path.dirname(__file__)) or ".",
                       env=env, timeout=300)
    assert "ELASTIC_OK" in r.stdout, r.stderr[-2000:]


# ======================================================================
# data pipeline
# ======================================================================

def test_data_deterministic_and_resumable():
    cfg = get_config("granite-8b").reduced()
    dc = DataConfig(seed=5, seq_len=33, global_batch=4)
    b1 = batch_at(cfg, dc, 10)
    b2 = batch_at(cfg, dc, 10)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = batch_at(cfg, dc, 11)
    assert not np.array_equal(b1["tokens"], b3["tokens"])


def test_data_host_sharding_disjoint():
    cfg = get_config("granite-8b").reduced()
    a = batch_at(cfg, DataConfig(seed=5, seq_len=17, global_batch=8, n_hosts=2, host_index=0), 3)
    b = batch_at(cfg, DataConfig(seed=5, seq_len=17, global_batch=8, n_hosts=2, host_index=1), 3)
    assert a["tokens"].shape[0] == 4
    assert not np.array_equal(a["tokens"], b["tokens"])


# ======================================================================
# HLO analyzer
# ======================================================================

def test_hlo_analyzer_scales_scan_bodies():
    from repro.launch import hlo

    def one(x, w):
        return jnp.tanh(x @ w)

    def scanned(x, ws):
        def body(h, w):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, x, ws)
        return h

    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    w1 = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    wL = jax.ShapeDtypeStruct((12, 128, 128), jnp.float32)
    f1 = hlo.analyze(jax.jit(one).lower(x, w1).compile().as_text())["flops"]
    fL = hlo.analyze(jax.jit(scanned).lower(x, wL).compile().as_text())["flops"]
    assert abs(fL / f1 - 12.0) < 0.2, (f1, fL)


def test_hlo_shape_bytes():
    from repro.launch.hlo import shape_bytes
    assert shape_bytes("f32[4,8]{1,0}") == 128
    assert shape_bytes("bf16[10]") == 20
    assert shape_bytes("(s32[], f32[2,2])") == 4 + 16


# ======================================================================
# serving engine
# ======================================================================

@pytest.fixture(scope="module")
def small_engine():
    from repro.serving.engine import ServingEngine
    cfg = get_config("musicgen-medium").reduced()
    params = M.init_params(jax.random.key(0), cfg)
    return cfg, params, ServingEngine(cfg, params, max_batch=4, max_len=64)


def test_engine_batched_matches_single(small_engine):
    """A batched engine slot must track a standalone prefill+decode loop.
    Teacher-forced (identical token stream fed to both) so the check probes
    CACHE correctness, not bf16 argmax tie-breaking."""
    cfg, params, eng = small_engine
    prompt = [5, 6, 7, 8]
    # standalone reference
    lg, cache = M.prefill(params, cfg, {"tokens": jnp.asarray([prompt], jnp.int32)}, max_len=64)
    tok = jnp.argmax(lg, -1).astype(jnp.int32)
    forced = [int(tok[0])]
    toks_single = []
    for _ in range(6):
        lg, cache = M.decode_step(params, cfg, cache, tok)
        tok = jnp.argmax(lg, -1).astype(jnp.int32)
        toks_single.append(int(tok[0]))
        forced.append(int(tok[0]))
    # batched, teacher-forced with the same stream
    slot = eng.add_request(prompt, request_id=1)
    toks_batched = []
    for i in range(6):
        eng.pending_tokens[slot] = forced[i]
        out = eng.step()
        toks_batched.append(out[slot])
    # allow isolated argmax ties under bf16: >=5 of 6 must agree exactly
    agree = sum(a == b for a, b in zip(toks_batched, toks_single, strict=True))
    assert agree >= 5, (toks_batched, toks_single)
    eng.slots[slot].active = False


def test_engine_speculative_promote_and_preempt(small_engine):
    from repro.serving.spec_serving import SlotSpeculator, render_observation
    cfg, params, eng = small_engine
    for s in eng.slots:
        s.active = False
    spec = SlotSpeculator(eng, budget_slots=2)
    from repro.core.hypothesis import BranchHypothesis, Node, NodeKind
    from repro.core.events import DEFAULT_TOOLS
    n = Node(0, NodeKind.TOOL, "search", DEFAULT_TOOLS["search"].level,
             DEFAULT_TOOLS["search"].rho, 1.0)
    h = BranchHypothesis(77, [n], [], q=0.9, context_key=())
    spec.admit([(h, 1.0)], history_prompt=[2, 3])
    assert spec.spec_slots_used() == 1
    obs = render_observation("search", {}, "pred:77:0", cfg.vocab_size)
    got = spec.match_and_promote(obs, request_id=5)
    assert got is not None
    assert not eng.slots[got].speculative
    # preemption path
    spec.admit([(h, 1.0)], history_prompt=[2, 3])
    spec.ensure_authoritative_room(len(eng.free_slots()) + 1)
    assert spec.spec_slots_used() == 0


# ======================================================================
# serving entry point
# ======================================================================

def test_serve_run_reduced_serves_and_speculates():
    """The one serving loop, end to end at the toy preset: every episode is
    served, speculation admits slots, and the prefill compiles exactly for
    the authoritative and the speculative prompt length."""
    from repro.launch import serve
    eng, r = serve.run("granite-8b", reduced=True, n_episodes=2, max_batch=4,
                       max_len=128, prompt_len=24, reason_tokens=3)
    assert r.episodes == 2 and r.tokens_decoded >= 2 * 3
    assert r.spec_admitted > 0 and r.preemptions + r.promotions == r.spec_admitted
    assert sorted(r.compiled) == ["decode", "prefill_24",
                                  f"prefill_{24 + serve.OBS_TOKENS}"]
    assert eng.last_logits.shape == (4, eng.cfg.padded_vocab)
    assert not any(s.active for s in eng.slots)


def test_jitted_init_matches_eager_init():
    """The serving entry point's jitted init draws the very same weights."""
    from repro.launch import serve
    cfg = get_config("granite-8b").reduced()
    jitted = serve.init_params(cfg, 3)
    eager = M.init_params(jax.random.key(3), cfg)
    for a, b in zip(jax.tree.leaves(jitted), jax.tree.leaves(eager), strict=True):
        assert a.dtype == b.dtype == jnp.dtype(cfg.dtype)
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_compile_cache_dir(monkeypatch):
    from repro.launch.compile_cache import use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert use_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == before   # JAX reads the env itself
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = use_compile_cache()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
