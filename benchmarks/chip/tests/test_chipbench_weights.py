"""The served weights and the reference's are one draw: the stacked tree made
in one jitted call holds, layer by layer and kind by kind, what the reference
regenerates, for each architecture.  And llama's weights and reference logits
are pinned: the digests below were taken before its code moved into
``archs/llama.py`` and ``references/llama.py``, on the CPU, and hold since
references give layer kinds."""
import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(BENCH)), "src"))

from chipbench import harness, reference, testing, weights  # noqa: E402

CONFIGS = {"llama": testing.TINY_CONFIG, "toy_moe": testing.TINY_MOE_CONFIG,
           "toy_hybrid": testing.TINY_HYBRID_CONFIG}
# where each kind's leaves sit in the served tree, the program's name of a
# reference leaf where the two differ, and whether the kind is stacked (one
# layer per index) or one copy that every application reads
PLACES = {"toy_hybrid": {"mamba": ("blocks", {"ln_gate": "norm_w"}, True),
                         "shared_attn": ("shared_attn", {"ln1": "ln"}, False)}}
PIN_SEED = 2**33 + 17
# tie_word_embeddings: (served tree, reference logits, fp8 control logits)
PINNED = {
    False: ("1555339b2d7da085063e13d66690f7cb617269cb92d1b5517e88a975a2cb8817",
            "68097dc26b141aa035652fa074d310d06e76c8407885be598020ea2c801fea3a",
            "393114d5246c20d82c92f72500ef57ad64b11a40c2b5d2d6a2f7ca1e1ca345a5"),
    True: ("7bff90e47f2d5e3aefecb9dd27dbc4dfea54ab831dba2fe5fa9737a5da485aa3",
           "928ab2ea3cff5644dba816ff5153a5e5b09d563f6f36a189293e8f9dd9202d19",
           "8934f81acdaef6eec9ab41e381d96249f23f25028b4e3e36813191fbca75e9f9"),
}


@pytest.fixture(scope="module")
def copy_root(tmp_path_factory):
    """A copy of the benchmark with the second architecture's files added."""
    return os.path.dirname(testing.tiny_bench(str(tmp_path_factory.mktemp("weights"))))


def _modules(root, name):
    return (harness.load_module(os.path.join(root, "archs", name + ".py"), "t_arch_" + name),
            harness.load_module(os.path.join(root, "references", name + ".py"),
                                "t_ref_" + name))


def _leaves(tree):
    """Leaves by the last key of their path: each name occurs once."""
    out = {}
    for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = path[-1].key
        assert name not in out
        out[name] = a
    return out


@pytest.mark.parametrize("arch,tie", [
    pytest.param("llama", False, id="False"), pytest.param("llama", True, id="True"),
    pytest.param("toy_moe", False, id="toy_moe-False"),
    pytest.param("toy_moe", True, id="toy_moe-True"),
    pytest.param("toy_hybrid", False, id="toy_hybrid-False"),
    pytest.param("toy_hybrid", True, id="toy_hybrid-True")])
def test_served_tree_matches_the_per_layer_draw(copy_root, arch, tie):
    cfg = dict(CONFIGS[arch], tie_word_embeddings=tie)
    arch_mod, ref = _modules(copy_root, arch)
    mcfg = arch_mod.model_config(cfg)
    seed = 2**33 + 9
    p = arch_mod.served_params(cfg, mcfg, seed, ref)
    key, dtype = weights.root_key(seed), jnp.dtype(cfg["torch_dtype"])
    kinds = weights.layer_kinds(ref, cfg)
    places = PLACES.get(arch, {weights.ONE_KIND: ("blocks", {}, True)})
    assert set(p) == {"embed", "final_norm", "lm_head"} | {t for t, _, _ in places.values()}
    # as the reference regenerates it: one layer in a jitted call, the key
    # an argument (a constant key would let the compiler fold the draw)
    draw = jax.jit(lambda k, li, kind: ref.layer_weights(k, li, cfg, kind, dtype),
                   static_argnums=2)
    for kind, (top, names, stacked) in places.items():
        got = _leaves(p[top])
        assert set(got) == {names.get(n, n) for n in ref.layer_shapes(cfg, kind)}
        at = [li for li, x in enumerate(kinds) if x == kind]
        for j, li in enumerate(at):
            w = draw(key, li, kind)
            for n in w:
                a = np.asarray(got[names.get(n, n)])
                np.testing.assert_array_equal(a[j] if stacked else a,
                                              np.asarray(w[n]).astype(a.dtype))
    embed, head, norm = jax.jit(
        lambda k: weights.embed_weights(k, cfg, mcfg.padded_vocab, dtype))(key)
    np.testing.assert_array_equal(np.asarray(p["embed"]), np.asarray(embed))
    np.testing.assert_array_equal(np.asarray(p["lm_head"]), np.asarray(head))
    np.testing.assert_array_equal(np.asarray(p["final_norm"]), np.asarray(norm))
    assert np.array_equal(np.asarray(head), np.asarray(embed).T) == tie


def _tree_digest(tree):
    h = hashlib.sha256()
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, a in sorted(flat, key=lambda x: jax.tree_util.keystr(x[0])):
        a = np.asarray(a)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _logits_digest(logits):
    h = hashlib.sha256()
    for x in logits:
        x = np.asarray(x, np.float32)
        h.update(str(x.shape).encode())
        h.update(x.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("tie", [False, True])
def test_llama_weights_and_logits_are_pinned(tie):
    cfg = dict(testing.TINY_CONFIG, tie_word_embeddings=tie)
    arch, ref = _modules(BENCH, "llama")
    mcfg = arch.model_config(cfg)
    assert _tree_digest(arch.served_params(cfg, mcfg, PIN_SEED, ref)) == PINNED[tie][0]
    rng = np.random.default_rng(7)
    seqs = [rng.integers(0, 256, size=n).tolist() for n in (37, 200)]
    pos = [list(range(30, 37)), list(range(150, 200))]
    for i, control in ((1, False), (2, True)):
        r = reference.Layerwise(ref, cfg, PIN_SEED, mcfg.padded_vocab, 256, control=control)
        assert _logits_digest(r.logits(seqs, pos)) == PINNED[tie][i]


def test_seeds_above_32_bits_differ():
    a = jax.random.key_data(weights.root_key(5))
    b = jax.random.key_data(weights.root_key(2**32 + 5))
    assert not np.array_equal(np.asarray(a), np.asarray(b))
