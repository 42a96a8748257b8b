"""Per-kernel correctness: Pallas (interpret=True) and jnp-chunked
implementations vs the pure-jnp oracles, swept over shapes and dtypes."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

pytestmark = pytest.mark.slow      # compile-heavy; fast loop: -m "not slow"

RNG = np.random.default_rng(0)


def _mk(shape, dtype):
    return jnp.asarray(RNG.normal(size=shape), dtype)


ATTN_SHAPES = [
    # (B, S, H, KV, D)
    (1, 128, 4, 4, 64),
    (2, 200, 8, 2, 32),
    (1, 64, 6, 3, 128),
]


@pytest.mark.parametrize("impl", ["jnp", "pallas_interpret"])
@pytest.mark.parametrize("shape", ATTN_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 48), (False, None)])
def test_flash_attention(impl, shape, dtype, causal, window):
    b, s, h, kv, d = shape
    q, k, v = _mk((b, s, h, d), dtype), _mk((b, s, kv, d), dtype), _mk((b, s, kv, d), dtype)
    want = ref.mha_reference(q, k, v, causal=causal, window=window)
    got = ops.flash_attention(q, k, v, causal=causal, window=window, impl=impl,
                              block_q=64, block_k=64)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


DECODE_SHAPES = [
    # (B, H, KV, D, Smax)
    (2, 8, 2, 64, 256),
    (3, 4, 4, 32, 100),
    (1, 6, 2, 128, 513),
]


@pytest.mark.parametrize("impl", ["jnp", "pallas_interpret"])
@pytest.mark.parametrize("shape", DECODE_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [None, 40])
def test_decode_attention(impl, shape, dtype, window):
    b, h, kv, d, smax = shape
    q = _mk((b, h, d), dtype)
    kc, vc = _mk((b, smax, kv, d), dtype), _mk((b, smax, kv, d), dtype)
    lens = jnp.asarray(RNG.integers(1, smax + 1, size=(b,)), jnp.int32)
    want = ref.decode_attention_reference(q, kc, vc, lens, window=window)
    got = ops.decode_attention(q, kc, vc, lens, window=window, impl=impl, block_k=64)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("impl", ["jnp", "pallas_interpret"])
@pytest.mark.parametrize("layer", [0, 2])
def test_decode_attention_reads_a_layer_of_the_stack(impl, layer):
    """With ``layer``, the caches are every layer's stack and the query
    attends to that layer's cache alone."""
    b, h, kv, d, smax = 2, 8, 2, 64, 200
    q = _mk((b, h, d), jnp.float32)
    kc, vc = _mk((3, b, smax, kv, d), jnp.float32), _mk((3, b, smax, kv, d), jnp.float32)
    lens = jnp.asarray([37, 200], jnp.int32)
    want = ref.decode_attention_reference(q, kc[layer], vc[layer], lens)
    got = ops.decode_attention(q, kc, vc, lens, impl=impl, block_k=64,
                               layer=jnp.int32(layer))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


SSD_SHAPES = [
    # (B, S, H, P, G, N, chunk)
    (1, 96, 2, 16, 1, 16, 32),
    (2, 130, 4, 32, 2, 16, 64),
    (1, 64, 4, 64, 1, 64, 32),
]


@pytest.mark.parametrize("impl", ["jnp", "pallas_interpret"])
@pytest.mark.parametrize("shape", SSD_SHAPES)
@pytest.mark.parametrize("with_h0", [False, True])
def test_ssd_scan(impl, shape, with_h0):
    b, s, h, p, g, n, chunk = shape
    x = _mk((b, s, h, p), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, size=(b, s, h)), jnp.float32)
    A = -jnp.asarray(RNG.uniform(0.5, 2.0, size=(h,)), jnp.float32)
    B = _mk((b, s, g, n), jnp.float32)
    C = _mk((b, s, g, n), jnp.float32)
    D = _mk((h,), jnp.float32)
    h0 = _mk((b, h, p, n), jnp.float32) if with_h0 else None
    want_y, want_h = ref.ssd_reference(x, dt, A, B, C, D, initial_state=h0)
    got_y, got_h = ops.ssd_scan(x, dt, A, B, C, D, chunk=chunk, impl=impl,
                                initial_state=h0)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y), atol=3e-4, rtol=3e-4)
    np.testing.assert_allclose(np.asarray(got_h), np.asarray(want_h), atol=3e-4, rtol=3e-4)


def test_ssm_decode_matches_scan():
    """Recurrent decode steps must agree with the chunked scan."""
    b, s, h, p, g, n = 2, 17, 2, 8, 1, 8
    x = _mk((b, s, h, p), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, size=(b, s, h)), jnp.float32)
    A = -jnp.asarray(RNG.uniform(0.5, 2.0, size=(h,)), jnp.float32)
    B = _mk((b, s, g, n), jnp.float32)
    C = _mk((b, s, g, n), jnp.float32)
    D = _mk((h,), jnp.float32)
    want_y, want_h = ref.ssd_reference(x, dt, A, B, C, D)
    state = jnp.zeros((b, h, p, n), jnp.float32)
    ys = []
    for t in range(s):
        y, state = ops.ssm_decode_step(x[:, t], dt[:, t], A, B[:, t], C[:, t], D, state)
        ys.append(y)
    got_y = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(state), np.asarray(want_h), atol=2e-4, rtol=2e-4)


def test_hypothesis_streaming_softmax_invariance():
    """Property: flash attention must be invariant to KV block size."""
    pytest.importorskip("hypothesis",
                        reason="hypothesis not installed (requirements-dev.txt)")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=10, deadline=None)
    @given(
        s=st.integers(16, 96),
        bk=st.sampled_from([16, 32, 64]),
        causal=st.booleans(),
    )
    def prop(s, bk, causal):
        q = _mk((1, s, 2, 16), jnp.float32)
        k = _mk((1, s, 2, 16), jnp.float32)
        v = _mk((1, s, 2, 16), jnp.float32)
        want = ref.mha_reference(q, k, v, causal=causal)
        got = ops.flash_attention(q, k, v, causal=causal, impl="pallas_interpret",
                                  block_q=bk, block_k=bk)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)

    prop()


@pytest.mark.parametrize("impl", ["jnp", "pallas_interpret"])
def test_decode_attention_partials_combine(impl):
    """Split-KV partials from two half-caches must combine to the oracle —
    the distributed flash-decode identity used by attn_decode_sharded."""
    b, h, kv, d, s = 2, 8, 2, 64, 300
    q = _mk((b, h, d), jnp.float32)
    kc, vc = _mk((b, s, kv, d), jnp.float32), _mk((b, s, kv, d), jnp.float32)
    lens = jnp.asarray([120, 300], jnp.int32)
    want = ref.decode_attention_reference(q, kc, vc, lens)
    halves = []
    for lo, hi in ((0, 150), (150, 300)):
        eff = jnp.clip(lens - lo, 0, hi - lo)
        halves.append(ops.decode_attention_partials(
            q, kc[:, lo:hi], vc[:, lo:hi], eff, impl=impl, block_k=64))
    m_g = jnp.maximum(halves[0][1], halves[1][1])
    l_g = sum(jnp.exp(m - m_g) * l for a, m, l in halves)
    acc_g = sum(jnp.exp(m - m_g)[..., None] * a for a, m, l in halves)
    out = (acc_g / jnp.maximum(l_g[..., None], 1e-30)).reshape(b, h, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)
