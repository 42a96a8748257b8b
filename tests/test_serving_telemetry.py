"""The serving engine's own spans and counters (``serving/telemetry.py``), as a
``jax.monitoring`` listener receives them, and the device-side names of the
decode program."""
import dataclasses
import time

import jax
import jax._src.monitoring as monitoring
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models import model as M
from repro.serving.engine import ServingEngine, make_decode

MAX_LEN = 32
PARENT = {"engine.prefill.write_slot": "engine.prefill", "engine.prefill.wait": "engine.prefill",
          "engine.decode.prep": "engine.decode", "engine.decode.wait": "engine.decode",
          "engine.decode.emit": "engine.decode"}


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("granite-8b").reduced()
    params = M.init_params(jax.random.key(0), cfg)
    # no end-of-sequence token: slots end only at max_len
    return ServingEngine(cfg, params, max_batch=4, max_len=MAX_LEN, eos_id=-1)


@pytest.fixture
def heard():
    got = {"spans": [], "counts": []}

    def on_span(event, t0, t1, **attrs):
        if event.startswith("/repro/"):
            got["spans"].append((event, t0, t1, attrs))

    def on_scalar(event, value, **attrs):
        if event.startswith("/repro/"):
            got["counts"].append((event, value, attrs))
    jax.monitoring.register_event_time_span_listener(on_span)
    jax.monitoring.register_scalar_listener(on_scalar)
    yield got
    jax.monitoring.unregister_event_time_span_listener(on_span)
    jax.monitoring.unregister_scalar_listener(on_scalar)


def _free(engine):
    for s in engine.slots:
        s.active, s.done, s.speculative, s.request_id = False, False, False, None


def _spans(got, name):
    return [s for s in got["spans"] if s[0] == "/repro/" + name]


def test_every_span_and_count_with_its_attributes(engine, heard):
    _free(engine)
    t_before = time.perf_counter()
    a = engine.add_request([5] * 8, request_id=11)
    b = engine.add_request([6] * 8, request_id=-3, speculative=True)
    c = engine.add_request([7] * 8, request_id=-4, speculative=True)
    engine.step()
    engine.step()
    engine.preempt(b)
    engine.promote(c, 12)
    engine.step()
    # a speculative slot that decodes into max_len ends itself
    d = engine.add_request([8] * (MAX_LEN - 3), request_id=-5, speculative=True)
    assert d == b
    engine.step()
    out = engine.step()
    assert not engine.slots[d].active and engine.slots[d].done and d in out
    t_after = time.perf_counter()

    prefills = _spans(heard, "engine.prefill")
    assert [s[3] for s in prefills] == [
        {"tokens": 8, "speculative": 0, "slot": a, "request_id": 11},
        {"tokens": 8, "speculative": 1, "slot": b, "request_id": -3},
        {"tokens": 8, "speculative": 1, "slot": c, "request_id": -4},
        {"tokens": MAX_LEN - 3, "speculative": 1, "slot": d, "request_id": -5}]
    decodes = _spans(heard, "engine.decode")
    assert [(s[3]["active"], s[3]["speculative"]) for s in decodes] == [
        (3, 2), (3, 2), (2, 0), (3, 1), (3, 1)]
    for child in PARENT:
        assert len(_spans(heard, child)) == len(_spans(heard, PARENT[child])), child
    assert heard["counts"] == [
        ("/repro/engine.slot_release", 2, {"outcome": "preempt"}),
        ("/repro/engine.slot_release", 2, {"outcome": "promote"}),
        ("/repro/engine.slot_release", 2, {"outcome": "done"})]
    # on the host clock the client reads: inside the calls
    assert all(t_before <= t0 <= t1 <= t_after for _, t0, t1, _ in heard["spans"])


def test_children_lie_inside_their_parent(engine, heard):
    _free(engine)
    engine.add_request([5] * 8, request_id=1)
    engine.add_request([6] * 8, request_id=-1, speculative=True)
    for _ in range(3):
        engine.step()
    for child, parent in PARENT.items():
        parents = [(t0, t1) for _, t0, t1, _ in _spans(heard, parent)]
        for _, c0, c1, _ in _spans(heard, child):
            assert any(p0 <= c0 <= c1 <= p1 for p0, p1 in parents), child
    # the parts of a step follow one another
    for name in ("engine.decode", "engine.prefill"):
        kids = sorted(s[1:3] for s in heard["spans"] if s[0].startswith(f"/repro/{name}."))
        assert all(x[1] <= y[0] for x, y in zip(kids, kids[1:], strict=False))


def test_engine_runs_with_no_listener(engine, monkeypatch):
    monkeypatch.setattr(monitoring, "_event_time_span_listeners", [])
    monkeypatch.setattr(monitoring, "_scalar_listeners", [])
    _free(engine)
    slot = engine.add_request([5] * 8, request_id=1)
    spec = engine.add_request([6] * 8, request_id=-1, speculative=True)
    assert set(engine.step()) == {slot, spec}
    engine.preempt(spec)
    assert set(engine.step()) == {slot}


def test_decode_program_names_its_mask_and_cache_write_back(engine):
    """The decode program names its mask and its parts, and writes the cache
    back in place: every cache argument is donated to an output, and, lowered
    for the TPU (where the decode kernel reads the stacked cache itself), no
    op outputs one layer's K/V and no select runs over the cache."""
    b = engine.max_batch
    args = (engine.params, engine.cache, jax.ShapeDtypeStruct((b,), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.bool_))
    text = engine._decode.lower(*args).as_text(debug_info=True)
    assert '"jit(_decode)/engine.active_mask/' in text
    assert '"attention/' in text and '"mlp/' in text
    main = next(ln for ln in text.splitlines() if "func.func public @main" in ln)
    assert main.count("tf.aliasing_output") == len(jax.tree.leaves(engine.cache))

    tpu = make_decode(dataclasses.replace(engine.cfg, attn_impl="pallas")).trace(
        *args).lower(lowering_platforms=("tpu",)).as_text()
    k = engine.cache["k"]
    stack = "tensor<" + "x".join(map(str, k.shape)) + "x"
    assert stack in tpu
    assert "tensor<" + "x".join(map(str, k.shape[1:])) + "x" not in tpu
    assert not [ln for ln in tpu.splitlines() if "stablehlo.select" in ln and stack in ln]
