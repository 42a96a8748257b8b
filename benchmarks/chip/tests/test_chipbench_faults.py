"""The check that decides ``correct`` fails a broken timed path: a run on the
CPU (the look for a chip skipped) with a fault planted under the engine."""
import io
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))), "src"))

from chipbench import harness, testing  # noqa: E402


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return testing.tiny_bench(str(tmp_path_factory.mktemp("chipbench_faults")))


def _run(bench):
    out = io.StringIO()
    harness.run_cell("tiny.tiny_mix", 11, 2.0, False, bench_root=os.path.dirname(bench),
                     bench_json=bench, require_tpu=False, out=out, err=io.StringIO())
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_sound_run_is_correct(bench):
    assert _run(bench)["correct"] is True


def test_token_altered_where_produced(bench, monkeypatch):
    from repro.serving.engine import ServingEngine
    inner = ServingEngine.step

    def step(self):
        out = inner(self)
        if self.steps_executed % 3 == 0:
            for slot in out:
                if not self.slots[slot].speculative:
                    tok = (out[slot] + 1) % self.cfg.vocab_size
                    out[slot] = self.pending_tokens[slot] = tok
        return out
    monkeypatch.setattr(ServingEngine, "step", step)
    last = _run(bench)
    assert last["correct"] is False
    assert last["compared"]["logit_gap"]["value"] > last["compared"]["logit_gap"]["limit"]


def test_decode_step_that_leaves_the_cache_unchanged(bench, monkeypatch):
    from repro.models import model
    inner = model.decode_step

    def decode_step(params, cfg, cache, tokens, mesh_info=None):
        logits, _ = inner(params, cfg, cache, tokens, mesh_info)
        return logits, cache
    monkeypatch.setattr(model, "decode_step", decode_step)
    last = _run(bench)
    assert last["correct"] is False
