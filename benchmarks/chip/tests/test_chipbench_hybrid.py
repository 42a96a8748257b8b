"""A third architecture, whose layers differ in kind, added as files only:
``toy_hybrid`` (the program's ``hybrid`` family: 5 Mamba-2 layers, one
shared attention block after every 2nd, applied twice, then a tail layer)
comes with an ``archs/`` file, a ``references/`` file and a configuration,
and the harness serves it to ``correct`` on the CPU without a change to any
file the benchmark has.  A Mamba-2 state that decode does not carry from one
step to the next fails it (the SSM state fed as zeros to
``ops.ssm_decode_step``; the convolution's state dropped), and so does the
control (``test_chipbench_control.py``).

The toy is served in bfloat16 (``testing.TINY_HYBRID_CONFIG`` gives the
readings its limit was set from): in float32 the control, the reference's
products in bfloat16, read a widest gap of 0 on 3 of 14 seeds, and no limit
fails a control that reads 0.  The program keeps its convolution states in
bfloat16 whatever the model's dtype (``models/ssm.py``, ``init_ssm_state``),
which rounds as the rest of a bfloat16 model does."""
import io
import json
import os
import sys

import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))), "src"))

from chipbench import harness, testing  # noqa: E402

CELL = "tiny_hybrid.tiny_mix"
SEED = 2**31 + 13


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return testing.tiny_bench(str(tmp_path_factory.mktemp("chipbench_hybrid")))


def _run(bench, seed=SEED):
    out = io.StringIO()
    harness.run_cell(CELL, seed, 2.0, False, bench_root=os.path.dirname(bench),
                     bench_json=bench, require_tpu=False, out=out, err=io.StringIO())
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_hybrid_is_served_correct(bench):
    last = _run(bench)
    assert last["correct"] is True and last["attempted"] > 0 and last["failed"] == 0
    assert last["compared"]["logit_gap"]["value"] <= last["compared"]["logit_gap"]["limit"]


def test_ssm_state_not_carried_at_decode_is_not_correct(bench, monkeypatch):
    from repro.kernels import ops
    inner = ops.ssm_decode_step

    def ssm_decode_step(x, dt, A, B, C, D, state):
        return inner(x, dt, A, B, C, D, jnp.zeros_like(state))
    monkeypatch.setattr(ops, "ssm_decode_step", ssm_decode_step)
    last = _run(bench)
    assert last["correct"] is False
    assert last["compared"]["logit_gap"]["value"] > last["compared"]["logit_gap"]["limit"]


def test_conv_state_dropped_at_decode_is_not_correct(bench, monkeypatch):
    from repro.models import ssm
    inner = ssm._conv_step

    def conv_step(x_t, w, b, state):
        return inner(x_t, w, b, jnp.zeros_like(state))
    monkeypatch.setattr(ssm, "_conv_step", conv_step)
    last = _run(bench)
    assert last["correct"] is False
    assert last["compared"]["logit_gap"]["value"] > last["compared"]["logit_gap"]["limit"]
