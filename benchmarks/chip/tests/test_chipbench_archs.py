"""A second architecture added as files only: ``toy_moe`` (the program's
``moe`` family, 4 experts, top-2, a capacity that drops nothing) comes with
an ``archs/`` file, a ``references/`` file and a configuration, and the
harness serves it to ``correct`` on the CPU without a change to any file the
benchmark has.  Leaving one expert's output out of the served layers fails
it.

The toy is served in float32.  In bfloat16 its top-2 routing flips at
near-ties between the served path and the float32 reference, and those
flips read widest gaps up to 0.22 on this toy, above the fp8 control's least
reading (0.084), so no limit would separate the two there."""
import filecmp
import io
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(BENCH)), "src"))

from chipbench import harness, testing  # noqa: E402

CELL = "tiny_moe.tiny_mix"


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return testing.tiny_bench(str(tmp_path_factory.mktemp("chipbench_archs")))


def _run(bench, seed):
    out = io.StringIO()
    harness.run_cell(CELL, seed, 2.0, False, bench_root=os.path.dirname(bench),
                     bench_json=bench, require_tpu=False, out=out, err=io.StringIO())
    return json.loads(out.getvalue().strip().splitlines()[-1])


def _bench_files(root, skip=("tests", "__pycache__")):
    return {os.path.relpath(os.path.join(d, f), root)
            for d, dirs, fs in os.walk(root) if not set(skip) & set(d.split(os.sep))
            for f in fs}


def test_files_the_benchmark_has_are_unchanged(bench):
    """Every file the copy came with is the benchmark's, byte for byte, but
    ``peaks.json``, to which the copy only adds a CPU row."""
    copy = os.path.dirname(bench)
    ours = _bench_files(BENCH)
    assert ours <= _bench_files(copy)
    for rel in sorted(ours - {"peaks.json"}):
        assert filecmp.cmp(os.path.join(BENCH, rel), os.path.join(copy, rel), shallow=False), rel
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    with open(os.path.join(copy, "peaks.json")) as f:
        copied = json.load(f)
    assert set(copied) - set(peaks) == {"cpu"}
    assert {k: copied[k] for k in peaks} == peaks
    for rel in ("archs/toy_moe.py", "references/toy_moe.py", "configs/tiny_moe.json",
                "archs/toy_hybrid.py", "references/toy_hybrid.py", "configs/tiny_hybrid.json"):
        assert rel in _bench_files(copy) - ours


def test_second_architecture_is_served_correct(bench):
    last = _run(bench, 2**31 + 13)
    assert last["correct"] is True and last["attempted"] > 0 and last["failed"] == 0
    assert last["compared"]["logit_gap"]["value"] <= last["compared"]["logit_gap"]["limit"]


def test_an_expert_left_out_is_not_correct(bench, monkeypatch):
    from repro.models import moe
    inner = moe._expert_ffn

    def expert_ffn(xd, w_gate, w_up, w_down):
        return inner(xd, w_gate, w_up, w_down).at[0].set(0)
    monkeypatch.setattr(moe, "_expert_ffn", expert_ffn)
    last = _run(bench, 2**31 + 13)
    assert last["correct"] is False
    assert last["compared"]["logit_gap"]["value"] > last["compared"]["logit_gap"]["limit"]
