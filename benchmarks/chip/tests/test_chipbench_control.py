"""The control at a size a test run holds: the reference computed with
float8 matrix products fails the configuration's limit on the same prompts
and tokens that the served bfloat16 program passes, for the llama
architecture and for the hybrid one whose layers differ in kind
(``toy_hybrid``)."""
import io
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))), "src"))

from chipbench import harness, testing  # noqa: E402


@pytest.mark.parametrize("cell,seed", [
    pytest.param("tiny.tiny_mix", 3, id="3"),
    pytest.param("tiny.tiny_mix", 2**31 + 1, id="2147483649"),
    pytest.param("tiny_hybrid.tiny_mix", 3, id="tiny_hybrid-3"),
    pytest.param("tiny_hybrid.tiny_mix", 2**31 + 1, id="tiny_hybrid-2147483649")])
def test_control_fails_where_the_program_passes(tmp_path, cell, seed):
    bench = testing.tiny_bench(str(tmp_path))
    c = harness.Cell(cell, os.path.dirname(bench), bench)
    rec = harness.measure(c, seed, 2.0, False, require_tpu=False, err=io.StringIO())
    gap, picked, ctrl = harness.compare(c, rec, control=True, err=io.StringIO())
    assert picked
    assert gap <= c.limit < ctrl
    assert harness.is_correct(rec, picked, gap, c.limit)
    assert not harness.is_correct(rec, picked, ctrl, c.limit)
