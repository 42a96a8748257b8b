"""The control at a size a test run holds: the reference computed with
float8 matrix products fails the configuration's limit on the same prompts
and tokens that the served bfloat16 program passes."""
import io
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))), "src"))

from chipbench import harness, testing  # noqa: E402


@pytest.mark.parametrize("seed", [3, 2**31 + 1])
def test_control_fails_where_the_program_passes(tmp_path, seed):
    bench = testing.tiny_bench(str(tmp_path))
    c = harness.Cell("tiny.tiny_mix", os.path.dirname(bench), bench)
    rec = harness.measure(c, seed, 2.0, False, require_tpu=False, err=io.StringIO())
    gap, picked, ctrl = harness.compare(c, rec, control=True, err=io.StringIO())
    assert picked
    assert gap <= c.limit < ctrl
    assert harness.is_correct(rec, picked, gap, c.limit)
    assert not harness.is_correct(rec, picked, ctrl, c.limit)
