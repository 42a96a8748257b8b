"""A tiny run of the harness on the CPU: the client drives the engine with
the Pallas kernels in interpret mode, through a configuration, a traffic mix
and a per-layer metric that exist as new files only."""
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
    return testing.tiny_bench(str(tmp_path_factory.mktemp("chipbench")))


def _run(bench, trace):
    out, err = io.StringIO(), io.StringIO()
    line = harness.run_cell("tiny.tiny_mix", 2**31 + 7, 2.0, trace,
                            bench_root=os.path.dirname(bench), bench_json=bench,
                            require_tpu=False, out=out, err=err)
    last = json.loads(out.getvalue().strip().splitlines()[-1])
    assert last == json.loads(json.dumps(line))
    return last, err.getvalue()


def test_run_prints_the_result_line(bench):
    last, err = _run(bench, trace=False)
    assert list(last) == ["correct", "attempted", "failed", "metrics", "device", "compared"]
    assert last["correct"] is True and last["attempted"] > 0 and last["failed"] == 0
    assert set(last["metrics"]) == {"turn_p90_s", "ttft_p90_s", "itl_p95_ms",
                                    "tokens_per_s", "setup_s"}
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(last["device"])
    assert last["device"]["platform"] == "cpu"
    assert err.strip().splitlines()[-1].startswith("compared: logit_gap ")
    assert "compilations inside the window: 0" in err


def test_traced_run_reads_the_new_metric(bench):
    last, _ = _run(bench, trace=True)
    assert last["correct"] is True
    assert last["metrics"]["turns_finished"]["value"] >= last["attempted"]
    assert "decode_step_ms" in last["metrics"]


def test_no_tpu_is_refused(bench):
    with pytest.raises(harness.NoChip):
        harness.run_cell("tiny.tiny_mix", 1, 1.0, False, bench_root=os.path.dirname(bench),
                         bench_json=bench, require_tpu=True, out=io.StringIO(),
                         err=io.StringIO())
