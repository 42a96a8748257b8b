"""The trace reduction on a small trace recorded on a TPU v5e: a traced run
of a 2-layer model at granite-8b's widths, cut to one prefill and the three
decode steps after it, with the benchmark's host spans
(``data/trace_small.xplane.pb``, 42 ms)."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from chipbench import readers, xplane  # noqa: E402

TRACE = os.path.join(BENCH, "data", "trace_small.xplane.pb")


@pytest.fixture(scope="module")
def red():
    return xplane.reduce(TRACE)


def test_programs_and_busy_time(red):
    assert red["modules"]["jit__decode"][1] == 3
    assert red["modules"]["jit__prefill_one"][1] == 1
    assert 0 < red["busy_s"] <= red["window_s"]
    decode = red["modules"]["jit__decode"][0] + red["modules"]["jit__prefill_one"][0]
    assert decode <= red["busy_s"] * 1.0001


def test_each_kernel_is_found_in_its_own_program(red):
    kernels = {(m, o) for m, o, _, _, k in red["ops"] if k}
    assert {m for m, _ in kernels} == {"jit__decode", "jit__prefill_one"}
    names = {o.split(".")[0] for _, o in kernels}
    assert names == {"decode_attention", "flash_attention"}
    rec = {"trace": red}
    # a layer's kernel runs once per layer and program run
    assert readers.kernel_time(rec, "jit__decode", "decode_attention") > 0
    assert readers.kernel_time(rec, "jit__prefill_one", "flash_attention") > 0
    assert readers.kernel_time(rec, "jit__decode", "flash_attention") is None
    for m, o, _, count, k in red["ops"]:
        if k:
            assert count == 2 * red["modules"][m][1]


def test_no_operation_is_counted_twice(red):
    per_module = {}
    for m, _, t, _, _ in red["ops"]:
        per_module[m] = per_module.get(m, 0.0) + t
    for m, (t, _) in red["modules"].items():
        assert per_module.get(m, 0.0) <= t * 1.0001


def test_idle_time_is_named_by_host_span(red):
    assert set(red["gaps"]) <= {"engine.step", "engine.add_request", "client.idle", "(none)"}
    idle = red["window_s"] - red["busy_s"]
    assert sum(red["gaps"].values()) == pytest.approx(idle, rel=1e-6)
    assert red["gaps"]["engine.step"] > 0


def test_breakdown_shape(red):
    b = xplane.breakdown(red)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all(isinstance(n, str) and t > 0 for n, t in b["device_ops"])
