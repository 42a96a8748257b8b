"""The program's own spans and counters: the readers' arithmetic on events
fed through ``jax.monitoring`` to the collector, a tiny traced run on the CPU
that reports them beside the client's twins, and the spans on the device
trace's clock in a traced run of ``granite-8b.react_decode`` on a TPU v5e,
cut by ``data/cut_trace.py`` to one prefill and the three decode steps after
it (``data/trace_program_spans.xplane.pb``)."""
import importlib.util
import io
import json
import os
import sys
from types import SimpleNamespace

import jax
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(BENCH)), "src"))

from chipbench import harness, program_spans, readers, testing, xplane  # noqa: E402

TRACE = os.path.join(BENCH, "data", "trace_program_spans.xplane.pb")
NEW = ("engine_step_ms", "engine_host_ms_per_step", "engine_prefill_ms_per_ktok",
       "spec_wasted_row_share")


def _metric(name):
    spec = importlib.util.spec_from_file_location(
        "metric_p_" + name, os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _span(name, t0, t1, **attrs):
    jax.monitoring.record_event_time_span("/repro/" + name, t0, t1, **attrs)


@pytest.fixture
def clock(monkeypatch):
    """The collector's arrival clock, set by hand."""
    now = [0.0]
    monkeypatch.setattr(program_spans, "time", SimpleNamespace(perf_counter=lambda: now[0]))
    program_spans.COLLECTOR.reset()
    yield now
    program_spans.COLLECTOR.reset()


@pytest.fixture
def rec(clock):
    """A window [0, 10) s: a warm-up before it, two decode steps and two
    prefills in it, a step after it."""
    clock[0] = -1.0
    _span("engine.decode", -1.0, -0.9, active=8, speculative=8)
    _span("engine.decode.wait", -0.99, -0.91)
    _span("engine.prefill", -2.0, -1.5, tokens=512, speculative=1, slot=0, request_id=-1)
    jax.monitoring.record_scalar("/repro/engine.slot_release", 9, outcome="preempt")
    # in the window
    _span("engine.decode", 1.0, 1.04, active=4, speculative=1)
    _span("engine.decode.prep", 1.0, 1.001)
    _span("engine.decode.wait", 1.001, 1.035)
    _span("engine.decode.emit", 1.035, 1.04)
    _span("engine.decode", 2.0, 2.05, active=6, speculative=2)
    _span("engine.decode.wait", 2.002, 2.044)
    _span("engine.prefill", 3.0, 3.1, tokens=1000, speculative=0, slot=1, request_id=4)
    _span("engine.prefill.wait", 3.05, 3.1)
    _span("engine.prefill", 4.0, 4.1, tokens=1000, speculative=1, slot=2, request_id=-5)
    for t, rows, outcome in ((2.5, 2, "preempt"), (3.5, 1, "done"), (4.5, 5, "promote")):
        clock[0] = t
        jax.monitoring.record_scalar("/repro/engine.slot_release", rows, outcome=outcome)
    # not the program's
    jax.monitoring.record_scalar("/other/engine.slot_release", 50, outcome="preempt")
    # after the window
    clock[0] = 10.5
    _span("engine.decode", 10.5, 10.6, active=8, speculative=8)
    jax.monitoring.record_scalar("/repro/engine.slot_release", 8, outcome="done")
    return {"t0": 0.0, "t_end": 10.0}


def test_engine_step_ms_is_the_mean_decode_span(rec):
    assert _metric("engine_step_ms")(rec) == pytest.approx(45.0)


def test_engine_host_time_leaves_out_the_wait_inside_each_step(rec):
    # (40 - 34 + 50 - 42) / 2 ms; the warm-up step's wait is not inside either
    assert _metric("engine_host_ms_per_step")(rec) == pytest.approx(7.0)


def test_engine_prefill_time_per_thousand_tokens(rec):
    assert _metric("engine_prefill_ms_per_ktok")(rec) == pytest.approx(100.0)


def test_wasted_rows_are_preempted_and_done_over_active(rec):
    # (2 + 1) of 4 + 6 active rows; the promoted 5 are not wasted
    assert _metric("spec_wasted_row_share")(rec) == pytest.approx(30.0)


@pytest.mark.parametrize("name", NEW)
def test_a_window_with_no_events_reads_none(rec, name):
    assert _metric(name)({"t0": 20.0, "t_end": 30.0}) is None


def test_prefix_is_cut_and_others_are_dropped(rec):
    c = program_spans.COLLECTOR
    assert all(not n.startswith("/") for n, *_ in c.spans + c.counts)
    assert sum(v for _, _, v, _ in c.counts) == 9 + 2 + 1 + 5 + 8


def test_traced_run_reports_the_program_twins(tmp_path):
    """The engine publishes, the harness loads the readers before the
    warm-up, and each twin keeps inside the client's timing of the same calls."""
    path = testing.tiny_bench(str(tmp_path))
    with open(path) as f:
        bench = json.load(f)
    cell = "tiny.tiny_mix"
    bench["per_layer"] += [{"name": n, "unit": "ms", "workloads": [cell]}
                           for n in NEW + ("prefill_ms_per_ktok", "spec_row_share")]
    with open(path, "w") as f:
        json.dump(bench, f)
    out = io.StringIO()
    harness.run_cell(cell, 2**31 + 11, 2.0, True, bench_root=os.path.dirname(path),
                     bench_json=path, require_tpu=False, out=out, err=io.StringIO())
    m = {k: v["value"] for k, v in json.loads(out.getvalue().splitlines()[-1])["metrics"].items()}
    assert set(NEW) <= set(m)
    assert 0 < m["engine_host_ms_per_step"] < m["engine_step_ms"] <= m["decode_step_ms"]
    assert 0 < m["engine_prefill_ms_per_ktok"] <= m["prefill_ms_per_ktok"]
    assert 0 <= m["spec_wasted_row_share"] <= 100


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData
    return ProfileData.from_file(TRACE)


def _host(profile, name):
    return [(a, b) for a, b, n in xplane._host_spans(profile) if n == name]


def _module_runs(profile, prefix):
    dev = next(p for p in profile.planes if p.name == "/device:TPU:0")
    line = next(ln for ln in dev.lines if ln.name == "XLA Modules")
    return [(e.start_ns, e.end_ns) for e in line.events if e.name.startswith(prefix)]


def test_each_wait_holds_the_end_of_one_decode_program(profile):
    waits = _host(profile, "engine.decode.wait")
    runs = _module_runs(profile, "jit__decode")
    assert len(waits) == len(runs) == 3
    for a, b in waits:
        assert sum(a <= end <= b for _, end in runs) == 1


def test_program_spans_nest_in_the_client_spans(profile):
    for child, parent in (("engine.decode", "engine.step"),
                          ("engine.prefill", "engine.add_request")):
        outer, inner = _host(profile, parent), _host(profile, child)
        assert inner
        assert all(any(p0 <= c0 <= c1 <= p1 for p0, p1 in outer) for c0, c1 in inner)


def test_idle_time_is_named_by_program_spans():
    red = xplane.reduce(TRACE)
    idle = red["window_s"] - red["busy_s"]
    assert sum(red["gaps"].values()) == pytest.approx(idle, rel=1e-6)
    assert red["gaps"]["engine.decode.wait"] > 0
    program = sum(t for n, t in red["gaps"].items()
                  if n.startswith(("engine.decode", "engine.prefill")))
    assert program > 0.9 * idle


@pytest.mark.parametrize("program,kernel", [("jit__decode", "decode_attention"),
                                            ("jit__prefill_one", "flash_attention")])
def test_kernel_by_name_is_the_programs_one_kernel(program, kernel):
    """Each program holds one Pallas kernel, so selecting it by name reads
    every Pallas operation of its program."""
    rec = {"trace": xplane.reduce(TRACE)}
    whole = sum(t for m, _, t, _, k in rec["trace"]["ops"] if m.startswith(program) and k)
    assert whole > 0
    assert readers.kernel_time(rec, program, kernel) == whole
