"""One run of one cell: set up, warm up, measure, check, report.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file found by its name under the benchmark's directory:
``configs/<config>.json`` (naming its architecture,
``archs/<architecture>.py``, and its reference,
``references/<reference>.py``), ``traffic/<traffic>.json`` (naming its
generator, ``generators/<generator>.py``) and ``metrics/<metric>.py`` (a
``read(rec)`` that returns a number, or None where it finds nothing to read).

An architecture gives ``model_config(cfg)``, the program's ``ModelConfig``,
and ``served_params(cfg, mcfg, seed, ref)``, the whole parameter tree made on
the device by ``weights.served_tree``, which hands it each kind's layers
stacked.  A reference imports nothing of the program and gives ``dims(cfg)``
(the record's ``dims``); ``layer_kinds(cfg)``, the kind of each layer it
runs in order, where its layers differ (else ``dims["L"]`` layers of one
kind, ``weights.ONE_KIND``); per kind ``layer_shapes(cfg, kind)``,
``layer_weights(key, li, cfg, kind, dtype)`` (the seeded draw both sides use,
with the draw rules of any leaf that is neither a norm scale nor a
projection) and ``layer(h, w, cfg, kind, lower)`` (one layer of the float32
pass that ``chipbench/reference.Layerwise`` runs, built from the shared
``attention`` and ``mamba2``); ``embedding_multiplier(cfg)`` and
``logits_multiplier(cfg)`` where the model scales them (else 1); and
``decode_work(dims, rows, sum_ctx)`` and ``prefill_work(dims, n)``: the
model's operations and, per Pallas kernel by name, its operations and bytes;
the record carries the module as ``ref``.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

from chipbench import results, spans as spans_mod

TRACE_S = 5.0           # length of the traced stretch of a --trace 1 window
TRACE_AT = 0.4          # where it starts, as a share of the window
PROFILER_SPAN = "bench.profiler"    # starting and stopping the profiler


class NoChip(RuntimeError):
    pass


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def find_cell(bench: Dict, workload: str) -> Dict:
    for c in bench["workloads"]:
        if c["name"] == workload:
            return c
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                   f"known: {[c['name'] for c in bench['workloads']]}")


def per_layer_for(bench: Dict, workload: str) -> List[Dict]:
    return [m for m in bench["per_layer"] if workload in m.get("workloads", [workload])]


def check_devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devs[0].platform} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    return devs


def warm_up(engine, lengths: List[int], draw) -> None:
    """Run every program the window will: a prefill of each prompt length,
    the slot write into every slot, a decode step, a preempt and a promote."""
    import jax
    n = engine.max_batch
    for i in range(max(n, len(lengths))):
        if not engine.free_slots():
            _release(engine)
        engine.add_request(draw(lengths[i % len(lengths)]), request_id=-(i + 1),
                           speculative=i % 2 == 1)
    engine.step()
    engine.promote(engine.slots[1].idx, 10**6)
    engine.slots[3].speculative = True
    engine.preempt(3)
    engine.step()
    jax.block_until_ready(engine.cache)
    _release(engine)


def _release(engine) -> None:
    for s in engine.slots:
        s.active, s.request_id, s.speculative, s.done = False, None, False, False


class Cell:
    """One workload's files, found by name."""

    def __init__(self, workload: str, bench_root: str, bench_json: str):
        self.bench = load_json(bench_json)
        self.cell = find_cell(self.bench, workload)
        self.name = workload
        self.cfg = load_json(os.path.join(bench_root, "configs", self.cell["config"] + ".json"))
        aname, rname = self.cfg["architecture"], self.cfg["reference"]
        self.arch = load_module(os.path.join(bench_root, "archs", aname + ".py"),
                                "arch_" + aname.replace(".", "_"))
        self.ref = load_module(os.path.join(bench_root, "references", rname + ".py"),
                               "ref_" + rname.replace(".", "_"))
        self.traffic = load_json(os.path.join(bench_root, "traffic",
                                              self.cell["traffic"] + ".json"))
        gname = self.traffic["generator"]
        self.gen = load_module(os.path.join(bench_root, "generators", gname + ".py"),
                               "gen_" + gname)
        self.peaks = load_json(os.path.join(bench_root, "peaks.json"))
        self.metric_specs = per_layer_for(self.bench, workload)
        self.readers = {
            m["name"]: load_module(os.path.join(bench_root, "metrics", m["name"] + ".py"),
                                   "metric_" + m["name"].replace(".", "_"))
            for m in self.metric_specs}
        self.limit = float(self.cfg["check"]["logit_gap_limit"])


def measure(c: Cell, seed: int, seconds: float, trace: bool, *, require_tpu: bool = True,
            t_start: Optional[float] = None, err=sys.stderr) -> Dict:
    """Set up, warm up, serve the window, read the peak memory, free the
    program's state.  Returns the window's record."""
    t_start = time.perf_counter() if t_start is None else t_start
    import jax

    from chipbench import client as client_mod, system, xplane
    devs = check_devices(c.cell["chips"], require_tpu)
    dev = devs[0]
    if dev.device_kind not in c.peaks:
        raise KeyError(f"device kind {dev.device_kind!r} is not in peaks.json")
    gen, traffic = c.gen, c.traffic
    roster = gen.load_roster(traffic)
    t_phase = time.perf_counter()
    engine, spec, builder = system.build(c.cfg, seed, roster, c.arch, c.ref)
    jax.block_until_ready(engine.params)
    print(f"set-up: JAX and the device {t_phase - t_start:.3f} s, weights "
          f"{time.perf_counter() - t_phase:.3f} s", file=err)
    vocab = engine.cfg.vocab_size
    t_phase = time.perf_counter()
    warm_up(engine, gen.prompt_lengths(traffic), gen.token_stream(seed, 1 << 20, vocab))
    print(f"set-up: warm-up {time.perf_counter() - t_phase:.3f} s", file=err)
    spec.admitted = spec.promotions = spec.preemptions = 0
    spec.active.clear()
    scripts = gen.make_agents(traffic, seed, roster)
    draws = {s["agent"]: gen.token_stream(seed, s["agent"], vocab) for s in scripts}
    client = client_mod.AgentClient(engine, spec, builder, gen, traffic, scripts, roster,
                                    draws, spans_mod.Spans(annotate=trace))
    counter = spans_mod.CompileCounter()

    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") if trace else None
    tr = {"state": "off", "t0": None, "t1": None, "ann": None}

    def on_tick(now):
        if not trace:
            return
        if tr["state"] == "off" and now >= tr["at"]:
            with client.spans.span(PROFILER_SPAN):
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0        # the spans are annotations
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
            tr["ann"] = jax.profiler.TraceAnnotation(xplane.WINDOW)
            tr["ann"].__enter__()
            tr["t0"], tr["state"] = time.perf_counter(), "on"
        elif tr["state"] == "on" and now >= tr["t0"] + min(TRACE_S, seconds / 4):
            tr["t1"] = time.perf_counter()
            tr["ann"].__exit__(None, None, None)
            with client.spans.span(PROFILER_SPAN):
                jax.profiler.stop_trace()
            tr["state"] = "done"

    setup_s = time.perf_counter() - t_start
    tr["at"] = time.perf_counter() + TRACE_AT * seconds
    counter.armed = True
    rec = client.run(seconds, on_tick=on_tick)
    counter.armed = False
    if tr["state"] == "on":
        on_tick(float("inf"))
    stats = dev.memory_stats() or {}
    rec.update(seed=seed, setup_s=setup_s, dims=c.ref.dims(c.cfg), ref=c.ref,
               peaks=c.peaks[dev.device_kind],
               trace_window=(tr["t0"], tr["t1"]), compilations=counter.count,
               rows=engine.cfg.padded_vocab, max_len=engine.max_len,
               device={"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(devs),
                       "memory_peak_bytes": stats.get("peak_bytes_in_use")})
    # free the program's state before the reference runs
    del client, spec, builder
    engine.params = engine.cache = engine.last_logits = None
    del engine
    gc.collect()
    rec["trace"] = None
    if trace:
        rec["trace"] = _reduce_trace(trace_dir, xplane)
        shutil.rmtree(trace_dir, ignore_errors=True)
    return rec


def compare(c: Cell, rec: Dict, control: bool = False, err=sys.stderr):
    """The served tokens of a sample against the float32 reference: the
    widest gap, and the sample.  With ``control`` also the control's gap:
    the gap of the tokens that the reference, computed in the precision
    below the configuration's, puts first at the same positions."""
    from chipbench.reference import Layerwise
    picked = results.sample(rec, rec["seed"])
    if not picked:
        return float("inf"), picked, None
    seqs, pos = results.sequences(picked)
    t_ref = time.perf_counter()
    ref = Layerwise(c.ref, c.cfg, rec["seed"], rec["rows"], rec["max_len"]).logits(seqs, pos)
    gap = results.widest_gap(ref, [t.tokens for t in picked])
    print(f"reference: {len(picked)} turns, {sum(len(t.tokens) for t in picked)} "
          f"served tokens, {time.perf_counter() - t_ref:.3f} s", file=err)
    ctrl_gap = None
    if control:
        ctrl = Layerwise(c.ref, c.cfg, rec["seed"], rec["rows"], rec["max_len"],
                         control=True).logits(seqs, pos)
        ctrl_gap = results.widest_gap(ref, [x.argmax(axis=-1) for x in ctrl])
    return gap, picked, ctrl_gap


def is_correct(rec: Dict, picked: List, gap: float, limit: float) -> bool:
    """What decides ``correct``: turns were counted and compared, and the
    widest gap keeps to the limit."""
    return bool(picked) and bool(results.counted(rec)) and gap <= limit


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             bench_root: str, bench_json: str, require_tpu: bool = True,
             t_start: Optional[float] = None, out=sys.stdout, err=sys.stderr) -> Dict:
    """One run.  Returns the result line's object (also printed)."""
    from chipbench import xplane
    c = Cell(workload, bench_root, bench_json)
    rec = measure(c, seed, seconds, trace, require_tpu=require_tpu, t_start=t_start, err=err)
    gap, picked, _ = compare(c, rec, err=err)
    counted = results.counted(rec)
    failed = sum(1 for t in rec["turns"] if t.failed and rec["t0"] <= t.due < rec["t_end"])
    correct = is_correct(rec, picked, gap, c.limit)

    if trace:
        metrics = {}
        for m in c.metric_specs:
            v = c.readers[m["name"]].read(rec)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        e2e = results.end_to_end(rec)
        e2e["setup_s"] = rec["setup_s"]
        metrics = {m["name"]: {"value": float(e2e[m["name"]]), "unit": m["unit"]}
                   for m in c.bench["end_to_end"]
                   if workload in m.get("workloads", [workload]) and m["name"] in e2e}

    device = dict(rec["device"])
    if rec["trace"]:
        device.update(busy_s=rec["trace"]["busy_s"], window_s=rec["trace"]["window_s"])
    line = {"correct": correct, "attempted": len(counted), "failed": failed,
            "metrics": metrics, "device": device}
    if rec["trace"]:
        line["breakdown"] = xplane.breakdown(rec["trace"])
    line["compared"] = {"logit_gap": {"value": gap, "limit": c.limit}}

    print(f"window: {len(counted)} turns counted, {failed} failed, "
          f"{rec['unfinished']} unfinished after the drain; {len(rec['steps'])} decode "
          f"steps, {len(rec['prefills'])} prefills "
          f"({sum(1 for p in rec['prefills'] if p[3])} speculative); speculation "
          f"admitted {rec['spec_admitted']} promoted {rec['spec_promoted']} "
          f"preempted {rec['spec_preempted']}", file=err)
    ctx = [st[4] for st in rec["steps"]] or [0]
    print(f"live cache: {sum(ctx) / len(ctx):.0f} tokens on average over the decode "
          f"steps, {max(ctx)} at most, of {c.cfg['engine']['max_batch'] * rec['max_len']} reserved",
          file=err)
    print(f"compilations inside the window: {rec['compilations']}", file=err)
    print(f"compared: logit_gap {gap!r} limit {c.limit!r}", file=err)
    err.flush()
    _dump(rec, workload, seed, trace)
    print(json.dumps(line), file=out)
    out.flush()
    return line


def _dump(rec: Dict, workload: str, seed: int, trace: bool) -> None:
    """With ``CHIPBENCH_DUMP=<dir>``, keep the window's turns, decode steps
    and prefills there, on the host clock from the window's start."""
    out_dir = os.environ.get("CHIPBENCH_DUMP")
    if not out_dir:
        return
    t0 = rec["t0"]
    turns = [{"agent": t.agent, "due": t.due - t0, "first": t.first and t.first - t0,
              "end": t.end and t.end - t0, "prompt": len(t.prompt), "reason": t.reason,
              "promoted": t.promoted, "failed": t.failed} for t in rec["turns"]]
    os.makedirs(out_dir, exist_ok=True)
    stem = os.path.join(out_dir, f"{workload}.{seed}.{int(trace)}")
    i = 0
    while os.path.exists(f"{stem}.{i}.json"):
        i += 1
    with open(f"{stem}.{i}.json", "w") as f:
        json.dump({"seconds": rec["seconds"], "setup_s": rec["setup_s"], "turns": turns,
                   "steps": [(a - t0, b - t0, n, s, x) for a, b, n, s, x in rec["steps"]],
                   "prefills": [(a - t0, b - t0, n, s) for a, b, n, s in rec["prefills"]]}, f)


def _reduce_trace(trace_dir: str, xplane) -> Dict:
    found = [os.path.join(d, f) for d, _, fs in os.walk(trace_dir) for f in fs
             if f.endswith(".xplane.pb")]
    if not found:
        raise RuntimeError("the profiler wrote no .xplane.pb")
    keep = os.environ.get("CHIPBENCH_KEEP_TRACE")
    if keep:
        os.makedirs(keep, exist_ok=True)
        shutil.copy(found[0], os.path.join(keep, os.path.basename(found[0])))
    return xplane.reduce(found[0])

