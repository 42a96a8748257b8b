#!/usr/bin/env python3
"""Smoke run of the served path on one TPU chip.

Runs granite-8b at its published widths (depth cut to 16 of 36 layers so that
the bf16 weights fit one 16 GB v5e), random weights from a seed, through the
normal entry point ``repro.launch.serve``, and checks on the chip that:

  c. the engine serves agent episodes with B-PASTE speculation on;
  d. the compiled prefill and decode programs hold the Pallas kernels;
  e. the kernels match their references in float32, and the served prefill
     plus decode steps match a full-sequence forward pass;
  f. the B-PASTE runtime's jitted scorer and admission kernel run on the
     device, and the admission kernel admits what the reference greedy does.

Every phase runs in this one process (a chip belongs to one process at a
time).  Any failed phase makes the exit code non-zero.  The last line of
standard output is one JSON object, printed only when every phase passed.
The times it prints are smoke numbers from one run, not benchmark results.

  python chip_smoke.py          # from the root of the checkout, on the chip
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

SEED = 0
ARCH, N_LAYERS = "granite-8b", 16
MAX_BATCH, MAX_LEN = 8, 2048
# authoritative prompts; a speculative prompt adds a 16-token observation,
# so the prefill compiles for exactly two lengths: 1008 and 1024
PROMPT_LEN = 1008
REASON_TOKENS = 32
N_EPISODES = 4
LOGIT_DECODE_STEPS = 8

# Kernels in float32 at HIGHEST matmul precision against kernels/ref.py,
# as max|got - want| / max|want|.  The kernels' blocked online softmax sums
# in another order than the reference's one-pass softmax: about 1e-6 at
# these shapes.  One bf16 pass anywhere would show as about 3e-3.  1e-4
# sits between the two.
KERNEL_REL_TOL = 1e-4
# Served logits (bf16 weights and activations; Pallas prefill + decode
# steps) against model.forward with attn_impl="ref" over the whole sequence,
# as max|got - want| / max|want| over every compared position.  The two
# paths round bf16 activations at different points: the reference rounds its
# attention scores and probabilities to bf16, the kernels keep them in f32.
# bf16 has an 8-bit mantissa (rounding error up to 2^-9, about 2e-3, per
# op).  At these widths it measured 9.4e-3 at 2 layers and 1.1e-2 at 4 on
# the CPU, and 1.3e-2 at 16 layers on a v5e; 0.05 leaves four times that.
# A wrong cache row, position or mask gives errors of the order of the
# logits themselves.
LOGITS_REL_TOL = 0.05


def _rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / np.abs(want).max())


def phase_serving():
    """c. Serve agent episodes through repro.launch.serve."""
    from repro.launch import serve

    t0 = time.perf_counter()
    engine, r = serve.run(ARCH, n_layers=N_LAYERS, n_episodes=N_EPISODES,
                          max_batch=MAX_BATCH, max_len=MAX_LEN,
                          prompt_len=PROMPT_LEN, reason_tokens=REASON_TOKENS,
                          speculate=True, seed=SEED)
    total = time.perf_counter() - t0
    cfg = engine.cfg
    print(f"serving: {cfg.name} {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads}/{cfg.n_kv_heads} heads, d_ff {cfg.d_ff}, "
          f"vocab {cfg.vocab_size}; batch {MAX_BATCH}, max_len {MAX_LEN}")
    print(f"serving: requests served: {r.episodes} episodes + "
          f"{r.spec_admitted} speculative prefills "
          f"(promoted {r.promotions}, preempted {r.preemptions}); "
          f"tokens decoded: {r.tokens_decoded} in {r.decode_steps} decode steps")
    print("serving (smoke numbers from one run, not benchmark results): "
          f"compile {r.compile_s:.3f} s; warm decode step "
          f"{r.decode_step_s * 1e3:.3f} ms (median, to block_until_ready); "
          f"serving loop {r.serve_s:.3f} s; whole phase {total:.3f} s; "
          f"peak_bytes_in_use {r.peak_bytes}")
    if r.episodes != N_EPISODES or r.tokens_decoded < N_EPISODES * REASON_TOKENS:
        raise AssertionError(f"served too little: {r}")
    if not r.spec_admitted:
        raise AssertionError("speculation admitted nothing")
    return engine, r


def phase_kernels_in_program(report) -> None:
    """d. The compiled decode and prefill programs hold a Pallas kernel."""
    for name, compiled in sorted(report.compiled.items()):
        n = compiled.as_text().count("tpu_custom_call")
        print(f"kernels in program: {name}: {n} tpu_custom_call")
        if not n:
            raise AssertionError(f"{name} holds no Pallas kernel")


def phase_kernel_numerics() -> None:
    """e. Flash prefill and decode attention at the model's widths, float32,
    against kernels/ref.py."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref
    from repro.launch import serve

    cfg = serve.build_config(ARCH, n_layers=N_LAYERS)
    rng = np.random.default_rng(SEED)
    hd, h, kv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    with jax.default_matmul_precision("highest"):
        q, k, v = normal(1, PROMPT_LEN, h, hd), normal(1, PROMPT_LEN, kv, hd), \
            normal(1, PROMPT_LEN, kv, hd)
        err = _rel_err(ops.flash_attention(q, k, v, causal=True),
                       ref.mha_reference(q, k, v, causal=True))
        print(f"kernel numerics: flash prefill S={PROMPT_LEN} rel err {err:.3e} "
              f"(tol {KERNEL_REL_TOL:.0e})")
        if not err <= KERNEL_REL_TOL:
            raise AssertionError("flash attention differs from its reference")

        q = normal(MAX_BATCH, h, hd)
        kc, vc = normal(MAX_BATCH, MAX_LEN, kv, hd), normal(MAX_BATCH, MAX_LEN, kv, hd)
        lengths = jnp.asarray(rng.integers(1, MAX_LEN + 1, MAX_BATCH), jnp.int32)
        err = _rel_err(ops.decode_attention(q, kc, vc, lengths),
                       ref.decode_attention_reference(q, kc, vc, lengths))
        print(f"kernel numerics: decode B={MAX_BATCH} S_max={MAX_LEN} rel err "
              f"{err:.3e} (tol {KERNEL_REL_TOL:.0e})")
        if not err <= KERNEL_REL_TOL:
            raise AssertionError("decode attention differs from its reference")


def phase_logits(engine) -> None:
    """e. Served prefill + decode steps against a full-sequence forward."""
    import jax
    import jax.numpy as jnp

    from repro.models import model as model_mod

    cfg = engine.cfg
    rng = np.random.default_rng(SEED + 1)
    prompt = rng.integers(2, cfg.vocab_size, PROMPT_LEN).tolist()
    slot = engine.add_request(prompt, request_id=-1)
    got = [engine.last_logits[0]]
    for _ in range(LOGIT_DECODE_STEPS):
        if not engine.slots[slot].active:
            raise AssertionError("the request ended before its decode steps")
        engine.step()
        got.append(engine.last_logits[slot])
    seq = engine.slots[slot].tokens          # the prompt + every token fed
    engine.slots[slot].active = False
    engine.slots[slot].request_id = None

    ref_cfg = dataclasses.replace(cfg, attn_impl="ref")
    want = jax.jit(lambda p, t: model_mod.forward(p, ref_cfg, {"tokens": t})[0])(
        engine.params, jnp.asarray([seq], jnp.int32))[0, PROMPT_LEN - 1:]
    v = cfg.vocab_size
    got = np.stack([np.asarray(g, np.float32)[:v] for g in got])
    want = np.asarray(want, np.float32)[:, :v]
    err = _rel_err(got, want)
    per_pos = [f"{_rel_err(g, w):.2e}" for g, w in zip(got, want, strict=True)]
    print(f"logits: prefill + {LOGIT_DECODE_STEPS} decode steps vs forward(ref), "
          f"{len(seq)} tokens: rel err {err:.3e} (tol {LOGITS_REL_TOL}); "
          f"per position {per_pos}; max|logit| {np.abs(want).max():.3f}")
    if not err <= LOGITS_REL_TOL:
        raise AssertionError("served logits differ from the forward pass")


def _random_beam(rng, k):
    from repro.core.events import DEFAULT_TOOLS
    from repro.core.hypothesis import BranchHypothesis, Node, NodeKind

    tools = ["grep", "read", "parse", "search", "fetch", "visit"]
    beam = []
    for hid in range(k):
        nodes = []
        for i in range(int(rng.integers(1, 5))):
            t = tools[int(rng.integers(0, len(tools)))]
            spec = DEFAULT_TOOLS[t]
            nodes.append(Node(i, NodeKind.TOOL, t, spec.level, spec.rho,
                              spec.base_latency))
        edges = [(i - 1, i) for i in range(1, len(nodes))]
        beam.append(BranchHypothesis(hid, nodes, edges, float(rng.uniform(0.1, 0.95)),
                                     context_key=("x",)))
    return beam


def phase_runtime() -> None:
    """f. The B-PASTE runtime with its full stack, then one admission pass
    over a pool too large for the host path."""
    from repro.core import admission, scoring
    from repro.core.events import RESOURCE_DIMS
    from repro.core.interference import Machine
    from repro.core.patterns import PatternEngine
    from repro.core.runtime import RuntimeConfig, run_mode
    from repro.core.workload import (
        WorkloadConfig, episodes_to_traces, make_episodes, open_loop_source,
    )

    machine = Machine()
    patterns = PatternEngine(context_len=2, min_support=3).fit(
        episodes_to_traces(make_episodes(WorkloadConfig(seed=1, n_episodes=20))))
    n_tenants = 6
    source = open_loop_source(WorkloadConfig(
        seed=SEED + 42, n_episodes=n_tenants, open_loop_rate=0.1,
        shared_frac=0.5, shared_pool=2))
    t0 = time.perf_counter()
    m = run_mode([], patterns, "bpaste", machine, seed=SEED,
                 max_concurrent_episodes=4, episode_source=source, memo=True,
                 model_max_batch=8, spec_model_steps=True, shed_alpha=1.0,
                 adaptive_linger=True)
    s = m.summary()
    served = len(m.tenant_sojourn)
    print(f"runtime: run_mode bpaste+stack, {served}/{n_tenants} tenants served "
          f"in {time.perf_counter() - t0:.3f} s of host time; "
          f"p95_sojourn {s['p95_sojourn']:.3f} modelled s, auth_slowdown "
          f"{s['mean_auth_slowdown']:.3f}, qos_violations {s['qos_violations']:.0f}")
    if served != n_tenants or s["mean_auth_slowdown"] > 1.0 + 1e-9 \
            or s["qos_violations"] != 0:
        raise AssertionError("the runtime broke a serving guarantee")

    host_max = RuntimeConfig().host_admit_max
    rng = np.random.default_rng(SEED)
    beam = _random_beam(rng, host_max + 88)
    scorer = scoring.Scorer(machine)
    slack = np.array([5.7, 41.0, 180.0, 1.0])
    budget = np.array([4.3, 33.0, 150.0, 1.0])
    auth = rng.uniform(0.0, 2.0, RESOURCE_DIMS)
    fused = admission.fused_admit(beam, scorer, slack, budget, auth,
                                  small_beam_threshold=host_max)
    greedy = admission.greedy_admit(beam, scorer, slack, budget, auth)
    fused_ids = sorted(h.hid for h in fused.admitted)
    greedy_ids = sorted(h.hid for h in greedy.admitted)
    print(f"runtime: admit_beam on a pool of {len(beam)} (> host_admit_max "
          f"{host_max}) admitted {fused_ids}; greedy_admit admitted {greedy_ids}")
    if fused_ids != greedy_ids or not fused_ids:
        raise AssertionError("admit_beam and greedy_admit disagree")
    # both sides are float32 on the device; the greedy re-scores a repacked
    # pool each round, so the sums may differ in the last bits
    for hid, eu in greedy.eu.items():
        np.testing.assert_allclose(fused.eu[hid], eu, rtol=1e-4, atol=1e-4)


def main() -> int:
    # libtpu would otherwise write its logs under /tmp, outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found platform {dev.platform!r}); "
              "nothing was run", file=sys.stderr)
        return 1
    count = len(jax.devices())
    print(f"device: {dev.device_kind}, {count} device(s), jax {jax.__version__}",
          flush=True)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import use_compile_cache
    print(f"compile cache: {use_compile_cache()}", flush=True)

    failed = []

    def phase(name, fn, *args):
        t0 = time.perf_counter()
        try:
            out = fn(*args)
        except Exception:
            traceback.print_exc()
            failed.append(name)
            print(f"FAILED {name}", flush=True)
            return None
        print(f"passed {name} ({time.perf_counter() - t0:.1f} s)", flush=True)
        return out if out is not None else True

    served = phase("serving", phase_serving)
    if served:
        engine, report = served
        phase("kernels in program", phase_kernels_in_program, report)
        phase("logits", phase_logits, engine)
        del engine, report, served
    else:
        failed += ["kernels in program", "logits"]
    phase("kernel numerics", phase_kernel_numerics)
    phase("runtime", phase_runtime)

    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
