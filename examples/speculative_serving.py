"""Speculative serving, end to end — two demos in one driver.

Default (fast, CI's fast tier runs exactly this):

  PYTHONPATH=src python examples/speculative_serving.py

demonstrates the **batched edge-box configuration** on the discrete-event
runtime: an accel=1 Thor-class box serving 8 concurrent tenants is
model-step-bound — the serial model-step queue, not tool work, sets the
makespan, so plain speculation cannot help (PR 3/4's converged
``thor_c8`` rows).  Turning on the batched model-step service
(``RuntimeConfig.model_max_batch``, src/repro/core/model_service.py)
coalesces concurrent tenants' reasoning steps into micro-batched model
invocations; the compressed queue frees accelerator time, and B-PASTE's
speculation + cross-episode result store convert the recovered slack into
end-to-end speedup — while ``mean_auth_slowdown`` stays at 1.0 and QoS
violations stay at zero (batching never taxes the authoritative path).

With ``--with-llm``, additionally serves agent episodes on a real
(reduced-config) LLM through ``repro.launch.serve``, once without and once
with B-PASTE batch-slot speculation: the agent loop decodes reasoning
tokens on the ServingEngine; during each tool call, B-PASTE prefills the
predicted observation into a free slot so the follow-up reasoning is
already decoding when the tool returns (promotion = zero-copy slot
re-tag).  This path compiles a JAX model.

  PYTHONPATH=src python examples/speculative_serving.py --with-llm --arch qwen2-7b
"""
import argparse


# ----------------------------------------------------------------------
# Part 1 (default): the batched edge-box serving configuration
# ----------------------------------------------------------------------
def run_edge_box_demo(n_episodes: int = 8, concurrency: int = 8,
                      max_batch: int = 8) -> None:
    from repro.core.interference import Machine
    from repro.core.patterns import PatternEngine
    from repro.core.runtime import run_mode
    from repro.core.workload import (
        WorkloadConfig, episodes_to_traces, make_episodes,
    )

    thor = Machine()                         # accel=1 Thor-class edge box
    train = make_episodes(WorkloadConfig(seed=1, n_episodes=20))
    engine = PatternEngine(context_len=2, min_support=3).fit(
        episodes_to_traces(train))
    tenants = make_episodes(WorkloadConfig(
        seed=42, n_episodes=n_episodes, arrival_stagger=4.0,
        shared_frac=0.5, shared_pool=2))

    print(f"edge box (accel=1), {n_episodes} tenants, "
          f"concurrency={concurrency}:")
    results = {}
    for label, mode, memo, mb in [
        ("serial (no speculation)", "serial", False, 1),
        ("bpaste+memo (queue serial)", "bpaste", True, 1),
        ("bpaste+memo+batch", "bpaste", True, max_batch),
    ]:
        m = run_mode(tenants, engine, mode, thor, seed=7,
                     max_concurrent_episodes=concurrency, memo=memo,
                     model_max_batch=mb)
        s = m.summary()
        results[label] = s
        batch = ""
        if s["model_batched_steps"]:
            batch = (f"  batch_occ={s['model_batch_occupancy']:.2f} "
                     f"queue_delay={s['mean_model_queue_delay']:.2f}s")
        print(f"  {label:28s} makespan={s['makespan']:7.1f}  "
              f"auth_slowdown={s['mean_auth_slowdown']:.3f}  "
              f"qos_violations={s['qos_violations']:.0f}{batch}")
    serial = results["serial (no speculation)"]
    plain = results["bpaste+memo (queue serial)"]
    batched = results["bpaste+memo+batch"]
    print(f"  -> with the model-step queue serial, speculation barely moves "
          f"the edge box ({serial['makespan'] / plain['makespan']:.2f}x): "
          f"the queue IS the bottleneck")
    print(f"  -> batching the queue separates it: "
          f"{serial['makespan'] / batched['makespan']:.2f}x over serial, "
          f"authoritative protection intact")
    assert batched["makespan"] < serial["makespan"], "edge regime must separate"
    assert batched["mean_auth_slowdown"] <= 1.05 and batched["qos_violations"] == 0


# ----------------------------------------------------------------------
# Part 1b (default): sustained load — open-loop arrivals + shed ladder
# ----------------------------------------------------------------------
def run_open_loop_demo(rate: float = 0.1, n_episodes: int = 16,
                       concurrency: int = 4) -> None:
    from repro.core.interference import Machine
    from repro.core.patterns import PatternEngine
    from repro.core.runtime import run_mode
    from repro.core.workload import (
        WorkloadConfig, episodes_to_traces, make_episodes, open_loop_source,
    )

    thor = Machine()
    train = make_episodes(WorkloadConfig(seed=1, n_episodes=20))
    engine = PatternEngine(context_len=2, min_support=3).fit(
        episodes_to_traces(train))

    def source():
        return open_loop_source(WorkloadConfig(
            seed=42, n_episodes=n_episodes, open_loop_rate=rate,
            shared_frac=0.5, shared_pool=2))

    print(f"\nopen loop: tenants arrive at rate={rate}/s "
          f"(exponential inter-arrivals), concurrency={concurrency}:")
    results = {}
    for label, mode, stack in [
        ("serial (no speculation)", "serial", {}),
        ("bpaste+stack (shed+linger)", "bpaste",
         dict(memo=True, model_max_batch=8, spec_model_steps=True,
              shed_alpha=1.0, adaptive_linger=True)),
    ]:
        m = run_mode([], engine, mode, thor, seed=7,
                     max_concurrent_episodes=concurrency,
                     episode_source=source(), **stack)
        s = m.summary()
        s["_served"] = len(m.tenant_sojourn)
        results[label] = s
        shed = ""
        if s["shed_passes"]:
            shed = (f"  shed_passes={s['shed_passes']:.0f} "
                    f"peak_backlog={s['shed_peak_backlog']:.0f}")
        print(f"  {label:28s} p95_sojourn={s['p95_sojourn']:7.1f}s  "
              f"auth_slowdown={s['mean_auth_slowdown']:.3f}  "
              f"qos_violations={s['qos_violations']:.0f}{shed}")
    for s in results.values():
        assert s["_served"] == n_episodes, "every tenant must be served"
        assert s["mean_auth_slowdown"] <= 1.0 + 1e-9
        assert s["qos_violations"] == 0
    print("  -> under sustained load the ladder sheds speculation first "
          "(never authoritative work): slowdown stays 1.000, QoS clean; "
          "the full goodput-vs-rate knee sweep lives in "
          "`python -m benchmarks.run --only serving`")


# ----------------------------------------------------------------------
# Part 2 (--with-llm): batch-slot speculation on a real reduced LLM
# ----------------------------------------------------------------------
def run_llm_demo(arch: str, n_episodes: int) -> None:
    from repro.launch import serve

    sizes = dict(reduced=True, n_episodes=n_episodes, max_batch=4,
                 max_len=192, prompt_len=48, reason_tokens=5)
    _, base = serve.run(arch, speculate=False, **sizes)
    _, spec = serve.run(arch, speculate=True, **sizes)
    print(f"baseline : {base.decode_steps} decode steps in {base.serve_s:.1f}s")
    print(f"B-PASTE  : {spec.decode_steps} decode steps in {spec.serve_s:.1f}s "
          f"(speculative slots admitted={spec.spec_admitted}, "
          f"promoted={spec.promotions}, preempted={spec.preemptions})")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b")
    ap.add_argument("--episodes", type=int, default=8,
                    help="tenants in the edge-box demo (LLM demo caps at 3)")
    ap.add_argument("--max-batch", type=int, default=8,
                    help="model-step micro-batch cap for the edge-box demo")
    ap.add_argument("--with-llm", action="store_true",
                    help="also run the reduced-LLM ServingEngine demo "
                         "(compiles a JAX model; minutes on CPU)")
    args = ap.parse_args()
    run_edge_box_demo(n_episodes=args.episodes, max_batch=args.max_batch)
    run_open_loop_demo()
    if args.with_llm:
        run_llm_demo(args.arch, min(args.episodes, 3))


if __name__ == "__main__":
    main()
