"""A copy of the benchmark with tiny configurations, a mix and a metric added
as files only, for the CPU tests: ``tiny`` of the llama architecture,
``tiny_moe`` of a second one, ``toy_moe`` (the program's ``moe`` family),
and ``tiny_hybrid`` of a third, ``toy_hybrid`` (the program's ``hybrid``
family, whose layers differ in kind), whose architectures and references
are new files too (``tests/toy_moe/``, ``tests/toy_hybrid/``)."""
from __future__ import annotations

import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY_CONFIG = {
    "name": "tiny", "architecture": "llama", "reference": "llama",
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
    "vocab_size": 256, "rope_theta": 10000.0, "rms_norm_eps": 1e-05,
    "attention_bias": False, "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "attn_impl": "pallas_interpret",
    "engine": {"max_batch": 4, "max_len": 256, "spec_slots": 2},
    "check": {"logit_gap_limit": 0.02},
}

# mixtral's keys: 4 experts, top-2, ``intermediate_size`` the expert width
TINY_MOE_CONFIG = dict(TINY_CONFIG, name="tiny_moe", architecture="toy_moe",
                       reference="toy_moe", num_local_experts=4, num_experts_per_tok=2,
                       intermediate_size=64, torch_dtype="float32")
# granite-4.0-h's Mamba-2 keys; 5 Mamba-2 layers, the shared attention block
# after every 2nd (applied twice), then a tail layer.  Served in bfloat16,
# so that the control is float8: in float32 the program read a widest gap
# of 0 on 14 seeds and the bfloat16 control 0 to 0.0051, 0 on 3 of them, so
# no limit fails that control.  In bfloat16 the program read 0 to 0.0120
# over 14 seeds, the float8 control 0.0343 to 0.1068, a state left out at
# decode 0.079 or more: the limit 0.025 lies between
TINY_HYBRID_CONFIG = dict(TINY_CONFIG, name="tiny_hybrid", architecture="toy_hybrid",
                          reference="toy_hybrid", num_hidden_layers=5, attn_every=2,
                          mamba_d_state=16, mamba_d_conv=4, mamba_expand=2, mamba_d_head=16,
                          mamba_n_heads=8, mamba_n_groups=1, mamba_chunk_size=32,
                          check={"logit_gap_limit": 0.025})
# the other architectures' files, laid out as in the benchmark
TOY_MOE = os.path.join(BENCH, "tests", "toy_moe")
TOY_HYBRID = os.path.join(BENCH, "tests", "toy_hybrid")

TINY_MIX = {
    "generator": "react_agents", "agents": 6, "rounds": 16, "roster": "roster.json",
    "initial_tokens": 64, "bucket": 32, "keep_tokens": 96,
    "reason_tokens": {"median": 5, "sigma": 0.4, "min": 3, "max": 8},
    "observation_tokens": {"median": 12, "sigma": 0.4, "min": 4, "max": 24},
    "stagger_s": 0.02, "tool_latency_scale": 0.03,
    "spec_beam_width": 3, "spec_obs_tokens": 16,
}

# a per-layer metric that no file of the benchmark has
NEW_METRIC = '''"""Turns counted in the window."""


def read(rec):
    return float(sum(1 for t in rec["turns"] if t.end is not None))
'''


def tiny_bench(root: str, **config_overrides) -> str:
    """Copy the benchmark under ``root``, add the tiny files, and return the
    path of the copy's BENCHMARK.json.  ``config_overrides`` apply to
    ``tiny``."""
    dst = os.path.join(root, "bench")
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for files in (TOY_MOE, TOY_HYBRID):
        shutil.copytree(files, dst, dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(dst, "configs", "tiny.json"), "w") as f:
        json.dump(dict(TINY_CONFIG, **config_overrides), f)
    for cfg in (TINY_MOE_CONFIG, TINY_HYBRID_CONFIG):
        with open(os.path.join(dst, "configs", cfg["name"] + ".json"), "w") as f:
            json.dump(cfg, f)
    with open(os.path.join(dst, "traffic", "tiny_mix.json"), "w") as f:
        json.dump(TINY_MIX, f)
    with open(os.path.join(dst, "metrics", "turns_finished.py"), "w") as f:
        f.write(NEW_METRIC)
    with open(os.path.join(dst, "peaks.json")) as f:
        peaks = json.load(f)
    peaks["cpu"] = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                    "source": "a stand-in for CPU tests; no CPU number is a device metric"}
    with open(os.path.join(dst, "peaks.json"), "w") as f:
        json.dump(peaks, f)
    cells = ["tiny.tiny_mix", "tiny_moe.tiny_mix", "tiny_hybrid.tiny_mix"]
    bench = {
        "workloads": [{"name": c, "config": c.split(".")[0], "traffic": "tiny_mix",
                       "chips": 1} for c in cells],
        "end_to_end": [{"name": n, "unit": u} for n, u in (
            ("turn_p90_s", "s"), ("ttft_p90_s", "s"), ("itl_p95_ms", "ms"),
            ("tokens_per_s", "tokens/s"), ("setup_s", "s"))],
        "per_layer": [{"name": "decode_step_ms", "unit": "ms", "workloads": cells},
                      {"name": "turns_finished", "unit": "turns", "workloads": cells}],
    }
    path = os.path.join(dst, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(bench, f)
    return path
