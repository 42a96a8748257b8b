"""Serving-substrate integration: agent episodes through the one serving
loop (``repro.launch.serve``) on a reduced model, with B-PASTE batch-slot
speculation.  Rows name the platform they ran on; a CPU row times the CPU
backend, not a device."""
from __future__ import annotations

from typing import Dict, List

import jax

from repro.launch import serve


def run() -> List[Dict]:
    max_batch = 4
    _, r = serve.run("granite-8b", reduced=True, n_episodes=3,
                     max_batch=max_batch, max_len=192, prompt_len=48,
                     reason_tokens=5)
    dev = jax.devices()[0]
    where = f"reduced model, {dev.platform} {dev.device_kind}"
    return [
        {"name": f"serving/decode_step_b{max_batch}",
         "us_per_call": r.decode_step_s * 1e6,
         "derived": f"median over {r.decode_steps} steps, "
                    f"{r.tokens_decoded} tokens ({where})"},
        {"name": "serving/compile", "us_per_call": r.compile_s * 1e6,
         "derived": f"decode + {len(r.compiled) - 1} prefill lengths ({where})"},
        {"name": "serving/speculative_slots", "us_per_call": r.serve_s * 1e6,
         "derived": f"admitted={r.spec_admitted} promoted={r.promotions} "
                    f"preempted={r.preemptions} (whole loop, {where})"},
    ]
