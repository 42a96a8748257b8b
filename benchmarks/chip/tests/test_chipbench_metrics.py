"""Each metric's arithmetic on a synthetic record of spans, counters and a
reduced trace."""
import importlib.util
import os
import sys
from types import SimpleNamespace

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from chipbench import flops, harness, results, testing  # noqa: E402

DIMS = {"L": 2, "D": 8, "H": 4, "KV": 2, "hd": 2, "F": 16, "V": 10}
PEAKS = {"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e5}
LLAMA = harness.load_module(os.path.join(BENCH, "references", "llama.py"), "ref_llama_t")


def _metric(name):
    spec = importlib.util.spec_from_file_location(
        "metric_t_" + name.replace(".", "_"), os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _turn(due, first, times, tokens=None, failed=False):
    return SimpleNamespace(due=due, first=first, end=times[-1], token_times=times,
                           tokens=tokens or list(range(len(times))), failed=failed,
                           prompt=[1] * 4)


@pytest.fixture
def rec():
    return {
        "t0": 0.0, "t_end": 10.0, "seconds": 10.0, "dims": DIMS, "ref": LLAMA,
        "peaks": PEAKS,
        # t0 t1 active spec sum_ctx; the last starts after the window
        "steps": [(1.0, 1.1, 4, 1, 100), (2.0, 2.3, 2, 0, 50), (10.5, 10.6, 1, 0, 9)],
        # t0 t1 tokens speculative
        "prefills": [(0.5, 0.7, 64, False), (3.0, 3.2, 128, True)],
        "spans": [("engine.step", 1.0, 1.1, 1), ("engine.step", 2.0, 2.3, 1),
                  ("engine.add_request", 0.5, 0.7, 1), ("spec.admit", 2.9, 3.3, 1),
                  ("engine.add_request", 3.0, 3.2, 2), ("client.idle", 4.0, 9.0, 1),
                  ("hyp.build", 2.8, 2.9, 1)],
        "spec_admitted": 4, "spec_promoted": 1,
        "turns": [_turn(0.0, 0.7, [0.7, 1.1, 2.3]), _turn(2.0, 2.5, [2.5, 3.5]),
                  _turn(11.0, 11.2, [11.2, 11.4]), _turn(1.0, 1.0, [1.0], failed=True)],
        "trace_window": (0.9, 2.4),
        "trace": {"window_s": 2.0, "busy_s": 1.5, "devices": 1, "gaps": {},
                  "ops": [["jit__decode", "decode_attention.3", 0.02, 2, True],
                          ["jit__decode", "fusion.1", 0.3, 2, False],
                          ["jit__prefill_one", "flash_attention.1", 0.5, 1, True]],
                  "modules": {}},
    }


def test_loop_host_time_leaves_out_engine_and_idle(rec):
    # 10 s less 0.1 + 0.3 + 0.2 + 0.2 (engine) + 5 (idle) = 4.2 s over 2 steps
    assert _metric("loop_host_ms_per_step")(rec) == pytest.approx(2100.0)


def test_speculation_shares(rec):
    assert _metric("spec_promoted_share")(rec) == pytest.approx(25.0)
    assert _metric("spec_row_share")(rec) == pytest.approx(100.0 * 1 / 6)
    rec["spec_admitted"] = 0
    assert _metric("spec_promoted_share")(rec) is None


def test_engine_times(rec):
    assert _metric("decode_step_ms")(rec) == pytest.approx(200.0)
    assert _metric("prefill_ms_per_ktok")(rec) == pytest.approx(1e6 * 0.4 / 192)


def test_mfu(rec):
    want = (flops.model_prefill(DIMS, 64) + flops.model_prefill(DIMS, 128)) / (0.4 * 1e6)
    assert _metric("mfu.prefill")(rec) == pytest.approx(100 * want)
    want = (flops.model_decode(DIMS, 4, 100) + flops.model_decode(DIMS, 2, 50)) / (0.4 * 1e6)
    assert _metric("mfu.decode")(rec) == pytest.approx(100 * want)


def test_rooflines_read_the_traced_stretch_only(rec):
    # both steps lie in the traced stretch (0.9, 2.4); one prefill does not
    least = sum(flops.least_time(flops.decode_attn(DIMS, a, c), PEAKS)
                for a, c in ((4, 100), (2, 50)))
    assert _metric("decode_attn_roofline")(rec) == pytest.approx(100 * least / 0.02)
    assert _metric("flash_prefill_roofline")(rec) is None
    rec["trace_window"] = (0.4, 2.4)
    least = flops.least_time(flops.flash_prefill(DIMS, 64), PEAKS)
    assert _metric("flash_prefill_roofline")(rec) == pytest.approx(100 * least / 0.5)


def test_counts_come_from_the_records_reference(rec, tmp_path):
    """A second architecture's reference gives its own counts; one that
    counts no such kernel leaves the roofline silent."""
    copy = os.path.dirname(testing.tiny_bench(str(tmp_path)))
    toy = harness.load_module(os.path.join(copy, "references", "toy_moe.py"), "ref_toy_moe_t")
    rec["ref"], rec["dims"] = toy, dict(DIMS, E=4, k=2)
    want = sum(toy.decode_work(rec["dims"], a, c)["model_flops"] for a, c in ((4, 100), (2, 50)))
    assert want != flops.model_decode(DIMS, 4, 100) + flops.model_decode(DIMS, 2, 50)
    assert _metric("mfu.decode")(rec) == pytest.approx(100 * want / (0.4 * 1e6))
    rec["ref"] = SimpleNamespace(decode_work=lambda d, r, c: {"model_flops": 1.0,
                                                               "kernels": {}})
    assert _metric("decode_attn_roofline")(rec) is None


def test_a_hybrid_record_counts_each_application(rec, tmp_path):
    """The hybrid reference counts its Mamba-2 layers per row and its shared
    attention block at each of its applications, and the attention kernels
    once per application, so ``mfu.*`` and the rooflines read its record."""
    copy = os.path.dirname(testing.tiny_bench(str(tmp_path)))
    hyb = harness.load_module(os.path.join(copy, "references", "toy_hybrid.py"),
                              "ref_toy_hybrid_t")
    d = hyb.dims(testing.TINY_HYBRID_CONFIG)
    # by hand: a Mamba-2 layer meets 64*(2*128 + 2*16 + 8) + 4*(128 + 2*16) +
    # 128*64 = 27776 weights and 5*128*16 state elements, the shared block
    # 64*8*16 + 64*64 = 12288 weights; 5 layers, 2 applications, the head
    # 64*256; attention 4*4*16 operations per attended position, twice
    row = 2 * (5 * 27776 + 2 * 12288 + 64 * 256) + 5 * 5 * 128 * 16
    assert hyb.decode_work(d, 1, 10)["model_flops"] == row + 2 * 4 * 4 * 16 * 10
    rec["ref"], rec["dims"] = hyb, d
    want = sum(hyb.decode_work(d, a, c)["model_flops"] for a, c in ((4, 100), (2, 50)))
    assert _metric("mfu.decode")(rec) == pytest.approx(100 * want / (0.4 * 1e6))
    attn = dict(d, L=2)
    least = sum(flops.least_time(flops.decode_attn(attn, a, c), PEAKS)
                for a, c in ((4, 100), (2, 50)))
    assert _metric("decode_attn_roofline")(rec) == pytest.approx(100 * least / 0.02)
    rec["trace_window"] = (0.4, 2.4)
    least = flops.least_time(flops.flash_prefill(attn, 64), PEAKS)
    assert _metric("flash_prefill_roofline")(rec) == pytest.approx(100 * least / 0.5)
    want = sum(hyb.prefill_work(d, n)["model_flops"] for n in (64, 128))
    assert _metric("mfu.prefill")(rec) == pytest.approx(100 * want / (0.4 * 1e6))


def test_idle_share_and_no_trace(rec):
    assert _metric("device_idle_share")(rec) == pytest.approx(25.0)
    rec["trace"] = None
    for name in ("device_idle_share", "decode_attn_roofline", "flash_prefill_roofline"):
        assert _metric(name)(rec) is None


def test_end_to_end(rec):
    e = results.end_to_end(rec)
    # counted: the first two turns (the third is due after the window, the
    # fourth failed); tokens inside the window: 3 + 2
    assert e["tokens_per_s"] == pytest.approx(0.5)
    assert e["turn_p90_s"] == pytest.approx(1.5 + 0.9 * (2.3 - 1.5))
    assert e["ttft_p90_s"] == pytest.approx(0.5 + 0.9 * 0.2)
    # gaps 400, 1200, 1000 ms
    assert e["itl_p95_ms"] == pytest.approx(1000 + 0.9 * 200)


def test_widest_gap():
    import numpy as np
    lg = [np.array([[0.0, 2.0, 1.0], [3.0, 0.5, 0.0]])]
    assert results.widest_gap(lg, [[1, 0]]) == 0.0
    assert results.widest_gap(lg, [[2, 2]]) == 3.0
