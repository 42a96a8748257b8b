"""The agent traffic generator: repeatable per seed, different across seeds,
and the same set of sizes for every seed."""
import importlib.util
import json
import os

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _gen():
    spec = importlib.util.spec_from_file_location(
        "gen_react_agents_t", os.path.join(BENCH, "generators", "react_agents.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def test_same_seed_same_traffic_and_tokens():
    gen, p = _gen(), _mix("react_decode")
    roster = gen.load_roster(p)
    big = 2**31 + 12345
    assert gen.make_agents(p, big, roster) == gen.make_agents(p, big, roster)
    a, b = gen.token_stream(big, 3, 49152), gen.token_stream(big, 3, 49152)
    assert a(100) == b(100)


def test_seeds_differ_in_order_not_in_sizes():
    gen, p = _gen(), _mix("react_history")
    roster = gen.load_roster(p)
    x, y = gen.make_agents(p, 1, roster), gen.make_agents(p, 2, roster)
    assert x != y
    assert gen.token_stream(1, 0, 512)(50) != gen.token_stream(2, 0, 512)(50)
    for key in ("reason", "obs"):
        for r in range(p["rounds"]):
            assert sorted(a[key][r] for a in x) == sorted(a[key][r] for a in y)
    assert sorted(a["delay_s"] for a in x) == sorted(a["delay_s"] for a in y)
    assert sorted(a["episode0"] for a in x) == sorted(a["episode0"] for a in y)
    # whole scripts are dealt, so each keeps its lengths with its tools
    def script(a):
        return json.dumps({k: v for k, v in a.items() if k != "agent"}, sort_keys=True)
    assert sorted(map(script, x)) == sorted(map(script, y))
    assert [a["agent"] for a in x] == list(range(p["agents"]))


def test_sizes_follow_the_mix():
    gen, p = _gen(), _mix("react_decode")
    agents = gen.make_agents(p, 5, gen.load_roster(p))
    r = np.array([a["reason"] for a in agents])
    o = np.array([a["obs"] for a in agents])
    assert r.min() >= p["reason_tokens"]["min"] and r.max() <= p["reason_tokens"]["max"]
    assert o.min() >= p["observation_tokens"]["min"]
    assert o.max() <= p["observation_tokens"]["max"]
    assert abs(np.median(r) - p["reason_tokens"]["median"]) <= 2


def test_history_lands_on_a_bucket_and_is_kept_to_its_tail():
    gen, p = _gen(), _mix("react_decode")
    draw = gen.token_stream(0, 0, 1000)
    assert gen.prompt_lengths(p) == [1024, 1536]
    hist = gen.pad_history(list(range(1024 + 40 + 100)), p, draw)
    assert len(hist) == 1536 and hist[:1164] == list(range(1164))
    hist = gen.pad_history(list(range(1536 + 200)), p, draw)
    assert len(hist) == 1536 and hist[0] == 512
