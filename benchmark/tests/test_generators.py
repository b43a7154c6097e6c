"""Each generator: byte-identical for one seed, different for another."""
import importlib
import json
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent
MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))


def plan_bytes(mix_name, seed, **load):
    mix = json.loads((BENCH / "traffic" / f"{mix_name}.json").read_text())
    gen = importlib.import_module(f"generators.{mix['generator']}")
    params = dict(mix, rate_per_s=4.0, clients=3, horizon_s=20.0, **load)
    plan = gen.make(params, seed, 1000, 0.05)
    first = plan.initial()
    later = [r for q in first[:3] for r in plan.on_done(q, [5, 6, 7], 9.0)]
    return json.dumps([[r.due, r.prompt, r.max_new, str(r.key)]
                       for r in first + later]).encode()


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_bytes_other_seed_other_bytes(mix):
    assert plan_bytes(mix, 7) == plan_bytes(mix, 7)
    assert plan_bytes(mix, 7) != plan_bytes(mix, 8)


def test_chat_prompts_share_no_first_token():
    mix = json.loads((BENCH / "traffic" / "chat.json").read_text())
    gen = importlib.import_module("generators.poisson_open")
    plan = gen.make(dict(mix, rate_per_s=20.0, horizon_s=30.0), 1, 152064, 1.0)
    reqs = plan.initial()
    assert len({r.prompt[0] for r in reqs}) == len(reqs) > 400
    assert all(32 <= len(r.prompt) <= 2048 and 16 <= r.max_new <= 384
               for r in reqs)


def test_session_turns_extend_their_own_history():
    mix = json.loads((BENCH / "traffic" / "sessions.json").read_text())
    gen = importlib.import_module("generators.sessions")
    plan = gen.make(dict(mix, rate_per_s=4.0, horizon_s=30.0), 3, 152064, 1.0)
    first = plan.initial()
    fresh = next(r for r in first if len(r.prompt) <= 2048 + 256)
    assert fresh.prompt[:2048] in plan.system
    nxt = plan.on_done(fresh, [11] * fresh.max_new, 12.0)[0]
    assert nxt.prompt[:len(fresh.prompt)] == fresh.prompt
    assert nxt.prompt[len(fresh.prompt):][:fresh.max_new] == [11] * fresh.max_new
    assert 12.0 + 2.0 <= nxt.due <= 12.0 + 6.0
    assert max(len(r.prompt) + r.max_new for r in first) <= 4096


def test_closed_loop_client_sends_its_next_when_the_last_ended():
    mix = json.loads((BENCH / "traffic" / "longctx-sat.json").read_text())
    gen = importlib.import_module("generators.closed_loop")
    plan = gen.make(dict(mix, clients=16, horizon_s=50.0), 1, 250880, 1.0)
    first = plan.initial()
    assert len(first) == 16 and all(1024 <= len(r.prompt) <= 1792
                                    and r.max_new == 128 for r in first)
    nxt = plan.on_done(first[5], [1] * 128, 7.5)
    assert len(nxt) == 1 and nxt[0].due == 7.5 and nxt[0].key == first[5].key


def test_the_work_is_fixed_by_the_mix_not_by_the_seed():
    mix = json.loads((BENCH / "traffic" / "chat.json").read_text())
    gen = importlib.import_module("generators.poisson_open")
    params = dict(mix, rate_per_s=2.4, horizon_s=55.0)

    def work(seed):
        reqs = gen.make(params, seed, 152064, 1.0).initial()
        window = [r for r in reqs if r.due >= mix["ramp_s"]]
        return (len(reqs), len(window), sorted(len(r.prompt) for r in window),
                sorted(r.max_new for r in window))

    assert work(1) == work(2)
    assert work(1)[:2] == (132, 120)           # 2.4/s x 5 s ramp + 50 s window
    a = gen.make(params, 1, 152064, 1.0).initial()
    b = gen.make(params, 2, 152064, 1.0).initial()
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]   # order differs


def test_closed_loop_lengths_are_spread_evenly_whatever_the_seed():
    mix = json.loads((BENCH / "traffic" / "longctx-sat.json").read_text())
    gen = importlib.import_module("generators.closed_loop")
    means = []
    for seed in (1, 2, 3):
        plan = gen.make(dict(mix, clients=6, horizon_s=55.0), seed, 250880, 1.0)
        reqs = plan.initial()
        for k in range(14):
            reqs += plan.on_done(reqs[k], [1] * 128, 5.0 + k)
        means.append(sum(len(r.prompt) for r in reqs) / len(reqs))
    assert max(means) - min(means) < 0.03 * 1408       # 1408 = the mean
