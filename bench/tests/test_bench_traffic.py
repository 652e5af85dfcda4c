"""The traffic generator: one plan per seed, lengths inside their clips,
every seed the same multiset of sizes, and prompts exact in tokens."""

from collections import Counter

import pytest

from bench import traffic

MIXES = sorted(p.stem for p in traffic.TRAFFIC_DIR.glob("*.json"))
NODES = ["edge-0", "edge-1"]
BIG_SEED = 2 ** 40 + 17


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_plan(name):
    mix = traffic.load_mix(name)
    assert traffic.generate(mix, BIG_SEED, NODES) == traffic.generate(mix, BIG_SEED, NODES)
    assert traffic.generate(mix, 3, NODES) != traffic.generate(mix, 4, NODES)


@pytest.mark.parametrize("name", MIXES)
def test_clips_hold(name):
    mix = traffic.load_mix(name)
    plan = traffic.generate(mix, BIG_SEED, NODES)
    sys_tokens = mix.get("system_prompt_tokens", 0)
    assert len(plan.sessions) == mix["sessions"]
    for s in plan.sessions:
        assert mix["turns"]["min"] <= len(s.turns) <= mix["turns"]["max"]
        assert sum(t.roam for t in s.turns) <= 1
        for j, t in enumerate(s.turns):
            body = t.prompt_tokens - (sys_tokens if j == 0 else 0)
            assert mix["prompt_tokens"]["min"] <= body <= mix["prompt_tokens"]["max"]
            assert mix["output_tokens"]["min"] <= t.max_new <= mix["output_tokens"]["max"]
            assert j == 0 or t.think_ms >= mix["think_s"]["min"] * 1e3


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_the_same_sizes(name):
    mix = traffic.load_mix(name)

    def sizes(seed):
        plan = traffic.generate(mix, seed, NODES)
        turns = [t for s in plan.sessions for t in s.turns]
        starts = [s.start_ms for s in plan.sessions]
        sys_tokens = mix.get("system_prompt_tokens", 0)
        bodies = [t.prompt_tokens - (sys_tokens if j == 0 else 0)
                  for s in plan.sessions for j, t in enumerate(s.turns)]
        return (Counter(len(s.turns) for s in plan.sessions),
                Counter(bodies),
                Counter(t.max_new for t in turns),
                sorted(b - a for a, b in zip([0.0] + starts, starts)))

    a, b = sizes(5), sizes(BIG_SEED)
    assert a[:3] == b[:3]
    assert a[3] == pytest.approx(b[3], abs=1e-3)


def test_roam_share_and_system_prompt():
    roam = traffic.generate(traffic.load_mix("roam-chat"), 9, NODES)
    roamers = [s for s in roam.sessions if any(t.roam for t in s.turns)]
    assert len(roamers) == len(roam.sessions) // 2
    for s in roamers:
        j = next(i for i, t in enumerate(s.turns) if t.roam)
        assert j >= 1 and s.turns[j].node != s.turns[0].node
        assert all(t.node == s.turns[j].node for t in s.turns[j:])
    shared = traffic.generate(dict(traffic.load_mix("roam-chat"), system_prompt_tokens=64),
                              9, NODES)
    heads = {s.turns[0].prompt[:64] for s in shared.sessions}
    assert len(heads) == 1


def test_prompts_are_exact_in_tokens():
    from repro.tokenizer import get_tokenizer

    tok = get_tokenizer(151936, seed=0)
    for name in MIXES:
        plan = traffic.generate(traffic.load_mix(name), BIG_SEED, NODES)
        for s in plan.sessions[:20]:
            for t in s.turns:
                assert len(tok.encode(t.prompt)) == t.prompt_tokens


@pytest.mark.parametrize("name", MIXES)
def test_arrivals_keep_the_rate(name):
    """The stratified arrival gaps of ``n`` sessions add up to ``n / rate``
    (to the half gap the highest quantile leaves out), in start order."""
    mix = traffic.load_mix(name)
    plan = traffic.generate(mix, BIG_SEED, NODES)
    last = plan.sessions[-1].start_ms / 1e3
    assert last == pytest.approx(mix["sessions"] / mix["session_rate_per_s"], rel=0.05)
    assert all(a.start_ms <= b.start_ms for a, b in zip(plan.sessions, plan.sessions[1:]))


@pytest.mark.parametrize("n", [10, 60, 400])
def test_session_count_is_the_mix_s(n):
    mix = dict(traffic.load_mix("roam-chat"), sessions=n)
    plan = traffic.generate(mix, 5, NODES)
    assert len(plan.sessions) == n
    assert sum(len(s.turns) for s in plan.sessions) >= 3 * n
