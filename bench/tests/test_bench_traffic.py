"""The traffic generator: stored pools, determinism per seed, the same
work for every seed."""
from collections import Counter

import numpy as np
import pytest

from bench.harness import spec, traffic

MIXES = ["agent-dsa", "rollout-grpo"]


def mix(name):
    return spec.load_json(spec.BENCH_DIR / "traffic" / f"{name}.json")


@pytest.mark.parametrize("name", MIXES)
def test_file_holds_its_drawn_pool(name):
    m = mix(name)
    assert m["pool"] == traffic.draw_pool(m)


def _sessions(seed, n=40.0):
    return traffic.sessions(mix("agent-dsa"), seed, 64000, 0.5, n)


def test_sessions_deterministic_per_seed():
    a, b = _sessions(2**33 + 5), _sessions(2**33 + 5)
    assert [s.start for s in a] == [s.start for s in b]
    for x, y in zip(a, b):
        assert np.array_equal(x.first, y.first)
        assert all(np.array_equal(p, q) for p, q in zip(x.tools, y.tools))
    c = _sessions(2**33 + 6)
    assert not np.array_equal(a[0].first, c[0].first)


def test_sessions_draw_from_pool_same_work_per_seed():
    m = mix("agent-dsa")
    pool = m["pool"]
    rate, horizon = 0.2, 66.0
    n = round(rate * horizon)
    work = []
    for seed in (1, 2**31 + 11):
        ss = traffic.sessions(m, seed, 64000, rate, horizon)
        assert len(ss) == n
        # each pool script as often as the others
        used = Counter(pool["scripts"].index(s.script) for s in ss)
        assert max(used.values()) - min(used.values()) <= 1
        for s in ss:
            assert len(s.first) == m["system_prompt_tokens"] + \
                s.script["task"]
            assert [len(t) for t in s.tools] == \
                [t["tool"] for t in s.script["turns"][1:]]
        # the pool's scripts in turn, started at the first n stored gaps
        # scaled to the horizon
        assert [s.script for s in ss] == \
            [pool["scripts"][i % len(pool["scripts"])] for i in range(n)]
        starts = [s.start for s in ss]
        assert starts[0] == 0.0 and starts[-1] < horizon
        gaps = np.diff(starts + [horizon])
        scale = horizon / sum(pool["gaps"][:n])
        assert np.allclose(gaps, np.asarray(pool["gaps"][:n]) * scale)
        # one shared system prompt, distinct first task tokens
        sp = m["system_prompt_tokens"]
        assert all(np.array_equal(s.first[:sp], ss[0].first[:sp]) for s in ss)
        firsts = [s.first[sp] for s in ss]
        assert len(set(firsts)) == len(firsts)
        work.append(([s.script for s in ss], starts))
    assert work[0] == work[1]


def test_warmup_sessions_replay_every_script_once():
    m = mix("agent-dsa")
    ss = traffic.sessions(m, 7, 64000, 1.0, 0.0, warmup=True,
                          system_tokens=256)
    assert [s.script for s in ss] == m["pool"]["scripts"]
    assert all(s.start == 0.0 for s in ss)
    assert all(len(s.first) == 256 + s.script["task"] for s in ss)


def test_groups_deterministic_and_from_pool():
    m = mix("rollout-grpo")
    pool = m["pool"]
    take = lambda seed: [g for g, _ in zip(          # noqa: E731
        traffic.groups(m, seed, 256000), range(48))]
    a, b = take(2**32 + 3), take(2**32 + 3)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
    for g in a:
        assert len(g.prompt) in pool["prompts"]
        assert all(r in pool["responses"] for r in g.max_new)
        assert len(g.max_new) == m["group_size"]
    # the pool's sizes in their stored order, round and round
    k = len(pool["prompts"])
    assert [len(g.prompt) for g in a] == \
        [pool["prompts"][i % k] for i in range(len(a))]
    r = pool["responses"]
    assert [x for g in a for x in g.max_new] == \
        [r[i % len(r)] for i in range(len(a) * m["group_size"])]
    assert len({int(g.prompt[0]) for g in a}) == len(a)
    # another seed: the same sizes, other tokens
    c = take(2**32 + 4)
    assert [(len(g.prompt), g.max_new) for g in c] == \
        [(len(g.prompt), g.max_new) for g in a]
    assert not np.array_equal(a[0].prompt, c[0].prompt)


def test_warmup_groups_cover_every_prompt_length():
    m = mix("rollout-grpo")
    gs = list(traffic.groups(m, 5, 256000, warmup=True))
    assert [len(g.prompt) for g in gs] == m["pool"]["prompts"]
