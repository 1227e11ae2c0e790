"""The traffic generator: seeded, the same work for every seed, and the
mixes' own promises."""
import json
from pathlib import Path

import pytest

from bench.harness.traffic import ClosedLoop, lognormal_pool

MIXES = Path(__file__).resolve().parents[1] / "traffic"


def _mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


def _draw(name, seed, lanes=8, n=64):
    gen = ClosedLoop(_mix(name), lanes, seed)
    specs = [gen.spec(i % len(gen.clients)) for i in range(n)]
    return gen, [(s.seed, s.steps, s.prompt_len) for s in specs]


@pytest.mark.parametrize("name", ["wave", "stagger", "chat"])
def test_deterministic_per_seed(name):
    big = 2 ** 31 + 12345
    a, specs_a = _draw(name, big)
    b, specs_b = _draw(name, big)
    assert specs_a == specs_b
    assert [c.start_tick for c in a.clients] == \
        [c.start_tick for c in b.clients]
    _, specs_c = _draw(name, big + 1)
    assert specs_a != specs_c


def test_stagger_start_offsets_are_distinct():
    gen = ClosedLoop(_mix("stagger"), 8, 7)
    starts = [c.start_tick for c in gen.clients]
    assert len(set(starts)) == len(starts) == 8
    assert all(0 <= s < 50 for s in starts)
    # every seed gets the same offsets, in another order
    other = [c.start_tick for c in ClosedLoop(_mix("stagger"), 8, 8).clients]
    assert sorted(other) == sorted(starts) and other != starts


def test_wave_is_one_client_with_a_lane_wide_batch():
    gen = ClosedLoop(_mix("wave"), 8, 3)
    assert len(gen.clients) == 1 and gen.batch == 8
    assert [c.cid for c in gen.due(0)] == [0]


def test_chat_prompts_only_from_warmed_buckets():
    gen, specs = _draw("chat", 11, lanes=32, n=2048)
    lens = [p for _, _, p in specs]
    assert set(lens) == set(gen.prompt_lens) == {128, 512}
    assert abs(lens.count(128) - lens.count(512)) <= 2


def test_chat_steps_same_multiset_for_every_seed():
    mix = _mix("chat")
    pool = mix.get("pool", 512)
    a = sorted(s for _, s, _ in _draw("chat", 1, 32, pool)[1])
    b = sorted(s for _, s, _ in _draw("chat", 2, 32, pool)[1])
    assert a == b
    st = mix["steps"]
    assert st["min"] <= min(a) and max(a) <= st["max"]
    assert abs(sorted(a)[pool // 2] - st["lognormal"]["median"]) <= 2


def test_lognormal_pool_quantiles():
    pool = lognormal_pool(4, 64, 0.75, 16, 256)
    assert pool == sorted(pool) and pool[0] >= 16 and pool[-1] <= 256
