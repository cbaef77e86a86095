"""The benchmark's traffic generator: seeded, stratified, shared as stated."""
import json
import pathlib
import statistics
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[2] / "bench"
sys.path.insert(0, str(BENCH))

from traffic import generator  # noqa: E402


# an open-loop mix that uses every key the generator reads (no cell's mix)
CHAT = {
    "system_prompts": {"count": 4, "length": {"dist": "uniform", "lo": 512,
                                              "hi": 1024}, "zipf_s": 1.0,
                       "primed": True},
    "turns": {"lo": 1, "hi": 4},
    "turn_gap_s": {"dist": "uniform_real", "lo": 2, "hi": 6},
    "prompt_len": {"dist": "lognormal", "median": 96, "sigma": 0.6,
                   "lo": 16, "hi": 256},
    "max_new": {"dist": "lognormal", "median": 96, "sigma": 0.6, "lo": 16,
                "hi": 192},
}


def mix(name):
    if name == "chat":
        return json.loads(json.dumps(CHAT))
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def flat(reqs):
    return [(r.prompt.tolist(), r.max_new, r.due_s) for r in reqs]


def test_open_loop_is_deterministic_per_seed():
    a = generator.open_loop(mix("chat"), 0.5, 60, 151936, 2**33 + 7)
    b = generator.open_loop(mix("chat"), 0.5, 60, 151936, 2**33 + 7)
    c = generator.open_loop(mix("chat"), 0.5, 60, 151936, 2**33 + 8)
    assert flat(a) == flat(b)
    assert flat(a) != flat(c)


def test_seeds_offer_the_same_sizes_in_another_order():
    m = mix("chat")
    a = generator.open_loop(m, 0.5, 60, 1000, 3)
    b = generator.open_loop(m, 0.5, 60, 1000, 4)
    assert [r.max_new for r in a] != [r.max_new for r in b]
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert len(a) == len(b)
    spec = {"dist": "exponential", "mean": 2.0}
    ga = generator.strata(np.random.default_rng(3), 50, spec)
    gb = generator.strata(np.random.default_rng(4), 50, spec)
    assert sorted(ga) == sorted(gb) and list(ga) != list(gb)


@pytest.mark.parametrize("name,key,lo,hi,stat,value", [
    ("chat", "max_new", 16, 192, statistics.median, 96),
    # the published means (vLLM, SOSP 2023, Fig. 11a) of ShareGPT
    ("sharegpt-batch", "prompt_len", 4, 1024, statistics.mean, 161.31),
    ("sharegpt-batch", "max_new", 4, 1024, statistics.mean, 337.99),
])
def test_length_distributions(name, key, lo, hi, stat, value):
    v = generator.strata(np.random.default_rng(0), 2000, mix(name)[key])
    assert v.min() >= lo and v.max() <= hi
    assert stat(v.tolist()) == pytest.approx(value, rel=0.03)


def test_truncated_lognormal_drops_lengths_out_of_range():
    spec = dict(mix("sharegpt-batch")["max_new"])
    cut = generator.strata(np.random.default_rng(0), 1000, spec)
    spec.pop("truncate")
    clipped = generator.strata(np.random.default_rng(0), 1000, spec)
    # clipping piles the tail on the upper end; truncation spreads it
    assert (clipped == 1024).sum() > 50
    assert (cut == 1024).sum() <= 1
    assert cut.mean() < clipped.mean()


def test_poisson_gaps_have_the_stated_mean():
    g = generator.strata(np.random.default_rng(1), 4000,
                         {"dist": "exponential", "mean": 2.0})
    assert g.mean() == pytest.approx(2.0, rel=0.01)
    assert np.median(g) == pytest.approx(2.0 * np.log(2), rel=0.01)


def test_chat_turns_extend_the_previous_prompt_and_share_system_prompts():
    reqs = generator.open_loop(mix("chat"), 1.0, 120, 151936, 9)
    by_session = {}
    for r in reqs:
        by_session.setdefault(r.session, []).append(r)
    # the four system prompts come first, once each, with one output token
    primers = by_session.pop(-1)
    assert reqs[:4] == primers
    assert [r.max_new for r in primers] == [1] * 4
    assert [len(r.prompt) for r in primers] == \
        sorted((len(r.prompt) for r in primers), reverse=True)
    firsts = {}
    for turns in by_session.values():
        turns.sort(key=lambda r: r.turn)
        for a, b in zip(turns, turns[1:]):
            assert b.prompt[:len(a.prompt)].tolist() == a.prompt.tolist()
            assert len(b.prompt) >= len(a.prompt) + a.max_new + 16
            assert 2.0 - 1e-9 <= b.due_s - a.due_s <= 6.0 + 1e-9
        assert 1 <= len(turns) <= 4
        head = tuple(turns[0].prompt[:512].tolist())
        firsts[head] = firsts.get(head, 0) + 1
    # 4 system prompts, picked with Zipf weights: the most used about twice
    # as often as the next
    counts = sorted(firsts.values(), reverse=True)
    assert len(counts) == 4
    assert counts[0] == pytest.approx(2 * counts[1], rel=0.25)
    # the most popular system prompt is the longest, for every seed
    assert firsts[tuple(primers[0].prompt[:512].tolist())] == counts[0]
    assert max(len(r.prompt) for r in reqs) <= \
        generator.longest_prompt(mix("chat"))


def test_closed_loop_deals_every_client_its_queue():
    q = generator.closed_loop(mix("sharegpt-batch"), 32, 4, 256000, 5)
    assert len(q) == 32 and all(len(c) == 4 for c in q)
    assert all(4 <= len(r.prompt) <= 1024 for c in q for r in c)


def test_mix_that_cannot_fit_is_refused():
    with pytest.raises(ValueError):
        generator.validate(mix("chat"), 2048)
    generator.validate(mix("chat"), 2832)
    with pytest.raises(ValueError):
        generator.validate(mix("sharegpt-batch"), 2048)
    generator.validate(mix("sharegpt-batch"), 2064)
