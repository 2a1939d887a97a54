"""The generator makes its requests from the seed alone."""
from bench import traffic

MIXES = sorted(p.stem for p in traffic.TRAFFIC_DIR.glob("*.json"))
SEED = 3_000_000_019  # above 2**31, as the check's seeds are


def _key(arrivals):
    return [(a.due_s, tuple(a.prompt), a.max_new_tokens, a.temperature,
             a.top_p, a.stream) for a in arrivals]


def _small(name):
    mix = traffic.load_mix(name)
    return dict(mix, requests=2 * mix["block"])


def test_every_mix_is_found_by_name():
    assert {"chat", "long_decode"} <= set(MIXES)


def test_same_seed_same_requests():
    for name in MIXES:
        mix = _small(name)
        assert _key(traffic.schedule(mix, SEED, 1000)) == \
            _key(traffic.schedule(mix, SEED, 1000)), name


def test_seeds_offer_the_same_work():
    """Two seeds give the same lengths, due times and greedy requests,
    with other token ids and sampling streams."""
    for name in MIXES:
        mix = _small(name)
        a = traffic.schedule(mix, SEED, 1000)
        b = traffic.schedule(mix, SEED + 1, 1000)
        for xs in (a, b):
            assert len(xs) == mix["requests"]
        assert [len(x.prompt) for x in a] == [len(x.prompt) for x in b]
        assert [x.max_new_tokens for x in a] == \
            [x.max_new_tokens for x in b]
        assert [x.temperature for x in a] == [x.temperature for x in b]
        assert [x.due_s for x in a] == [x.due_s for x in b]
        assert [x.prompt for x in a] != [x.prompt for x in b]
        assert [x.stream for x in a] != [x.stream for x in b]


def test_lengths_follow_the_mix():
    mix = dict(traffic.load_mix("chat"), requests=640)
    xs = traffic.schedule(mix, 5, 151936)
    plen = sorted(len(x.prompt) for x in xs)
    assert plen[0] >= 32 and plen[-1] <= 4096
    assert abs(plen[len(plen) // 2] - 512) <= 16  # the median
    rate = len(xs) / xs[-1].due_s
    assert abs(rate - mix["arrival"]["rate_per_s"]) < 0.05 * rate
    share = sum(x.temperature == 0 for x in xs) / len(xs)
    assert share == mix["sampling"]["greedy_share"]


def test_backlog_is_due_at_once():
    xs = traffic.schedule(_small("long_decode"), 9, 49152)
    assert {x.due_s for x in xs} == {0.0}
    assert all(1024 <= len(x.prompt) <= 1536 for x in xs)
    assert all(256 <= x.max_new_tokens <= 512 for x in xs)
    assert all(len(x.prompt) + x.max_new_tokens <= 2048 for x in xs)
