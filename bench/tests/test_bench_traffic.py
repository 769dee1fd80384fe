"""The traffic files give the same requests and due times for a seed, and
every seed the same amount of work."""
import numpy as np
import pytest

from bench import traffic

MIXES = ["decode-backlog", "chat-bursty"]
SEEDS = [0, 2**31 + 77, 2**40 + 3]


def _reqs(name, seed, seconds=30.0):
    return traffic.generate(traffic.load_mix(name), seed=seed, seconds=seconds,
                            vocab_size=50_000)


@pytest.mark.parametrize("name", MIXES)
@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_same_requests(name, seed):
    a, b = _reqs(name, seed), _reqs(name, seed)
    assert [(r.rid, r.due_s, r.new_tokens) for r in a] == \
        [(r.rid, r.due_s, r.new_tokens) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_the_work_not_the_order(name):
    a, b = _reqs(name, 1), _reqs(name, 2)
    assert len(a) == len(b)
    for key in (lambda r: len(r.prompt), lambda r: r.new_tokens):
        assert sorted(map(key, a)) == sorted(map(key, b))
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])


@pytest.mark.parametrize("name", MIXES)
@pytest.mark.parametrize("seed", SEEDS)
def test_prefill_groups_cover_every_equal_prompt_run(name, seed):
    """Set-up warms each group size up to the longest run of one prompt
    length in arrival order, and no size the traffic cannot form."""
    mix = traffic.load_mix(name)
    reqs = _reqs(name, seed)
    lens = [len(r.prompt) for r in reqs]
    assert set(lens) <= set(mix["prompt_tokens"]["values"])
    longest, run = {}, 0
    for i, n in enumerate(lens):
        run = run + 1 if i and lens[i - 1] == n else 1
        longest[n] = max(longest.get(n, 0), run)
    groups = traffic.prefill_groups(reqs, 64)
    assert sorted(groups) == sorted((k, n) for n, r in longest.items()
                                    for k in range(1, r + 1))
    capped = traffic.prefill_groups(reqs, 2)
    assert max(k for k, _ in capped) <= 2 and {n for _, n in capped} == set(lens)


def test_open_loop_rate_and_window():
    mix = traffic.load_mix("chat-bursty")
    for seed in SEEDS:
        due = [r.due_s for r in _reqs("chat-bursty", seed, seconds=30.0)]
        assert due[0] == 0.0 and due == sorted(due) and due[-1] < 30.0
        assert len(due) == round(mix["rate_hz"] * 30.0)


def test_bursts_run_faster_than_quiets():
    mix = traffic.load_mix("chat-bursty")
    rng = np.random.default_rng(5)
    gaps = np.diff(traffic.arrival_times(mix, 200.0, rng))
    # the fixed layout puts half the requests in bursts at twice the rate
    assert np.median(gaps) < 1 / mix["rate_hz"]


def test_backlog_is_due_at_once():
    reqs = _reqs("decode-backlog", 9)
    assert len(reqs) == traffic.load_mix("decode-backlog")["requests"]
    assert {r.due_s for r in reqs} == {0.0}
