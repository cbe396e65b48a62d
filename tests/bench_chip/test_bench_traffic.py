"""The traffic generator: every seed draws the same set of sizes and
gaps in another order, and large seeds work."""

import numpy as np
import pytest

from benchmarks.chip import traffic

MIX = {"prompt_tokens": [32, 96], "zipf_exponent": 1.2}


@pytest.mark.parametrize("seed", [0, 2**31 + 3, 2**40 + 1])
def test_every_seed_draws_the_same_lengths_in_another_order(seed):
    n = traffic.BLOCK
    a = traffic.PromptStream(MIX, 1000, seed)
    b = traffic.PromptStream(MIX, 1000, seed + 1)
    la = [len(a.next().tokens) for _ in range(n)]
    lb = [len(b.next().tokens) for _ in range(n)]
    assert sorted(la) == sorted(lb) != la
    assert min(la) == 32 and max(la) == 96
    assert sorted(la) == list(traffic.stratified_lengths(32, 96, n))


def test_the_same_seed_gives_the_same_prompts():
    a = traffic.PromptStream(MIX, 50, 2**33)
    b = traffic.PromptStream(MIX, 50, 2**33)
    for _ in range(10):
        pa, pb = a.next(), b.next()
        assert pa.rid == pb.rid and np.array_equal(pa.tokens, pb.tokens)
        assert pa.tokens.max() < 50 and pa.tokens.dtype == np.int32
    assert 0 <= traffic.jax_seed(2**40) < 2**31


def test_poisson_gaps_keep_their_rate_for_every_seed():
    for seed in (1, 2**35):
        c = traffic.ArrivalClock(2.5, seed)
        times = [c.next() for _ in range(traffic.BLOCK)]
        assert np.all(np.diff(times) > 0)
        assert times[-1] == pytest.approx(
            traffic.exponential_gaps(2.5, traffic.BLOCK).sum())
    assert traffic.exponential_gaps(2.5, 10_000).mean() == pytest.approx(
        0.4, rel=2e-3)
