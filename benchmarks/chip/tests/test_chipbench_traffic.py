"""The traffic generator is a pure function of the mix and the seed,
and serves every seed the sizes the mix states."""

import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(REPO), str(REPO / "src")]

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmarks.chip import traffic  # noqa: E402

SEEDS = [0, 1, 2 ** 31 + 11, 2 ** 33 + 7]


@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_same_requests(seed):
    mix = traffic.load_mix("decode-long")
    a = traffic.generate(mix, seed, 32768)
    b = traffic.generate(mix, seed, 32768)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert x.max_new_tokens == y.max_new_tokens
    c = traffic.generate(mix, seed + 1, 32768)
    assert not np.array_equal(a[0].prompt[:64], c[0].prompt[:64])


def test_seeds_past_32_bits_differ():
    mix = traffic.load_mix("decode-long")
    a = traffic.generate(mix, 7, 32768)
    b = traffic.generate(mix, 2 ** 32 + 7, 32768)
    assert not np.array_equal(a[0].prompt[:64], b[0].prompt[:64])


def test_decode_long_draws_its_stated_sessions():
    mix = traffic.load_mix("decode-long")
    t = traffic.generate(mix, 5, 32768)
    assert len(t) == len(mix["context_tokens"]) == 4
    for s in t:
        assert s.max_new_tokens == mix["new_tokens"]
        assert len(s.prompt) + s.max_new_tokens <= 8192
        assert s.prompt.min() >= 0 and s.prompt.max() < 32768
    # no two sessions share a prefix
    heads = {tuple(s.prompt[:16]) for s in t}
    assert len(heads) == len(t)


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5, 2 ** 33 + 1])
def test_every_seed_serves_the_same_sizes(seed):
    mix = traffic.load_mix("decode-long")
    lengths = [len(s.prompt) for s in traffic.generate(mix, seed, 32768)]
    assert sorted(lengths) == sorted(mix["context_tokens"])
    orders = {tuple(len(s.prompt) for s in traffic.generate(mix, x, 32768))
              for x in range(seed, seed + 8)}
    assert len(orders) > 1          # the seed sets the order
