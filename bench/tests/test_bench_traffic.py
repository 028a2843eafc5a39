"""The traffic generator: deterministic per seed, clipped, same work for
every seed."""
import itertools
import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import generator  # noqa: E402

MIXES = Path(__file__).resolve().parents[1] / "traffic"
BIG_SEED = 2**31 + 977          # seeds above 32 signed bits occur


def mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


def first(name, seed, n, rate=0.0):
    return list(itertools.islice(
        generator.stream(mix(name), seed, 49155, rate), n))


@pytest.mark.parametrize("name", ["chat", "long"])
def test_same_seed_same_requests(name):
    a, b = first(name, BIG_SEED, 100, 2.0), first(name, BIG_SEED, 100, 2.0)
    assert [(r.due, r.out_len) for r in a] == [(r.due, r.out_len) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = first(name, BIG_SEED + 1, 100, 2.0)
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("name", ["chat", "long"])
def test_every_seed_gets_the_same_schedule(name):
    a, c = first(name, BIG_SEED, 100, 2.0), first(name, 3, 100, 2.0)
    assert [(r.due, len(r.prompt), r.out_len) for r in a] == \
        [(r.due, len(r.prompt), r.out_len) for r in c]


@pytest.mark.parametrize("name", ["chat", "long"])
def test_lengths_keep_their_clips(name):
    m = mix(name)
    reqs = first(name, 5, 3 * m["block"])
    prompts = [len(r.prompt) for r in reqs]
    outs = [r.out_len for r in reqs]
    assert m["prompt"]["min"] <= min(prompts) <= max(prompts) <= m["prompt"]["max"]
    assert m["output"]["min"] <= min(outs) <= max(outs) <= m["output"]["max"]
    assert max(int(r.prompt.max()) for r in reqs) < 49155


@pytest.mark.parametrize("name", ["chat", "long"])
def test_each_block_holds_the_same_quantiles(name):
    n = mix(name)["block"]
    reqs = first(name, 9, 2 * n)
    for blk in (reqs[:n], reqs[n:]):
        assert sorted(len(r.prompt) for r in blk) == sorted(
            generator.lognormal_quantiles(mix(name)["prompt"], n))
        assert sorted(r.out_len for r in blk) == sorted(
            generator.lognormal_quantiles(mix(name)["output"], n))


def test_open_loop_window_and_rate():
    reqs = generator.open_loop(mix("chat"), 11, 49155, rate=4.0, seconds=48.0)
    dues = [r.due for r in reqs]
    assert dues == sorted(dues) and dues[-1] < 48.0
    # stratified exponential gaps: each block of 64 spans 64 / rate seconds
    # but for the rounding of the quantiles
    assert abs(len(reqs) - 4.0 * 48.0) <= 8
    gaps = np.diff([0.0] + dues[:64])
    assert sum(gaps) == pytest.approx(
        sum(generator.exponential_quantiles(4.0, 64)))


def test_backlog_has_no_schedule():
    assert all(r.due == 0.0 for r in first("long", 3, 10))
