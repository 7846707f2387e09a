"""The traffic generator: deterministic per seed, the same sizes for every
seed, lengths as their parameters say."""
import json
import os

import numpy as np
import pytest

import traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic")))
SEED = 2 ** 31 + 977   # the driver's seeds exceed 32 signed bits

OFFLINE = {"arrivals": {"kind": "backlog", "depth_per_lane": 2},
           "prompt": {"median": 128, "sigma": 0.8, "min": 16, "max": 1024},
           "output": {"median": 256, "sigma": 0.6, "min": 32, "max": 1000},
           "block": 64}
COMMITTED_BLOCK = 8


def test_same_seed_same_requests():
    a, b = traffic.Traffic(OFFLINE, SEED, 1000), traffic.Traffic(
        OFFLINE, SEED, 1000)
    assert [a.request(i) for i in range(100)] == [b.request(i)
                                                  for i in range(100)]


def test_seeds_share_sizes_in_another_order():
    a, b = traffic.Traffic(OFFLINE, 1, 1000), traffic.Traffic(
        OFFLINE, SEED, 1000)
    la = [a.lengths(i) for i in range(128)]
    lb = [b.lengths(i) for i in range(128)]
    for blk in (slice(0, 64), slice(64, 128)):
        assert sorted(x for x, _ in la[blk]) == sorted(x for x, _ in lb[blk])
        assert sorted(y for _, y in la[blk]) == sorted(y for _, y in lb[blk])
    assert la != lb
    assert a.request(0)[0] != b.request(0)[0]


def test_lengths_follow_their_law():
    t = traffic.Traffic(OFFLINE, 5, 1000)
    p = np.array([t.lengths(i)[0] for i in range(64)])
    o = np.array([t.lengths(i)[1] for i in range(64)])
    assert p.min() >= 16 and p.max() <= 1024
    assert o.min() >= 32 and o.max() <= 1000
    assert abs(np.median(p) - 128) <= 8 and abs(np.median(o) - 256) <= 12
    prompt, out = t.request(3)
    assert (len(prompt), out) == t.lengths(3)
    assert min(prompt) >= 2 and max(prompt) < 1000


def test_default_block_is_smaller_than_a_window():
    """Without ``block`` a mix permutes blocks of 8: every 8 requests carry
    the same sizes whatever the seed."""
    spec = {k: v for k, v in OFFLINE.items() if k != "block"}
    a, b = traffic.Traffic(spec, 11, 1000), traffic.Traffic(spec, SEED, 1000)
    assert a.block == COMMITTED_BLOCK
    for k in range(4):
        blk = range(8 * k, 8 * k + 8)
        for j in (0, 1):
            assert (sorted(a.lengths(i)[j] for i in blk)
                    == sorted(b.lengths(i)[j] for i in blk))


def test_only_backlog_arrivals():
    with pytest.raises(ValueError, match="poisson"):
        traffic.Traffic(dict(OFFLINE, arrivals={"kind": "poisson"}), 1, 100)


@pytest.mark.parametrize("mix", MIXES)
def test_committed_mix_fits_its_cells(mix):
    """The longest request a committed mix can make (its clipped lengths)
    fits every cell that sends it, and its blocks are smaller than the
    requests a window finishes: submit wants the prompt plus the outputs
    below max_seq."""
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        cells = [w for w in json.load(f)["workloads"] if w["traffic"] == mix]
    spec = traffic.load(mix)
    assert spec.get("block", COMMITTED_BLOCK) <= COMMITTED_BLOCK
    longest = spec["prompt"]["max"] + spec["output"]["max"]
    for w in cells:
        with open(os.path.join(BENCH, "configs", w["config"] + ".json")) as f:
            assert longest < json.load(f)["serving"]["max_seq"]
