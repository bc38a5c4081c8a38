"""The generator: the same seed gives the same inputs, another seed the
same work in another order."""
import json
import os

import numpy as np

import traffic
from conftest import BENCH


def mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def test_requests_repeat_and_differ():
    m = mix("chat_closed_c8")
    big = 3_000_000_019     # the driver's seeds pass 2**31
    a = traffic.requests(m, big, 32000, 130)
    b = traffic.requests(m, big, 32000, 130)
    c = traffic.requests(m, big + 1, 32000, 130)
    assert a == b
    assert [r["prompt"] for r in a] != [r["prompt"] for r in c]
    grid = sorted(traffic.length_grid(m))
    for reqs in (a, c):     # every lap holds the whole grid once
        lap = sorted((len(r["prompt"]), r["max_new_tokens"])
                     for r in reqs[:len(grid)])
        assert lap == grid
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in c]
    lo, hi = m["prompt_tokens"]["low"], m["prompt_tokens"]["high"]
    assert all(lo <= len(r["prompt"]) <= hi for r in a)
    assert all(0 < t < 32000 for r in a for t in r["prompt"])


def test_batches_repeat_and_differ():
    m = mix("pretrain_s128_b128")
    a = traffic.batches(m, 7, 30522, 1)
    b = traffic.batches(m, 7, 30522, 1)
    c = traffic.batches(m, 8, 30522, 4)
    assert len(a) == m["pool_batches"]
    assert all(np.array_equal(x[0], y[0]) for x, y in zip(a, b))
    assert a[0][0].shape == (128, 128) and c[0][0].shape == (512, 128)
    tokens = a[0][0]
    assert (tokens[::4, -16:] == 0).all() and (tokens[1, :] != 0).all()
    assert len({row.tobytes() for row in tokens}) == len(tokens)


def test_dp4_mix_is_the_one_chip_mix_a_chip():
    one, four = mix("pretrain_s128_b128"), mix("pretrain_s128_b128_dp4")
    one.pop("why"), four.pop("why")
    assert one == four
