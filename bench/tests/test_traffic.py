"""The seeded traffic: one seed, one schedule; every seed, the same work."""
import json
from pathlib import Path

import numpy as np
import pytest

from traffic import lengths, prefill_chunks, schedule

CHAT = json.loads((Path(__file__).parents[1] / "traffic" / "chat.json")
                  .read_text())
BIG = 2 ** 31 + 12345


def _key(arrivals):
    return [(a.rid, a.due, a.max_new, a.in_window, a.prompt.tobytes())
            for a in arrivals]


def test_same_seed_same_schedule():
    assert _key(schedule(CHAT, BIG, 45, 32000)) == \
        _key(schedule(CHAT, BIG, 45, 32000))


def test_seeds_share_the_schedule_and_draw_their_own_tokens():
    a, b = schedule(CHAT, 7, 45, 32000), schedule(CHAT, BIG, 45, 32000)
    shape = lambda s: [(x.rid, x.due, len(x.prompt), x.max_new, x.in_window)
                       for x in s]
    assert shape(a) == shape(b)
    assert all(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    win = [x for x in a if x.in_window]
    assert len(win) == int(CHAT["rate_per_s"] * 45)
    assert max(x.due for x in win) < 45 <= min(x.due for x in a
                                               if not x.in_window)


def test_a_mix_without_an_order_seed_is_refused():
    mix = {k: v for k, v in CHAT.items() if k != "order_seed"}
    with pytest.raises(ValueError, match="order_seed"):
        schedule(mix, 7, 45, 32000)


def test_the_order_seed_orders_the_same_sizes():
    mix = dict(CHAT, order_seed=CHAT["order_seed"] + 1)
    a = [x for x in schedule(CHAT, 7, 45, 32000) if x.in_window]
    b = [x for x in schedule(mix, 7, 45, 32000) if x.in_window]
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in b)
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in b)
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]


def test_lengths_are_clipped_quantiles():
    xs = lengths(CHAT["prompt"], 101)
    assert xs.min() >= 32 and xs.max() <= 1024
    assert xs[50] == 256                         # the median


def test_prefill_chunks_are_powers_of_two_largest_first():
    assert prefill_chunks(13, 512) == [(0, 8), (8, 4), (12, 1)]
    assert prefill_chunks(1100, 512) == [(0, 512), (512, 512), (1024, 64),
                                         (1088, 8), (1096, 4)]
