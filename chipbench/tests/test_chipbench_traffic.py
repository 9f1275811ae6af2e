"""The open-loop schedule: one seed gives one schedule, every seed the
same work in another order, lengths in their clip ranges, the fill
before the window, and the offered rate exact in each."""

import json
import os

import numpy as np
import pytest

from chipbench import traffic

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MIXES = ["danube-chat", "mamba2-chat-burst"]


def mix(name):
    with open(os.path.join(REPO, "chipbench", "traffic", name + ".json")) as f:
        return json.load(f)


def key(s):
    return [(a.due_s, a.max_new_tokens, a.prompt.tolist()) for a in s]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    t = mix(name)
    assert key(traffic.schedule(t, 7, 30, 1000)) == key(traffic.schedule(t, 7, 30, 1000))
    assert key(traffic.schedule(t, 7, 30, 1000)) != key(traffic.schedule(t, 8, 30, 1000))


def parts(s):
    return [x for x in s if x.due_s < 0], [x for x in s if x.due_s >= 0]


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_offers_the_same_work(name):
    t = mix(name)
    a = traffic.schedule(t, 3, 45, 1000)
    b = traffic.schedule(t, 2**31 + 12345, 45, 1000)     # a large seed too
    rate = t["arrivals"]["rate_per_s"]
    def gaps(p, end):        # the last gap runs to the part's end
        return sorted(np.diff([x.due_s for x in p] + [end]))

    for pa, pb, n, end in zip(parts(a), parts(b),
                              (round(rate * t["fill_s"]), round(rate * 45)),
                              (0.0, 45.0)):
        assert len(pa) == len(pb) == n
        # the same gaps, prompt and output lengths in each part ...
        for f in (lambda x: len(x.prompt), lambda x: x.max_new_tokens):
            assert sorted(map(f, pa)) == sorted(map(f, pb))
        assert np.allclose(gaps(pa, end), gaps(pb, end))
        # ... in another order
        assert [len(x.prompt) for x in pa] != [len(x.prompt) for x in pb]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_in_range_and_arrivals_in_window(name):
    t = mix(name)
    s = traffic.schedule(t, 11, 45, 500)
    p, o = t["prompt_tokens"], t["output_tokens"]
    assert all(p["min"] <= len(a.prompt) <= p["max"] for a in s)
    assert all(o["min"] <= a.max_new_tokens <= o["max"] for a in s)
    assert all(0 <= a.prompt.min() and a.prompt.max() < 500 for a in s)
    due = [a.due_s for a in s]
    assert due == sorted(due) and due[0] == -t["fill_s"] and due[-1] < 45
    assert min(d for d in due if d >= 0) == 0.0
    assert t["engine"]["max_len"] >= p["max"] + o["max"]


def test_no_fill_without_fill_s():
    t = {k: v for k, v in mix("danube-chat").items() if k != "fill_s"}
    s = traffic.schedule(t, 4, 45, 1000)
    assert len(s) == round(t["arrivals"]["rate_per_s"] * 45)
    assert s[0].due_s == 0.0 and all(a.due_s >= 0 for a in s)


def test_burstiness_follows_the_process():
    t = mix("mamba2-chat-burst")
    g = np.diff([a.due_s for a in traffic.schedule(t, 5, 2000, 100)
                 if a.due_s >= 0])
    cv = g.std() / g.mean()
    assert 1.5 < cv < 2.5            # Gamma renewal, CV 2
    t = mix("danube-chat")
    g = np.diff([a.due_s for a in traffic.schedule(t, 5, 2000, 100)
                 if a.due_s >= 0])
    assert 0.85 < g.std() / g.mean() < 1.15          # Poisson: CV 1
