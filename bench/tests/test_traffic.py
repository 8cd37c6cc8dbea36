"""Every seed serves the same lengths, block by block, in another order."""

import json
import os

import pytest

from benchlib import traffic

MIX = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "traffic", "serve-chat.json")


@pytest.fixture(scope="module")
def mix():
    with open(MIX) as f:
        return json.load(f)


def lengths(reqs):
    return sorted((len(r.prompt), r.max_new_tokens) for r in reqs)


def test_each_block_holds_the_same_pairs_on_every_seed(mix):
    b = mix["block"]
    one = traffic.backlog(mix, 32000, 2**33 + 1)
    two = traffic.backlog(mix, 32000, 7)
    assert len(one) == len(two) == mix["requests"]
    first = lengths(one[:b])
    for k in range(0, mix["requests"], b):
        assert lengths(one[k:k + b]) == lengths(two[k:k + b]) == first
    # the seed orders the block and draws the token ids
    assert [len(r.prompt) for r in one[:b]] != [len(r.prompt) for r in two[:b]]
    assert one[0].prompt != two[0].prompt
    assert all(0 <= t < 32000 for r in one[:b] for t in r.prompt)


def test_a_seed_gives_the_same_backlog_twice(mix):
    a = traffic.backlog(mix, 32000, 2**31 + 3)
    b = traffic.backlog(mix, 32000, 2**31 + 3)
    assert [(r.prompt, r.max_new_tokens) for r in a] == [
        (r.prompt, r.max_new_tokens) for r in b]


def test_block_must_divide_the_backlog(mix):
    with pytest.raises(ValueError):
        traffic.backlog(dict(mix, block=mix["block"] + 1), 32000, 1)
