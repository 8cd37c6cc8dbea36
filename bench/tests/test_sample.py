"""The serving check's sample spreads over the decode lanes."""

import types

from benchlib.serve import ServeCell


def fake_cell(n_slots, per_lane, seed=2**33 + 7):
    requests, lane_of, served = [], {}, {}
    for rid in range(n_slots * per_lane):
        requests.append(types.SimpleNamespace(rid=rid, prompt=(1,) * 8))
        lane_of[rid] = rid % n_slots
        served[rid] = [2] * (4 + rid % 5)
    served[17] = [2] * 500          # the longest
    cell = object.__new__(ServeCell)
    cell.seed, cell.requests = seed, requests
    cell.timed = types.SimpleNamespace(
        done=list(served), served=served, lane_of=lane_of, n_slots=n_slots)
    return cell


def test_sample_has_the_longest_and_both_halves_of_the_lanes():
    cell = fake_cell(256, 6)
    picked = cell.sample(48)
    assert len(picked) == 48
    assert picked[0] == 17
    lanes = [cell.timed.lane_of[rid] for rid in picked[1:]]
    assert len(set(lanes)) == 47                 # one request per lane
    assert sum(x < 128 for x in lanes) in (23, 24)   # the halves in turn


def test_sample_takes_a_second_request_of_a_lane_only_after_all_lanes():
    cell = fake_cell(4, 10)
    picked = cell.sample(9)
    lanes = [cell.timed.lane_of[rid] for rid in picked]
    assert sorted(lanes[1:5]) == [0, 1, 2, 3]
    assert sorted(lanes[5:9]) == [0, 1, 2, 3]
