"""The work counters against brute force and ``repro.core.paths``."""

import itertools
import math

import pytest

from benchlib import work
from repro.core.paths import find_topk_paths
from repro.core.tensor_network import tt_linear_network

def all_orders_input_macs(cores: list[dict], x: dict) -> int:
    """Brute force over every pairwise contraction order (small networks
    only): the least input-dependent MACs."""
    best = math.inf

    def rec(nodes, acc):
        nonlocal best
        if acc >= best:
            return
        if len(nodes) == 1:
            best = acc
            return
        for i, j in itertools.combinations(range(len(nodes)), 2):
            (a, ia), (b, ib) = nodes[i], nodes[j]
            shared = set(a) & set(b)
            merged = {e: d for e, d in list(a.items()) + list(b.items())
                      if e not in shared}
            cost = math.prod({**a, **b}.values()) if (ia or ib) else 0
            rest = [nodes[k] for k in range(len(nodes)) if k not in (i, j)]
            rec(rest + [(merged, ia or ib)], acc + cost)

    rec([(c, False) for c in cores] + [(x, True)], 0)
    return int(best)


GEOMETRIES = [
    ((4, 4), (8, 4), (4, 4, 4)),
    ((8, 4), (4, 4), (8, 8, 4)),
    ((4, 2, 2), (2, 4, 2), (2, 4, 4, 4, 2)),
]


@pytest.mark.parametrize("tokens", [1, 3, 16])
@pytest.mark.parametrize("geom", GEOMETRIES)
def test_least_macs_is_the_least_over_every_order(geom, tokens):
    in_m, out_m, ranks = geom
    net = work.tt_linear_tensors(tokens, in_m, out_m, ranks)
    assert work.least_input_macs(*net) == all_orders_input_macs(*net)


@pytest.mark.parametrize("tokens", [1, 7, 256])
@pytest.mark.parametrize("geom", GEOMETRIES + [
    ((12, 8, 8), (16, 16, 8), (16, 16, 16, 16, 16)),
    ((16, 16, 16), (107, 16, 8), (16, 16, 16, 16, 16))])
def test_least_macs_bounds_the_searched_paths(geom, tokens):
    """No path of the program's search does less input-dependent work,
    and the count is linear in the tokens."""
    in_m, out_m, ranks = geom
    least = work.least_input_macs(*work.tt_linear_tensors(tokens, in_m,
                                                          out_m, ranks))
    paths = find_topk_paths(tt_linear_network(tokens, in_m, out_m, ranks),
                            k=8)
    searched = min(sum(g.macs for g in p.gemms if g.a_is_input or g.b_is_input)
                   for p in paths)
    assert least <= searched <= paths[0].macs
    one = work.least_input_macs(*work.tt_linear_tensors(1, in_m, out_m, ranks))
    assert least == tokens * one


def test_head_count_matches_brute_force():
    net = work.tt_head_tensors(5, (4, 2, 4), (2, 4, 2), (3, 4))
    assert work.least_input_macs(*net) == all_orders_input_macs(*net)


def test_counter_arithmetic():
    proj = {"attn.wq": work.Projection("attn.wq", 8, 16),
            "head": work.Projection("head", 8, 32)}
    c = work.Counter(proj, n_layers=2, n_heads=2, head_dim=4, act_bytes=4,
                     param_bytes=4, peak={"flops": 1e3, "hbm_bytes_per_s": 1e3})
    c.prefill(3, kernels={"attn.wq"})
    # layers 2 x (3 x 8 x 16); attention 2 x 2 x 4 x (1+2+3) x 2 layers;
    # head at one position 8 x 32; FLOPs = 2 x MACs
    assert c.flops == 2 * (2 * 3 * 8 * 16 + 2 * 2 * 4 * 6 * 2 + 8 * 32)
    t_f = 2 * 3 * 8 * 16 / 1e3
    t_b = (3 * (8 + 16) * 4 + 8 * 16 * 4) / 1e3
    assert c.tt_least_s == pytest.approx(2 * max(t_f, t_b))
    before = c.flops
    c.decode([4, 1])
    assert c.flops - before == 2 * (2 * 2 * 8 * 16 + 2 * 2 * 4 * 5 * 2
                                    + 2 * 8 * 32)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("no such chip")
    assert work.peaks("TPU v5 lite")["flops"] == 197e12
