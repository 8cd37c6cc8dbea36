"""The program's span recorder (``repro.runtime.spans``)."""

import time

import pytest

from repro.runtime.spans import Recorder, Span, self_time


def test_nesting_links_each_span_to_the_one_open_around_it():
    rec = Recorder()
    with rec.span("outer", rid=7) as outer:
        with rec.span("inner") as inner:
            pass
        with rec.span("inner"):
            pass
        outer.set(lanes=3)
    with rec.span("next"):
        pass
    got = rec.spans()
    assert [s.name for s in got] == ["inner", "inner", "outer", "next"]
    assert [s.parent for s in got] == [outer.id, outer.id, None, None]
    assert got[0].id == inner.id
    assert got[2].attrs == {"rid": 7, "lanes": 3}
    assert all(s.t0 <= s.t1 for s in got)


def test_a_span_closes_when_an_exception_leaves_it():
    rec = Recorder()
    with pytest.raises(KeyError):
        with rec.span("outer"):
            with rec.span("inner"):
                raise KeyError
    with rec.span("after"):
        pass
    assert [(s.name, s.parent) for s in rec.spans()][-1] == ("after", None)


def test_self_time_leaves_out_what_children_cover():
    spans = [Span(0, "p", 0.0, 10.0, None, {}),
             Span(1, "c", 1.0, 3.0, 0, {}),
             Span(2, "c", 2.0, 5.0, 0, {}),    # overlaps the first child
             Span(3, "g", 2.0, 4.0, 1, {}),    # a grandchild
             Span(4, "c", 8.0, 9.0, 0, {}),
             Span(5, "q", 20.0, 21.0, None, {})]
    got = self_time(spans)
    assert got[0] == pytest.approx(10.0 - 4.0 - 1.0)
    assert got[1] == pytest.approx(1.0)
    assert got[3] == pytest.approx(2.0)
    assert got[5] == pytest.approx(1.0)


def test_spans_are_clipped_to_the_interval():
    rec = Recorder()
    with rec.span("before"):
        pass
    with rec.span("straddles"):
        t = time.perf_counter()
        with rec.span("after"):
            pass
    got = {s.name: s for s in rec.spans(since=t)}
    assert set(got) == {"straddles", "after"}
    assert got["straddles"].t0 == t
    assert got["after"].parent == got["straddles"].id
    assert [s.name for s in rec.spans(until=t)] == ["before", "straddles"]
    assert rec.spans(until=t)[1].t1 == t


def test_the_ring_keeps_the_newest_and_counts_what_it_dropped():
    rec = Recorder(capacity=4)
    for i in range(6):
        with rec.span("s", i=i):
            pass
    assert [s.attrs["i"] for s in rec.spans()] == [2, 3, 4, 5]
    assert rec.dropped == 2


def test_bench_names_are_the_benchmarks():
    with pytest.raises(ValueError, match="bench"):
        Recorder().span("bench.decode")


@pytest.mark.parametrize("head_dim,prompt,flash", [
    (128, 1000, 1), (128, 890, 0), (64, 1000, 0)])
def test_prefill_span_says_whether_flash_prefill_ran(head_dim, prompt, flash):
    """``serve.prefill`` carries ``flash`` = 1 exactly where the padded
    prompt's attention runs the ``flash_prefill`` kernel: 128-wide heads
    over at least one key block (1000 tokens pad to 1024; 890 to 896,
    under it), never 64-wide heads."""
    import jax

    from repro.configs import get_config
    from repro.models import api
    from repro.runtime import spans
    from repro.serve import ServeEngine

    cfg = get_config("tt-lm-100m", smoke=True).with_(head_dim=head_dim)
    eng = ServeEngine(cfg, api(cfg).init_params(jax.random.PRNGKey(0)),
                      n_slots=1, max_seq=1024, prompt_bucket=128)
    t0 = time.perf_counter()
    eng.prefill_request(list(range(1, prompt + 1)))
    pre = [s for s in spans.spans(t0, time.perf_counter())
           if s.name == "serve.prefill"]
    assert [s.attrs for s in pre] == [
        {"tokens": -(-prompt // 128) * 128, "real": prompt, "flash": flash}]
