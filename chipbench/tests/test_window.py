import types

import numpy as np
import pytest

from chipbench import common, serving


def test_quantile_matches_numpy():
    x = np.random.default_rng(0).exponential(size=301)
    for q in (0.5, 0.9, 0.99):
        assert common.quantile(list(x), q) == pytest.approx(
            np.percentile(x, 100 * q))


def _req(due, stamps, prompt=4):
    rec = serving.Emissions(due)
    rec.t = list(stamps)
    return types.SimpleNamespace(rec=rec, prompt=np.zeros(prompt, np.int32))


def window():
    w = serving.Window(types.SimpleNamespace(c={}, t={}), 10.0,
                       common.Spans(False))
    w.t0, w.t_end, w.t_close = 100.0, 110.0, 111.0
    w.reqs = [
        _req(100.0, [100.5, 101.0, 101.2]),                   # done
        _req(104.0, [106.0, 109.0, 111.0, 112.0]),     # ends after close
        _req(109.0, []),                                      # no token yet
        _req(110.5, []),                                      # after the end
    ]
    return w


def test_tokens_gaps_and_requests_count_only_the_window():
    w = window()
    # 111.0 is from the tick running at the end; 112.0 after the close
    assert len(w.emitted()) == 6
    assert sorted(w.gaps()) == pytest.approx([0.2, 0.5, 2.0, 3.0])
    assert w.attempted() == 3                    # 110.5 came after the end


def test_stamped_request_keeps_first_emission_times():
    from repro.launch.serve_loop import Request
    R = serving.stamped_request_class(Request)
    r = R(rid=1, prompt=np.zeros(3, np.int32), max_new=4)
    r.out.append(7)
    r.out.append(8)
    first = list(r.rec.t)
    r.out = []                                   # preempted: replay
    r.out.append(7)
    r.out.append(8)
    r.out.append(9)
    assert r.rec.preempted == 1
    assert r.rec.t[:2] == first and len(r.rec.t) == 3
    assert isinstance(r, Request) and r.out == [7, 8, 9]
