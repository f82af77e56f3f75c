import itertools

import numpy as np

from chipbench import traffic
from conftest import small_serve_traffic


def first(t, seed, n=48, open_loop=True):
    return list(itertools.islice(traffic.stream(t, seed, 499, open_loop), n))


def test_same_seed_same_requests():
    t = small_serve_traffic("chat-closed8")
    a, b = first(t, 2**31 + 9), first(t, 2**31 + 9)
    assert [s.max_new for s in a] == [s.max_new for s in b]
    assert [s.due for s in a] == [s.due for s in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))


def test_seeds_change_the_tokens_not_the_work():
    t = small_serve_traffic("chat-closed8")
    t.update(shared_prefix_frac=0.5, shared_prefix_len=16, n_prefixes=2)
    a, b = first(t, 1), first(t, 2**32 + 1)
    assert [len(x.prompt) for x in a] == [len(x.prompt) for x in b]
    assert [x.max_new for x in a] == [x.max_new for x in b]
    assert [x.due for x in a] == [x.due for x in b]
    # the same requests share a prefix, with other tokens
    pre = [x.prompt[:16] for x in a]
    assert len({p.tobytes() for p in pre}) < len(pre)
    assert all((x.prompt != y.prompt).any() for x, y in zip(a, b))


def test_sizes_keep_their_bounds_and_closed_loop_has_no_schedule():
    t = small_serve_traffic("chat-closed8")
    reqs = first(t, 3, open_loop=False)
    assert all(t["prompt_min"] <= len(r.prompt) <= t["prompt_max"]
               for r in reqs)
    assert all(t["out_min"] <= r.max_new <= t["out_max"] for r in reqs)
    assert all(r.due == 0.0 for r in reqs)
    assert all(r.prompt.dtype == np.int32 for r in reqs)

