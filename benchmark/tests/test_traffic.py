"""The traffic generator: every seed gets the same multiset of sizes and of
gaps between arrivals, in another order, with other tokens."""
import numpy as np

import harness
import traffic


def _mix(name):
    return harness.load_json(harness.HERE, "traffic", name + ".json")


def test_open_loop_same_sizes_and_gaps_for_every_seed_inside_the_window():
    mix = _mix("chat-mixed")
    a = traffic.open_loop(mix, 1, 40.0, 50257)
    b = traffic.open_loop(mix, 2 ** 31 + 7, 40.0, 50257)
    assert len(a) == len(b) == round(mix["rate_per_s"] * 40)
    size = lambda rs: sorted((len(r.prompt), r.max_new) for r in rs)
    assert size(a) == size(b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    gaps = lambda rs: np.sort(np.diff([0.0] + [r.due for r in rs]))
    assert np.allclose(gaps(a), gaps(b))
    assert all(0 < r.due < 40.0 for r in a + b)
    assert all(len(r.prompt) + r.max_new <= mix["max_total"] for r in a)
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])


def test_closed_loop_pool_is_a_permutation_of_one_set_of_sizes():
    mix = _mix("decode-saturated")
    a = traffic.closed_loop(mix, 3, 50257)
    b = traffic.closed_loop(mix, 4, 50257)
    assert len(a) == mix["pool"]
    size = lambda rs: sorted((len(r.prompt), r.max_new) for r in rs)
    assert size(a) == size(b)
    lo, hi = mix["prompt_len"]["min"], mix["prompt_len"]["max"]
    assert all(lo <= len(r.prompt) <= hi for r in a)
    assert all(1 <= int(r.prompt.min()) and int(r.prompt.max()) < 50257
               for r in a[:50])


def test_shared_prefix_groups_and_warm_requests():
    mix = dict(_mix("chat-mixed"),
               shared_prefix={"tokens": 16, "groups": 2})
    reqs = traffic.open_loop(mix, 5, 40.0, 50257)
    heads = {tuple(r.prompt[:16]) for r in reqs if len(r.prompt) > 16}
    assert len(heads) == 2
    warm = traffic.warm_requests(mix, 5, 50257)
    assert [(len(r.prompt), r.max_new) for r in warm] == [
        tuple(w) for w in mix["warm"]]
