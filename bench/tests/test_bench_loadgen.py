"""The traffic generator: deterministic by seed, clipped, and the same work
in every block whatever the seed."""

import numpy as np
import pytest

from bench.lib.loadgen import Traffic

MIX = {"loop": "open", "rate": 4.0, "block": 8,
       "prompt": {"median": 100, "sigma": 1.5, "min": 16, "max": 300},
       "output": {"median": 20, "sigma": 0.5, "min": 4, "max": 40}}
BIG = 2 ** 31 + 12345


def test_same_seed_same_requests():
    a, b = Traffic(MIX, BIG, 1000), Traffic(MIX, BIG, 1000)
    for i in (0, 5, 17):
        ra, rb = a.request(i), b.request(i)
        assert np.array_equal(ra.prompt, rb.prompt)
        assert (ra.max_new, ra.due) == (rb.max_new, rb.due)
    c = Traffic(MIX, BIG + 1, 1000)
    assert not np.array_equal(a.request(0).prompt, c.request(0).prompt)


def test_clipped_and_in_vocab():
    t = Traffic(MIX, 3, 50)
    for i in range(40):
        p, o = t.lengths(i)
        assert 16 <= p <= 300 and 4 <= o <= 40
        r = t.request(i)
        assert r.prompt.dtype == np.int32 and r.prompt.max() < 50
    # sigma 1.5 puts the lowest and highest quantiles past the clips
    assert {t.lengths(i)[0] for i in range(8)} >= {16, 300}


def test_every_block_holds_the_same_work():
    a, b = Traffic(MIX, 1, 1000), Traffic(MIX, 2, 1000)
    # the same lengths in the same order on every seed; blocks differ in
    # order, not in what they hold
    assert [a.lengths(i) for i in range(16)] == [b.lengths(i)
                                                for i in range(16)]
    for k in (0, 1):
        assert sorted(a.lengths(i)[k] for i in range(8)) == sorted(
            a.lengths(i)[k] for i in range(8, 16))
    assert [a.lengths(i) for i in range(8)] != [a.lengths(i)
                                               for i in range(8, 16)]
    # after a whole number of blocks the arrivals reach the same time
    assert np.isclose(a.due(15), b.due(15))
    assert np.isclose(a.due(15), np.sum(-np.log1p(-(np.arange(8) + .5) / 8))
                      * 2 / 4.0)


def test_warm_lengths_reach_each_bucket():
    """Warm-up takes the shortest prompt of each bucket the mix's prompts
    reach, and the shortest that is chunked; no bucket the mix never
    reaches."""
    import types

    from bench.run import warm_lengths

    t = Traffic(dict(MIX, prompt=dict(MIX["prompt"], min=20, max=300)), 0,
                10)
    lens = t.prompt_lengths()
    assert lens == sorted(set(lens)) and min(lens) >= 20 and max(lens) == 300
    serve = types.SimpleNamespace(buckets=(16, 64, 128, 256),
                                  chunked="auto")
    short, long = warm_lengths(serve, t)
    assert long == [min(n for n in lens if n > 256)]
    want = {}
    for n in lens:
        b = next((b for b in (16, 64, 128, 256) if b >= n), None)
        if b is not None:
            want.setdefault(b, n)
    assert short == sorted(want.values()) and 16 not in want
    off = types.SimpleNamespace(buckets=(512,), chunked="off")
    assert warm_lengths(off, t) == ([min(lens)], [])


CLOSED = {"loop": "closed", "clients": 8, "first_wave": "residual",
          "block": 8, "prompt": MIX["prompt"],
          "output": {"median": 200, "sigma": 0.5, "min": 64, "max": 800}}


def test_residual_first_wave():
    """Each client's first request keeps its prompt and a quantile-spaced
    share of its output; later requests are untouched, on every seed."""
    a, b = Traffic(CLOSED, 1, 1000), Traffic(CLOSED, BIG, 1000)
    plain = Traffic(dict(CLOSED, first_wave=None), 1, 1000)
    assert [a.lengths(i) for i in range(24)] == [b.lengths(i)
                                                for i in range(24)]
    for i in range(8):
        p, o = a.lengths(i)
        p0, o0 = plain.lengths(i)
        assert p == p0 and 1 <= o <= o0
    assert [a.lengths(i) for i in range(8, 24)] == [plain.lengths(i)
                                                   for i in range(8, 24)]
    shares = sorted(a.lengths(i)[1] / plain.lengths(i)[1] for i in range(8))
    assert shares == pytest.approx((np.arange(8) + 0.5) / 8, abs=0.01)


@pytest.mark.parametrize("key,value", [("think_s", 1.5), ("eos", True),
                                       ("shared_prefix", 256)])
def test_unimplemented_knobs_are_refused(key, value):
    Traffic(dict(MIX, **{key: 0 if key != "eos" else False}), 1, 10)
    with pytest.raises(ValueError, match=key):
        Traffic(dict(MIX, **{key: value}), 1, 10)
