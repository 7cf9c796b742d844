"""The roofline's bytes, worked by hand for both configurations, and
the peaks table."""
import pytest

import peaks
import roofline
import yardstick as ys


def _work(n_ranks, n_nodes, reqs, req_bytes, cb_bytes, merged, interleave):
    offsets, lengths = ys.e3sm_g_requests(n_ranks, reqs, req_bytes, merged,
                                          interleave)
    e = ys.ELEM_BYTES
    n_elems = ys.padded_file_elems(int((offsets + lengths).max()) // e,
                                   n_nodes, cb_bytes // e)
    counts = [reqs] * n_ranks
    return roofline.io_bytes(offsets // e, lengths // e, counts, n_elems,
                             n_nodes)


def test_e3sm_g_node():
    # the file is 43 windows of 16 MiB, half of it the other node's holes
    w = _work(1, 1, 679688, 524, 16 << 20, 64, 128)
    assert w == {"payload": 356156512, "file": 721420288,
                 "meta": 679688 * 8, "crossing": 0}
    t, bound = roofline.least_time(w, 1, peaks.peaks_of("TPU v5 lite"))
    assert bound == "hbm"
    assert 356156512 + 721420288 + 5437504 == 1083014304
    assert t == pytest.approx(1083014304 / 819e9)


def test_e3sm_g_2x2():
    # slots s of 131 elements, rank s % 4 on node (s % 4) // 2, domain
    # boundary at element 33,554,432 inside slot 256,140 (rank 0): 92
    # elements stay, 39 cross; 128,070 whole slots of node 1 lie in
    # domain 0 and 128,069 of node 0 in domain 1
    w = _work(4, 2, 128070, 524, 16 << 20, 1, 4)
    crossing = ((128070 + 128069) * 131 + 39) * 4
    assert w == {"payload": 268434720, "file": 268435456,
                 "meta": 4 * 128070 * 8, "crossing": crossing}
    assert crossing == 134216992
    t, bound = roofline.least_time(w, 4, peaks.peaks_of("TPU v5 lite"))
    assert bound == "ici"
    assert t == pytest.approx(crossing / 4 / 200e9)
    t_hbm = (268434720 + 268435456 + 4098240) / 4 / 819e9
    assert t_hbm < t


def test_dead_requests_count_nothing():
    w = roofline.io_bytes([[0, 4]], [[4, 4]], [1], 8, 1)
    assert w == {"payload": 16, "file": 32, "meta": 8, "crossing": 0}


def test_unknown_device_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_of("cpu")
