"""The benchmark's copies of the yardstick agree with ``src/`` at a tiny
size, and each configuration's numbers follow from the paper's Table I
by the stated cuts."""
import json

import numpy as np
import pytest

import yardstick as ys
from benchmarks.workloads import node_share
from repro.core.domains import contiguous_layout
from repro.core.twophase import write_reference
from repro.io_patterns import e3sm_g_pattern

CONFIGS = ys.__file__.rsplit("/", 1)[0] + "/configs/"
TABLE_I_RANKS = ys.TABLE_I["e3sm_g"]["ranks"]


def test_node_share_matches_src():
    assert ys.node_share("e3sm_g") == node_share("e3sm_g") == (679688, 524)


@pytest.mark.parametrize("n_ranks,reqs,req_bytes", [(1, 7, 8), (4, 9, 36)])
def test_requests_and_file_match_src(n_ranks, reqs, req_bytes):
    src = e3sm_g_pattern(n_ranks, reqs_per_rank=reqs, req_bytes=req_bytes,
                         seed=3)
    offsets, lengths = ys.e3sm_g_requests(n_ranks, reqs, req_bytes)
    for r, (o, ln, _) in enumerate(src):
        np.testing.assert_array_equal(offsets[r], o)
        np.testing.assert_array_equal(lengths[r], ln)
    e = ys.ELEM_BYTES
    data = np.stack([d.view(np.int32) for _, _, d in src])
    counts = np.full((n_ranks,), reqs, np.int32)
    extent = int((offsets + lengths).max()) // e
    assert extent == n_ranks * reqs * req_bytes // e
    n_elems = ys.padded_file_elems(extent, 2, 16)
    want = write_reference(contiguous_layout(n_elems, 2), offsets // e,
                           lengths // e, counts, data)
    got = ys.write_reference(n_elems, offsets // e, lengths // e, counts,
                             data)
    assert ys.bytes_differing(got, want) == 0
    assert ys.bytes_differing(got[:-1], want) == want.nbytes


@pytest.mark.parametrize("n_ranks,merged,interleave,reqs",
                         [(1, 4, 8, 13), (1, 64, 128, 200), (2, 3, 9, 7)])
def test_merged_ranks_are_src_ranks_in_file_order(n_ranks, merged,
                                                  interleave, reqs):
    """Each rank holds ``merged`` consecutive source ranks' requests of
    ``src``'s round-robin over ``interleave`` ranks, merged in file order;
    the other ranks' slots are holes."""
    src = e3sm_g_pattern(interleave, reqs_per_rank=reqs, req_bytes=8,
                         seed=0)
    offsets, lengths = ys.e3sm_g_requests(n_ranks, reqs, 8, merged,
                                          interleave)
    for p in range(n_ranks):
        mine = np.sort(np.concatenate(
            [src[q][0] for q in range(p * merged, (p + 1) * merged)]))
        np.testing.assert_array_equal(offsets[p], mine[:reqs])
    assert (lengths == 8).all()
    written = np.zeros(interleave * reqs, bool)
    written[(offsets // 8).reshape(-1)] = True
    assert written.sum() == n_ranks * reqs


def test_node_share_keeps_noncontiguous_runs():
    reqs, req_bytes = ys.node_share("e3sm_g")
    offsets, lengths = ys.e3sm_g_requests(1, reqs, req_bytes, 64, 128)
    ends = offsets[0] + lengths[0]
    runs = 1 + np.count_nonzero(offsets[0, 1:] != ends[:-1])
    assert runs == -(-reqs // 64) == 10621
    assert int(ends[-1]) == (10620 * 128 + 8) * 524


@pytest.mark.parametrize("name", ["e3sm_g_node", "e3sm_g_2x2"])
def test_config_numbers_follow_from_table_i(name):
    c = json.loads(open(CONFIGS + name + ".json").read())
    node, lagg, lmem = c["mesh"]
    assert (c["nodes"], c["ranks_per_node"]) == (node, lagg * lmem)
    share, req_bytes = ys.node_share("e3sm_g")
    assert c["request_bytes"] == req_bytes
    n_ranks = node * lagg * lmem
    reqs = share if "max_rounds" not in c else ys.capped_requests(
        share, req_bytes, n_ranks, node, c["cb_buffer_bytes"],
        c["max_rounds"])
    assert c["rank_requests"] == reqs
    e = ys.ELEM_BYTES
    offsets, lengths = ys.e3sm_g_requests(
        n_ranks, reqs, req_bytes, c["merged_ranks"], c["interleave_ranks"])
    n_elems = ys.padded_file_elems(int((offsets + lengths).max()) // e,
                                   node, c["cb_buffer_bytes"] // e)
    assert c["file_bytes"] == n_elems * e
    assert c["payload_bytes"] == n_ranks * reqs * req_bytes
    sd = c["source_deployment"]
    assert c["merged_ranks"] * n_ranks <= c["interleave_ranks"]
    assert sd["interleave_ranks"] == sd["ranks"] == TABLE_I_RANKS


def test_hand_worked_sizes():
    # one node's share: 679,688 requests in rows of 64 of a round-robin
    # over 128 slots; the last row (10,620) holds 8, so the extent ends
    # at slot 10,620 * 128 + 8, inside window 43 of 16 MiB
    assert 679688 * 524 == 356156512
    assert (10620 * 128 + 8) * 524 == 712308832
    assert 721420288 == 43 * 16 * 2**20 > 712308832 > 42 * 16 * 2**20
    # 2x2: 8 windows per node
    assert 4 * 128070 * 524 == 268434720
    assert 268435456 == 2 * 8 * 16 * 2**20
