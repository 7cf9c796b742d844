"""The benchmark's own copies of what decides sizes and correctness.

Kept here, apart from ``src/``, so that a change to the program cannot
move the yardstick:

* the paper's Table I figures for E3SM-G and the arithmetic that turns
  them into one node's share (a copy of ``benchmarks.workloads
  .node_share`` over ``repro.core.cost_model.e3sm_g``);
* the request structure of the E3SM-G pattern (the offsets and lengths
  of ``repro.io_patterns.e3sm_g_pattern``, with a node's ranks merged
  into one; the payload is made on the device from the run's seed
  instead);
* the plain reference of a collective write (a copy of
  ``repro.core.twophase.write_reference``), and the byte comparison.

``tests/test_bench_yardstick.py`` checks each copy against ``src/`` at a tiny
size. Nothing here imports the program.
"""
from __future__ import annotations

import numpy as np

# Table I of arXiv:1907.12656: the E3SM G case writes 85 GiB in
# 1.74e8 noncontiguous requests from 16,384 ranks on 256 nodes.
TABLE_I = {
    "e3sm_g": {"ranks": 16384, "nodes": 256, "requests": 1.74e8,
               "total_bytes": 85 * 2**30},
}

ELEM_BYTES = 4   # the engine moves int32 elements


def node_share(name: str) -> tuple[int, int]:
    """One node's share of a Table I case: ``(requests, bytes per
    request)``, the mean request size rounded down to whole elements
    (E3SM-G: 679,688 requests of 524 B)."""
    t = TABLE_I[name]
    req_bytes = int(t["total_bytes"] / t["requests"]) // ELEM_BYTES * ELEM_BYTES
    return round(t["requests"] / t["nodes"]), req_bytes


def capped_requests(requests: int, request_bytes: int, n_ranks: int,
                    n_nodes: int, cb_bytes: int, max_rounds: int) -> int:
    """Requests per rank when every rank's share is cut to
    ``max_rounds`` windows of ``cb_bytes`` per node."""
    unit = n_nodes * (cb_bytes // ELEM_BYTES)
    return min(requests, max_rounds * unit
               // (n_ranks * (request_bytes // ELEM_BYTES)))


def e3sm_g_requests(n_ranks: int, requests: int, request_bytes: int,
                    merged_ranks: int = 1, interleave_ranks: int = 0):
    """Byte offsets and lengths ``[n_ranks, requests]`` (int64) of the
    E3SM-G pattern: small requests interleaved round-robin over
    ``interleave_ranks`` source ranks (default ``n_ranks *
    merged_ranks``), source rank ``q`` owning slots ``q, q + Q,
    q + 2Q, ...``. Rank ``p`` here holds the requests of the
    ``merged_ranks`` source ranks ``p * merged_ranks ...`` merged in file
    order, as a node's aggregator holds them; the slots of source ranks
    that no rank here holds are holes in the file."""
    q = interleave_ranks or n_ranks * merged_ranks
    assert n_ranks * merged_ranks <= q
    row, k = np.divmod(np.arange(requests, dtype=np.int64), merged_ranks)
    offsets = np.stack([(row * q + p * merged_ranks + k) * request_bytes
                        for p in range(n_ranks)])
    lengths = np.full((n_ranks, requests), request_bytes, np.int64)
    return offsets, lengths


def padded_file_elems(extent: int, n_nodes: int, cb_elems: int) -> int:
    """File length in elements: the requests' extent (the end of the
    last request) padded to whole ``cb`` windows in every node's
    contiguous domain."""
    unit = n_nodes * cb_elems
    return -(-int(extent) // unit) * unit


def write_reference(file_len: int, offsets, lengths, counts, data):
    """Host-side oracle: scatter every rank's payload into a dense file."""
    file = np.zeros((file_len,), dtype=np.asarray(data).dtype)
    offsets, lengths = np.asarray(offsets), np.asarray(lengths)
    counts, data = np.asarray(counts), np.asarray(data)
    for p in range(offsets.shape[0]):
        pos = 0
        for i in range(counts[p]):
            o, l = int(offsets[p, i]), int(lengths[p, i])
            file[o:o + l] = data[p, pos:pos + l]
            pos += l
    return file


def bytes_differing(got, want) -> int:
    """Bytes in which ``got`` differs from ``want``; every byte counts
    as differing when the two differ in size."""
    got = np.ascontiguousarray(got).reshape(-1).view(np.uint8)
    want = np.ascontiguousarray(want).reshape(-1).view(np.uint8)
    if got.shape != want.shape:
        return max(got.size, want.size)
    return int(np.count_nonzero(got != want))
