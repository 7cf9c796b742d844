"""The least time the chip could take for one collective call.

The bytes are worked out from the cell's request lists and file layout,
never from the program's capacities or padded buffers, so the share
reads the same work whatever implements the write or the read:

* HBM bytes: payload bytes read, file bytes written (the file as the
  layout lays it out) and the requests' offsets and lengths, as int32
  pairs. A read moves the same bytes the other way.
* Crossing bytes (several chips): payload bytes whose file domain lies
  on another node. A node's contiguous domain is held by that node's
  chips, and rank ``p`` sits on node ``p // ranks_per_node`` (the mesh's
  ``(node, lagg, lmem)`` order).

The least time is the larger of the HBM bytes per chip over the peak
HBM rate and the crossing bytes per chip over the peak ICI rate.
"""
from __future__ import annotations

import numpy as np

META_BYTES = 8   # one int32 offset and one int32 length per request


def io_bytes(offsets, lengths, counts, file_elems: int, n_nodes: int,
             elem_bytes: int = 4) -> dict:
    """Bytes one collective write (or read) of these requests needs.

    ``offsets``/``lengths`` are ``[ranks, cap]`` in elements, ``counts``
    the live requests per rank; the file of ``file_elems`` elements is
    cut into ``n_nodes`` contiguous domains."""
    offsets = np.asarray(offsets, np.int64)
    lengths = np.asarray(lengths, np.int64)
    counts = np.asarray(counts, np.int64)
    n_ranks = offsets.shape[0]
    live = np.arange(offsets.shape[1])[None, :] < counts[:, None]
    lengths = np.where(live, lengths, 0)
    domain = -(-file_elems // n_nodes)
    node = (np.arange(n_ranks) // (n_ranks // n_nodes))[:, None]
    lo, hi = node * domain, (node + 1) * domain
    own = np.clip(np.minimum(offsets + lengths, hi)
                  - np.maximum(offsets, lo), 0, None)
    payload = int(lengths.sum()) * elem_bytes
    return {"payload": payload,
            "file": int(file_elems) * elem_bytes,
            "meta": int(counts.sum()) * META_BYTES,
            "crossing": int((lengths - own).sum()) * elem_bytes}


def least_time(work: dict, n_chips: int, peaks: dict) -> tuple[float, str]:
    """``(seconds, bound)``: the least time for ``work`` (from
    :func:`io_bytes`) on ``n_chips`` chips, and which peak bounds it."""
    hbm = (work["payload"] + work["file"] + work["meta"]) / n_chips
    t_hbm = hbm / peaks["hbm_bytes_per_s"]
    t_ici = work["crossing"] / n_chips / peaks["ici_bytes_per_s"]
    return (t_ici, "ici") if t_ici > t_hbm else (t_hbm, "hbm")
