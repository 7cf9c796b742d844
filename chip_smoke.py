"""Smoke run of the collective-I/O engine's main path on a TPU.

    python chip_smoke.py              # one chip: phases 1 and 2
    python chip_smoke.py --chips 4    # v5e 2x2: phase 1 on the (2,1,2) mesh

Phase 1 is the collective write and read of one node's share of the
paper's E3SM-G case (85 GiB in 1.74e8 requests over 256 nodes, so
679,688 requests of 524 B, ~340 MiB per rank), built with
``HOST_PATTERNS["e3sm_g"]`` from a fixed seed, moved in rounds of ROMIO's
default 16 MiB ``cb_buffer_size``. It writes with two-phase and with TAM,
serial and at pipeline depth 2, checks every file byte for byte against
``twophase.write_reference`` and every drop counter at zero, and reads
the file back with the planned read. Phase 2 saves a >= 1 GiB
device-resident tree with ``CheckpointManager.save_async``, restores it
onto the chip and compares every leaf bitwise.

Every line but the last is one JSON record of a step: sizes, whether the
bytes matched, drop counters, compile seconds and run seconds. The run
seconds are smoke timings of one call, not benchmark numbers. The last
line is ``{"ok": true, "device": {...}}`` and is printed only when every
check passed. Without a TPU the script exits nonzero before any work;
``--size tiny`` (for tests and CPU rehearsals only) lifts that check and
shrinks every size.

One process owns the chip(s); nothing here starts a child process. The
compile cache follows ``repro.launch.cache``.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from benchmarks.workloads import HOST_PATTERNS, node_share  # noqa: E402
from repro.checkpoint.checkpoint import CheckpointManager  # noqa: E402
from repro.checkpoint.host_io import HostCollectiveIO  # noqa: E402
from repro.core.domains import contiguous_layout  # noqa: E402
from repro.core.plan import IOConfig  # noqa: E402
from repro.core.requests import ELEM_BYTES  # noqa: E402
from repro.core.tam import make_tam_write  # noqa: E402
from repro.core.twophase import (make_twophase_read,  # noqa: E402
                                 make_twophase_write, write_reference)
from repro.launch.cache import enable_compilation_cache  # noqa: E402
from repro.launch.mesh import make_io_mesh  # noqa: E402

SEED = 0
CB_BYTES = 16 << 20           # ROMIO's default cb_buffer_size
MESHES = {1: (1, 1, 1), 4: (2, 1, 2)}   # (node, lagg, lmem); P_L = nodes
RANK_AXES = ("node", "lagg", "lmem")

# phase-2 tree: (shape, dtype) per leaf; 1 GiB of f32 + bf16 plus small
# leaves of mixed dtypes
CKPT_TREE = {
    "params": {"w_f32": ((8192, 16384), jnp.float32),
               "w_bf16": ((8192, 16384), jnp.bfloat16),
               "emb_bf16": ((4096, 16384), jnp.bfloat16),
               "head_f32": ((2048, 16384), jnp.float32)},
    "opt": {"step": ((), jnp.int32),
            "scale": ((7,), jnp.float32),
            "bias": ((3, 5), jnp.bfloat16)},
}
CKPT_TREE_TINY = {
    "params": {"w_f32": ((64, 128), jnp.float32),
               "w_bf16": ((64, 128), jnp.bfloat16)},
    "opt": {"step": ((), jnp.int32),
            "scale": ((7,), jnp.float32),
            "bias": ((3, 5), jnp.bfloat16)},
}

def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def check(failures: list[str], name: str, ok: bool) -> bool:
    """``ok``, with ``name`` added to ``failures`` when it is false."""
    if not ok:
        failures.append(name)
    return ok


# --------------------------------------------------------------------------
# phase 1: collective write + read
# --------------------------------------------------------------------------

def phase1_sizes(n_ranks: int, n_nodes: int, tiny: bool = False,
                 max_rounds: int | None = None):
    """``(requests per rank, elements per request, cb elements, layout)``
    of the phase-1 file: every rank holds one node's E3SM-G share, and the
    file is padded to whole ``cb`` windows per aggregator domain.
    ``max_rounds`` cuts the share to that many rounds per node (the
    ``cb``-sized window shapes stay those of the full share)."""
    if tiny:
        reqs, req_bytes, cb = 512, 32, 1024
    else:
        reqs, req_bytes = node_share("e3sm_g")
        cb = CB_BYTES // ELEM_BYTES
    req_elems = req_bytes // ELEM_BYTES
    unit = n_nodes * cb
    if max_rounds is not None:
        reqs = min(reqs, max_rounds * unit // (n_ranks * req_elems))
    extent = n_ranks * reqs * req_elems
    return reqs, req_elems, cb, contiguous_layout(-(-extent // unit) * unit,
                                                  n_nodes)


def io_config(reqs: int, req_elems: int, cb: int, depth: int) -> IOConfig:
    return IOConfig(req_cap=reqs, data_cap=reqs * req_elems,
                    cb_buffer_size=cb, pipeline=depth > 1,
                    pipeline_depth=depth)


def e3sm_g_arrays(n_ranks: int, reqs: int, req_elems: int):
    """The executor's ``(offsets, lengths, count, data)`` in int32
    elements, built from the byte-space E3SM-G pattern."""
    ranks = HOST_PATTERNS["e3sm_g"](n_ranks, reqs_per_rank=reqs,
                                    req_bytes=req_elems * ELEM_BYTES,
                                    seed=SEED)
    offsets = np.stack([o // ELEM_BYTES for o, _, _ in ranks]).astype(np.int32)
    lengths = np.stack([ln // ELEM_BYTES for _, ln, _ in ranks]).astype(np.int32)
    count = np.full((n_ranks,), reqs, np.int32)
    data = np.stack([d.view(np.int32) for _, _, d in ranks])
    return offsets, lengths, count, data


def compile_and_run(fn, args):
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    t1 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    t2 = time.perf_counter()
    return out, t1 - t0, t2 - t1


def phase1(mesh, tiny: bool, failures: list[str]) -> None:
    n_ranks, n_nodes = mesh.size, mesh.shape["node"]
    reqs, req_elems, cb, layout = phase1_sizes(n_ranks, n_nodes, tiny)
    host = e3sm_g_arrays(n_ranks, reqs, req_elems)
    rank_sharding = NamedSharding(mesh, P(RANK_AXES))
    dev = tuple(jax.device_put(a, rank_sharding) for a in host)
    shard_devices = {s.device for s in dev[3].addressable_shards}
    sizes = {"mesh": dict(mesh.shape), "ranks": n_ranks,
             "requests_per_rank": reqs,
             "bytes_per_request": req_elems * ELEM_BYTES,
             "payload_bytes_per_rank": int(host[3][0].nbytes),
             "file_bytes": layout.file_len * ELEM_BYTES,
             "cb_bytes": cb * ELEM_BYTES,
             "rounds": layout.file_len // n_nodes // cb,
             "input_shard_devices": len(shard_devices)}
    check(failures, "input shards on their own devices",
          len(shard_devices) == n_ranks
          and all(s.data.shape == (1,) + host[3].shape[1:]
                  for s in dev[3].addressable_shards))
    t0 = time.perf_counter()
    ref = write_reference(layout, *host)
    emit({"phase": "1/setup", **sizes,
          "write_reference_seconds": time.perf_counter() - t0})

    file_dev = None
    for method, make in (("twophase", make_twophase_write),
                         ("tam", make_tam_write)):
        for depth in (1, 2):
            cfg = io_config(reqs, req_elems, cb, depth)
            (f, stats), c_s, r_s = compile_and_run(
                make(mesh, layout, cfg), dev)
            got = np.asarray(f).reshape(-1)
            name = f"write {method} depth {depth}"
            match = check(failures, f"{name} bytes",
                          np.array_equal(got.view(np.uint8),
                                         ref.view(np.uint8)))
            drops = {k: int(stats[k])
                     for k in ("dropped_requests", "dropped_elems")}
            check(failures, f"{name} drops",
                  drops == {"dropped_requests": 0, "dropped_elems": 0})
            shard_shapes = {s.data.shape for s in f.addressable_shards}
            check(failures, f"{name} file sharded by node",
                  shard_shapes == {(1, layout.file_len // n_nodes)})
            rec = {"phase": "1/write", "method": method, "depth": depth,
                   "bytes_match_write_reference": match, **drops,
                   "compile_seconds": c_s, "run_seconds_smoke": r_s}
            if method == "tam":
                rec["requests_before_coalesce"] = int(
                    stats["requests_before_coalesce"])
                rec["requests_after_coalesce"] = int(
                    stats["requests_after_coalesce"])
            emit(rec)
            if method == "twophase" and depth == 1:
                file_dev = f
            else:
                del f

    for depth in (1, 2):
        cfg = io_config(reqs, req_elems, cb, depth)
        out, c_s, r_s = compile_and_run(make_twophase_read(mesh, layout, cfg),
                                        dev[:3] + (file_dev,))
        match = check(failures, f"read depth {depth} payloads",
                      np.array_equal(np.asarray(out), host[3]))
        emit({"phase": "1/read", "depth": depth,
              "payloads_match": match, "compile_seconds": c_s,
              "run_seconds_smoke": r_s})


# --------------------------------------------------------------------------
# phase 2: checkpoint cycle of a device-resident tree
# --------------------------------------------------------------------------

def _bits(x):
    x = jnp.asarray(x)
    return jax.lax.bitcast_convert_type(
        x, {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize])


def make_tree(spec, device):
    leaves, treedef = jax.tree_util.tree_flatten(
        spec, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[0], tuple))
    keys = jax.random.split(jax.random.PRNGKey(SEED), len(leaves))
    out = []
    for k, (shape, dtype) in zip(keys, leaves):
        if jnp.issubdtype(dtype, jnp.integer):
            x = jax.random.randint(k, shape, 0, 1 << 30, dtype)
        else:
            x = jax.random.normal(k, shape, jnp.float32).astype(dtype)
        out.append(jax.device_put(x, device))
    return jax.tree_util.tree_unflatten(treedef, out)


def phase2(device, tiny: bool, failures: list[str]) -> None:
    tree = jax.block_until_ready(
        make_tree(CKPT_TREE_TINY if tiny else CKPT_TREE, device))
    leaves = jax.tree_util.tree_leaves(tree)
    total = sum(x.nbytes for x in leaves)
    directory = ROOT / ".chip_smoke" / "ckpt"
    shutil.rmtree(directory, ignore_errors=True)
    io = HostCollectiveIO(n_ranks=8, n_nodes=2, stripe_size=1 << 20,
                          stripe_count=4)
    mgr = CheckpointManager(directory, io, method="tam")
    try:
        t0 = time.perf_counter()
        pending = mgr.save_async(tree, 1)
        t1 = time.perf_counter()
        manifest, timings = pending.result()
        t2 = time.perf_counter()
        shardings = jax.tree.map(lambda x: x.sharding, tree)
        restored, step = mgr.restore(tree, 1, shardings=shardings)
        restored = jax.block_until_ready(restored)
        t3 = time.perf_counter()
    finally:
        shutil.rmtree(directory.parent, ignore_errors=True)
    got = jax.tree_util.tree_leaves(restored)
    same = [bool(jnp.array_equal(_bits(a), _bits(b)))
            and a.shape == b.shape and a.dtype == b.dtype
            for a, b in zip(leaves, got)]
    placed = [b.sharding == a.sharding for a, b in zip(leaves, got)]
    check(failures, "checkpoint step", step == 1)
    check(failures, "checkpoint leaves bitwise",
          len(got) == len(leaves) and all(same))
    check(failures, "checkpoint leaves on the chip", all(placed))
    emit({"phase": "2/checkpoint", "tree_bytes": total,
          "leaves": len(leaves),
          "dtypes": sorted({str(x.dtype) for x in leaves}),
          "file_bytes": manifest["file_len"],
          "leaves_bitwise_equal": sum(same),
          "leaves_on_device": sum(placed),
          "snapshot_seconds_smoke": t1 - t0,
          "save_wait_seconds_smoke": t2 - t1,
          "restore_seconds_smoke": t3 - t2})


# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=sorted(MESHES), default=1,
                    help="4: run phase 1 alone on a v5e 2x2 (2,1,2) mesh")
    ap.add_argument("--size", choices=("full", "tiny"), default="full",
                    help="tiny: tests and CPU rehearsals only")
    args = ap.parse_args(argv)
    tiny = args.size == "tiny"

    devices = jax.devices()
    if devices[0].platform != "tpu" and not tiny:
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2

    cache_dir = enable_compilation_cache(ROOT)
    cache = {"hits": 0, "misses": 0}

    def on_event(event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    jax.monitoring.register_event_listener(on_event)

    failures: list[str] = []
    mesh = make_io_mesh(*MESHES[args.chips], devices=devices[:args.chips])
    phase1(mesh, tiny, failures)
    if args.chips == 1:
        phase2(devices[0], tiny, failures)
    emit({"phase": "compile_cache", "dir": cache_dir, **cache})

    if failures:
        print("chip_smoke: FAILED: " + "; ".join(failures), file=sys.stderr)
        return 1
    emit({"ok": True, "device": {"platform": devices[0].platform,
                                 "kind": devices[0].device_kind,
                                 "count": len(devices)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
