import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

"""Round-engine SPMD checks (run as a subprocess with 8 host devices).

Property: for round counts {1, 2, 5} (cb_buffer_size in {160, 80, 32}
on a 160-element domain) and mixed / strided / overlapping / spanning
request patterns, the multi-round two-phase and TAM collective writes
are byte-identical to BOTH the single-shot path and the
``write_reference`` oracle, with identical (zero) drop stats; the
PIPELINED round loop (``IOConfig.pipeline``, prologue → steady state →
epilogue) is byte-identical to the serial round loop and the oracle at
every round count AND at every ring depth — the depth-k window ring
(``IOConfig.pipeline_depth``) is swept over k in {3, 4} x all three
round counts for two-phase and at the 5-round cb for TAM (k in {1, 2}
are the serial/pipelined rows above; depth clamps to the round count,
so the 1-round sweep also exercises the clamp); the round-scheduled
reads (serial, pipelined, and depth-k) return every rank's payload;
and a deliberately overflowed round bucket reports nonzero
``dropped_elems`` instead of failing silently. The spanning pattern
crosses the file-domain boundary, exercising the split-at-domain
handling (those requests were silently truncated before PR 2).

Slow-hop codec: with ``slow_hop_codec="rle"`` (the lossless zero-run
wire transform wrapped around the slow-axis ``all_to_all`` inside the
round engine) the SAME byte-identity must hold — swept over ring
depths {1, 2, 4} x round counts {1, 2, 5} for two-phase, at the
5-round cb for TAM, plus an rle read — because a lossless codec may
change the wire, never the file. Exits nonzero on any failure.

Placement: an aggregator placement is a pure permutation of which slot
serves which domain (``core.placement``), so byte identity must hold
under it too: the handcrafted patterns run the two-phase and TAM
writers (and a read) with the swapped placement ``(1, 0)`` at the
5-round cb, and the FUZZ section below sweeps it properly.

Cross-executor fuzz: seeded random patterns (disjoint random extents
with offset-derived payloads, occasional deterministic identical-data
overlaps, and natural domain-/window-boundary spanners) are run
through BOTH executors — the SPMD writers under placement {identity,
swapped} x codec {None, rle} x depth {1, 2}, and the host executor
(byte units, same striping) under placement {off, spread, swapped} x
codec {None, rle} x depth {1, 2} — and every single run must
reproduce the ``write_reference`` oracle bytes exactly, so the two
backends are compared on inputs nobody hand-picked.

Transport (PR 10): seed 0's pattern additionally runs through the MP
transport executor (``checkpoint.mp_exec`` — real worker processes,
shared-memory fast hop, localhost-socket slow hop) under placement
{off, swapped} x codec {None, rle} x depth {1, 2} for two-phase
writes, a combined-frame TAM write, and node-cache on/off reads — all
byte-identical to the same oracle, so all THREE byte movers agree on
inputs nobody hand-picked.

Read direction (PR 8): the planner no longer nulls ``kernel_fusion``
for reads, so every (codec x depth) reader also runs FUSED
(``zero_skip_decode`` replacing the rle decode scatter inside the read
ring) against its unfused twin — byte-identical, and identical to the
requested payloads. On the host side, every fuzz pattern's files are
read BACK through the planned collective read
(``HostCollectiveIO.read``: ``compile_plan(direction="read")``, the
node-level window cache) across placement x codec x depth x cache
on/off — per-rank payloads must equal the write oracle's byte spans,
the cache must never model slower than the per-rank fetch baseline,
and both modes must account the same delivery count.

Kernel fusion: every SPMD fuzz configuration runs a second time with
``IOConfig.kernel_fusion="fused_round"`` (the planner's
``lower_kernels`` pass selects the single-``pallas_call`` sort +
dual-pack drain of ``kernels/fused_round.py``, plus the fused rle
zero-skip encode when the codec is on) — the fused writes must be
byte-identical to BOTH the unfused writes and the oracle across the
whole placement x codec x depth cross, on both schedules. The host
executor accepts the same unified config (``write(config=...)``) and
ignores the fusion (numpy backend) — its bytes must match the oracle
too, closing the both-executors contract.
"""
import numpy as np
import jax
import jax.numpy as jnp

from dataclasses import replace

FAILURES = []

P_RANKS, REQ_CAP, DATA_CAP, FILE_LEN = 8, 8, 64, 320
CBS = (160, 80, 32)   # domain_len=160 -> 1, 2, 5 rounds
DEPTHS = (3, 4)       # ring depths beyond the serial/pipelined rows


def check(name, ok):
    print(("PASS " if ok else "FAIL ") + name, flush=True)
    if not ok:
        FAILURES.append(name)


def mixed_pattern(rng):
    """Random disjoint extents, random lengths, shuffled ownership."""
    O = np.full((P_RANKS, REQ_CAP), 2**31 - 1, np.int32)
    L = np.zeros((P_RANKS, REQ_CAP), np.int32)
    C = np.zeros(P_RANKS, np.int32)
    D = np.zeros((P_RANKS, DATA_CAP), np.int32)
    slots = rng.permutation(FILE_LEN // 8)
    spr = len(slots) // P_RANKS
    for p in range(P_RANKS):
        mine = np.sort(slots[p * spr:(p + 1) * spr])[:6]
        lens = rng.integers(1, 9, size=len(mine)).astype(np.int32)
        O[p, :len(mine)], L[p, :len(lens)] = (mine * 8).astype(np.int32), lens
        C[p] = len(mine)
        D[p, :lens.sum()] = rng.integers(1, 999, size=lens.sum())
    return O, L, C, D


def strided_pattern(rng):
    """E3SM-style round-robin interleave: rank r owns slots r, r+P, ..."""
    O = np.full((P_RANKS, REQ_CAP), 2**31 - 1, np.int32)
    L = np.zeros((P_RANKS, REQ_CAP), np.int32)
    C = np.full(P_RANKS, REQ_CAP, np.int32)
    D = np.zeros((P_RANKS, DATA_CAP), np.int32)
    unit = FILE_LEN // (P_RANKS * REQ_CAP)  # 5 elements per request
    for p in range(P_RANKS):
        idx = np.arange(REQ_CAP, dtype=np.int32)
        O[p] = (idx * P_RANKS + p) * unit
        L[p] = unit
        D[p, :REQ_CAP * unit] = O[p].repeat(unit) % 97 + 1
    return O, L, C, D


def overlapping_pattern(rng):
    """Ranks 0 and 1 write IDENTICAL data to the same two regions (the
    only deterministic overlap; MPI leaves diverging overlaps
    undefined); ranks 2..7 write disjoint extents elsewhere. The spans
    are sized so TAM's duplicated stage-1 payload (2 x span at one
    local aggregator) still fits the smallest round bucket."""
    O = np.full((P_RANKS, REQ_CAP), 2**31 - 1, np.int32)
    L = np.zeros((P_RANKS, REQ_CAP), np.int32)
    C = np.zeros(P_RANKS, np.int32)
    D = np.zeros((P_RANKS, DATA_CAP), np.int32)
    span, regions = 12, (8, 280)
    for p in (0, 1):
        for i, o in enumerate(regions):
            O[p, i], L[p, i] = o, span
            D[p, i * span:(i + 1) * span] = np.arange(o, o + span) % 97 + 1
        C[p] = 2
    for p in range(2, P_RANKS):
        # disjoint extents clear of both regions and the domain boundary
        o = 40 + (p - 2) * 24 if p <= 4 else 170 + (p - 5) * 24
        O[p, 0], L[p, 0], C[p] = o, 20, 1
        D[p, :20] = rng.integers(1, 999, size=20)
    return O, L, C, D


def spanning_pattern(rng):
    """Requests crossing the file-domain boundary at 160 (and window
    boundaries): both paths must split them — the single-shot exchange
    truncated the spanning tail silently before the domain-split fix."""
    O = np.full((P_RANKS, REQ_CAP), 2**31 - 1, np.int32)
    L = np.zeros((P_RANKS, REQ_CAP), np.int32)
    C = np.zeros(P_RANKS, np.int32)
    D = np.zeros((P_RANKS, DATA_CAP), np.int32)
    # rank 0 straddles the domain boundary: [150, 174)
    O[0, 0], L[0, 0], C[0] = 150, 24, 1
    D[0, :24] = np.arange(150, 174) % 97 + 1
    # rank 1 straddles a cb=32 window boundary inside domain 1:
    # [250, 262) is domain-local [90, 102), crossing 96
    O[1, 0], L[1, 0], C[1] = 250, 12, 1
    D[1, :12] = np.arange(250, 262) % 97 + 1
    for p in range(2, P_RANKS):
        o = 8 + (p - 2) * 16
        O[p, 0], L[p, 0], C[p] = o, 12, 1
        D[p, :12] = rng.integers(1, 999, size=12)
    return O, L, C, D


def _fill_sorted(O, L, C, D, p, segs):
    """Install rank p's segments sorted by offset, payload derived from
    the absolute offset (so any overlap is identical-data, the only
    deterministic kind)."""
    segs = sorted(segs)
    pos = 0
    for i, (o, ln) in enumerate(segs):
        O[p, i], L[p, i] = o, ln
        D[p, pos:pos + ln] = (np.arange(o, o + ln) * 7 + 3) % 251 + 1
        pos += ln
    C[p] = len(segs)


def random_pattern(rng):
    """Seeded random request pattern: the file is cut at random points
    and the pieces are dealt to random ranks (bounded by the caps),
    with offset-derived payloads; ~1 in 4 patterns duplicates one
    piece onto a second rank (identical bytes — the deterministic
    overlap), and pieces freely straddle domain and window boundaries
    (the spanning case)."""
    O = np.full((P_RANKS, REQ_CAP), 2**31 - 1, np.int32)
    L = np.zeros((P_RANKS, REQ_CAP), np.int32)
    C = np.zeros(P_RANKS, np.int32)
    D = np.zeros((P_RANKS, DATA_CAP), np.int32)
    cuts = np.unique(rng.integers(1, FILE_LEN, size=rng.integers(8, 28)))
    bounds = np.concatenate([[0], cuts, [FILE_LEN]])
    per_rank: list[list] = [[] for _ in range(P_RANKS)]
    budget = np.zeros(P_RANKS, np.int64)
    dup = rng.random() < 0.25
    for a, b in zip(bounds[:-1], bounds[1:]):
        ln = min(int(b - a), int(rng.integers(1, 17)))
        if rng.random() < 0.3:
            continue                      # leave a hole
        targets = [int(rng.integers(0, P_RANKS))]
        if dup and rng.random() < 0.2:
            targets.append(int(rng.integers(0, P_RANKS)))
        for p in set(targets):
            if len(per_rank[p]) >= 6 or budget[p] + ln > DATA_CAP - 8:
                continue
            per_rank[p].append((int(a), ln))
            budget[p] += ln
    for p in range(P_RANKS):
        _fill_sorted(O, L, C, D, p, per_rank[p])
    return O, L, C, D


def _byte_requests(O, L, C, D):
    """The same pattern in the host executor's units: byte offsets and
    the int32 payloads' little-endian bytes."""
    reqs = []
    for p in range(P_RANKS):
        n = int(C[p])
        o = O[p, :n].astype(np.int64) * 4
        ln = L[p, :n].astype(np.int64) * 4
        total = int(L[p, :n].sum())
        payload = D[p, :total].astype("<i4").view(np.uint8).copy()
        reqs.append((o, ln, payload))
    return reqs


def main():
    from repro.core import IOConfig, contiguous_layout
    from repro.core.tam import make_tam_read, make_tam_write
    from repro.core.twophase import (make_twophase_read,
                                     make_twophase_write, write_reference)
    from repro.checkpoint.host_io import HostCollectiveIO
    from repro.launch.mesh import make_io_mesh

    mesh = make_io_mesh(2, 2, 2)
    layout = contiguous_layout(FILE_LEN, 2)
    base = IOConfig(req_cap=32, data_cap=DATA_CAP, coalesce_cap=32)

    writers = {None: (jax.jit(make_twophase_write(mesh, layout, base)),
                      jax.jit(make_tam_write(mesh, layout, base)))}
    pipelined = {}
    readers = {}
    readers_p = {}
    for cb in CBS:
        cfg = replace(base, cb_buffer_size=cb)
        cfgp = replace(base, cb_buffer_size=cb, pipeline=True)
        writers[cb] = (jax.jit(make_twophase_write(mesh, layout, cfg)),
                       jax.jit(make_tam_write(mesh, layout, cfg)))
        pipelined[cb] = (jax.jit(make_twophase_write(mesh, layout, cfgp)),
                         jax.jit(make_tam_write(mesh, layout, cfgp)))
        readers[cb] = (jax.jit(make_twophase_read(mesh, layout, cfg)),
                       jax.jit(make_tam_read(mesh, layout, cfg)))
    # pipelined reads: 5-round config exercises prologue + steady state
    # + epilogue (1-round = prologue/epilogue only, covered by writes)
    cfgp32 = replace(base, cb_buffer_size=32, pipeline=True)
    readers_p[32] = (jax.jit(make_twophase_read(mesh, layout, cfgp32)),
                     jax.jit(make_tam_read(mesh, layout, cfgp32)))
    # depth-k ring sweep: two-phase at every round count (the 1-round
    # config exercises the depth clamp), TAM at the 5-round cb, and a
    # depth-k read; byte-identity is checked on the mixed + spanning
    # patterns (the other patterns cover k in {1, 2} above)
    deep = {}
    for cb in CBS:
        for k in DEPTHS:
            cfgk = replace(base, cb_buffer_size=cb, pipeline=True,
                           pipeline_depth=k)
            deep[("twophase", cb, k)] = jax.jit(
                make_twophase_write(mesh, layout, cfgk))
    for k in DEPTHS:
        cfgk = replace(base, cb_buffer_size=32, pipeline=True,
                       pipeline_depth=k)
        deep[("tam", 32, k)] = jax.jit(make_tam_write(mesh, layout, cfgk))
    readers_k = {k: jax.jit(make_twophase_read(
        mesh, layout, replace(base, cb_buffer_size=32, pipeline=True,
                              pipeline_depth=k))) for k in DEPTHS}
    # slow-hop codec sweep: rle across depths {1, 2, 4} x all three
    # round counts for two-phase, TAM at the 5-round cb, one rle read
    CODEC_DEPTHS = (1, 2, 4)
    coded = {}
    for cb in CBS:
        for k in CODEC_DEPTHS:
            cfgc = replace(base, cb_buffer_size=cb, pipeline=k > 1,
                           pipeline_depth=k, slow_hop_codec="rle")
            coded[("twophase", cb, k)] = jax.jit(
                make_twophase_write(mesh, layout, cfgc))
    for k in CODEC_DEPTHS:
        cfgc = replace(base, cb_buffer_size=32, pipeline=k > 1,
                       pipeline_depth=k, slow_hop_codec="rle")
        coded[("tam", 32, k)] = jax.jit(make_tam_write(mesh, layout, cfgc))
    reader_rle = jax.jit(make_twophase_read(
        mesh, layout, replace(base, cb_buffer_size=32, pipeline=True,
                              pipeline_depth=2, slow_hop_codec="rle")))
    # placement: the swapped permutation at the 5-round cb for both
    # schedules plus a placement read — byte identity must hold because
    # a placement only moves WHERE the aggregation runs (the shards
    # ppermute back into domain order)
    SWAP = (1, 0)
    placed = {
        "twophase": jax.jit(make_twophase_write(mesh, layout, replace(
            base, cb_buffer_size=32, placement=SWAP))),
        "tam": jax.jit(make_tam_write(mesh, layout, replace(
            base, cb_buffer_size=32, placement=SWAP))),
    }
    reader_placed = jax.jit(make_twophase_read(mesh, layout, replace(
        base, cb_buffer_size=32, placement=SWAP)))
    # fused READ rows (PR 8): since lower_kernels stopped nulling the
    # fusion for reads, kernel_fusion="fused_round" swaps the rle
    # decode scatter for kernels/fused_round.zero_skip_decode inside
    # the read ring — every (codec x depth) pair runs fused and
    # unfused under the swapped placement and must agree byte-for-byte
    # with each other and with the requested payloads
    read_pairs = {}
    for codec in (None, "rle"):
        for k in (1, 2):
            for fused in (False, True):
                cfgr = replace(base, cb_buffer_size=32, pipeline=k > 1,
                               pipeline_depth=k, slow_hop_codec=codec,
                               placement=SWAP,
                               kernel_fusion=("fused_round" if fused
                                              else None))
                read_pairs[(codec, k, fused)] = jax.jit(
                    make_twophase_read(mesh, layout, cfgr))
    # cross-executor fuzz writers: placement x codec x depth (two-phase
    # full cross, TAM corners to bound compile time)
    fuzz_fns = {}
    for pl in (None, SWAP):
        for codec in (None, "rle"):
            for k in (1, 2):
                cfgf = replace(base, cb_buffer_size=32, pipeline=k > 1,
                               pipeline_depth=k, slow_hop_codec=codec,
                               placement=pl)
                fuzz_fns[("twophase", pl is not None, codec, k)] = \
                    jax.jit(make_twophase_write(mesh, layout, cfgf))
    for codec, k in ((None, 1), ("rle", 2)):
        cfgf = replace(base, cb_buffer_size=32, pipeline=k > 1,
                       pipeline_depth=k, slow_hop_codec=codec,
                       placement=SWAP)
        fuzz_fns[("tam", True, codec, k)] = jax.jit(
            make_tam_write(mesh, layout, cfgf))
    # the SAME cross with the fused round kernel selected — every fuzz
    # run is also a fused-vs-unfused byte-identity check
    fused_fns = {}
    for (mname, swapped, codec, k) in fuzz_fns:
        cfgf = replace(base, cb_buffer_size=32, pipeline=k > 1,
                       pipeline_depth=k, slow_hop_codec=codec,
                       placement=SWAP if swapped else None,
                       kernel_fusion="fused_round")
        mk = make_twophase_write if mname == "twophase" else make_tam_write
        fused_fns[(mname, swapped, codec, k)] = jax.jit(
            mk(mesh, layout, cfgf))

    rng = np.random.default_rng(0)
    patterns = {"mixed": mixed_pattern(rng),
                "strided": strided_pattern(rng),
                "overlapping": overlapping_pattern(rng),
                "spanning": spanning_pattern(rng)}

    for pname, (O, L, C, D) in patterns.items():
        ref = write_reference(layout, O, L, C, D)
        singles = {}
        for mi, mname in ((0, "twophase"), (1, "tam")):
            f, s = writers[None][mi](O, L, C, D)
            singles[mname] = np.asarray(f).reshape(-1)
            check(f"{pname}/{mname}/single_shot_vs_ref",
                  np.array_equal(singles[mname], ref))
        for cb in CBS:
            n_rounds = 160 // cb
            for mi, mname in ((0, "twophase"), (1, "tam")):
                f, s = writers[cb][mi](O, L, C, D)
                got = np.asarray(f).reshape(-1)
                tag = f"{pname}/{mname}/rounds{n_rounds}"
                check(f"{tag}_vs_ref", np.array_equal(got, ref))
                check(f"{tag}_vs_single_shot",
                      np.array_equal(got, singles[mname]))
                check(f"{tag}_no_drops",
                      int(s["dropped_requests"]) == 0
                      and int(s["dropped_elems"]) == 0)
                fp, sp = pipelined[cb][mi](O, L, C, D)
                gotp = np.asarray(fp).reshape(-1)
                check(f"{tag}_pipelined_vs_serial",
                      np.array_equal(gotp, got))
                check(f"{tag}_pipelined_vs_ref",
                      np.array_equal(gotp, ref))
                check(f"{tag}_pipelined_no_drops",
                      int(sp["dropped_requests"]) == 0
                      and int(sp["dropped_elems"]) == 0)
            rd2, rdt = readers[cb]
            for rd, mname in ((rd2, "twophase"), (rdt, "tam")):
                got = np.asarray(rd(O, L, C,
                                    jnp.asarray(ref).reshape(2, -1)))
                ok = all(np.array_equal(got[p][:L[p].sum()],
                                        D[p][:L[p].sum()])
                         for p in range(P_RANKS))
                check(f"{pname}/{mname}/read_rounds{n_rounds}", ok)
        for rd, mname in zip(readers_p[32], ("twophase", "tam")):
            got = np.asarray(rd(O, L, C, jnp.asarray(ref).reshape(2, -1)))
            ok = all(np.array_equal(got[p][:L[p].sum()],
                                    D[p][:L[p].sum()])
                     for p in range(P_RANKS))
            check(f"{pname}/{mname}/read_pipelined_rounds5", ok)
        if pname in ("mixed", "spanning"):
            for (mname, cb, k), fn in deep.items():
                f, s = fn(O, L, C, D)
                tag = f"{pname}/{mname}/depth{k}_rounds{160 // cb}"
                check(f"{tag}_vs_ref",
                      np.array_equal(np.asarray(f).reshape(-1), ref))
                check(f"{tag}_no_drops",
                      int(s["dropped_requests"]) == 0
                      and int(s["dropped_elems"]) == 0)
            for k, rd in readers_k.items():
                got = np.asarray(rd(O, L, C,
                                    jnp.asarray(ref).reshape(2, -1)))
                ok = all(np.array_equal(got[p][:L[p].sum()],
                                        D[p][:L[p].sum()])
                         for p in range(P_RANKS))
                check(f"{pname}/twophase/read_depth{k}_rounds5", ok)
            for (mname, cb, k), fn in coded.items():
                f, s = fn(O, L, C, D)
                tag = f"{pname}/{mname}/rle_depth{k}_rounds{160 // cb}"
                check(f"{tag}_vs_ref",
                      np.array_equal(np.asarray(f).reshape(-1), ref))
                check(f"{tag}_no_drops",
                      int(s["dropped_requests"]) == 0
                      and int(s["dropped_elems"]) == 0)
            got = np.asarray(reader_rle(O, L, C,
                                        jnp.asarray(ref).reshape(2, -1)))
            ok = all(np.array_equal(got[p][:L[p].sum()],
                                    D[p][:L[p].sum()])
                     for p in range(P_RANKS))
            check(f"{pname}/twophase/read_rle_rounds5", ok)
            for mname, fn in placed.items():
                f, s = fn(O, L, C, D)
                check(f"{pname}/{mname}/placement_swap_rounds5_vs_ref",
                      np.array_equal(np.asarray(f).reshape(-1), ref))
                check(f"{pname}/{mname}/placement_swap_no_drops",
                      int(s["dropped_requests"]) == 0
                      and int(s["dropped_elems"]) == 0)
            got = np.asarray(reader_placed(
                O, L, C, jnp.asarray(ref).reshape(2, -1)))
            ok = all(np.array_equal(got[p][:L[p].sum()],
                                    D[p][:L[p].sum()])
                     for p in range(P_RANKS))
            check(f"{pname}/twophase/read_placement_swap_rounds5", ok)
            for codec in (None, "rle"):
                for k in (1, 2):
                    outs = {}
                    for fused in (False, True):
                        rd = read_pairs[(codec, k, fused)]
                        outs[fused] = np.asarray(
                            rd(O, L, C, jnp.asarray(ref).reshape(2, -1)))
                    tag = (f"{pname}/twophase/read_"
                           f"{codec or 'raw'}_k{k}")
                    check(f"{tag}_fused_vs_unfused",
                          np.array_equal(outs[True], outs[False]))
                    ok = all(np.array_equal(outs[True][p][:L[p].sum()],
                                            D[p][:L[p].sum()])
                             for p in range(P_RANKS))
                    check(f"{tag}_fused_vs_payload", ok)

    # ---- cross-executor fuzz: seeded random patterns through BOTH
    # backends, every run against the oracle (so SPMD == host too) ----
    import tempfile
    for seed in range(4):
        O, L, C, D = random_pattern(np.random.default_rng(7000 + seed))
        ref = write_reference(layout, O, L, C, D)
        for (mname, swapped, codec, k), fn in fuzz_fns.items():
            f, s = fn(O, L, C, D)
            got = np.asarray(f).reshape(-1)
            tag = (f"fuzz{seed}/{mname}/pl{int(swapped)}_"
                   f"{codec or 'raw'}_k{k}")
            check(f"{tag}_vs_ref", np.array_equal(got, ref))
            check(f"{tag}_no_drops",
                  int(s["dropped_requests"]) == 0
                  and int(s["dropped_elems"]) == 0)
            ff, sf = fused_fns[(mname, swapped, codec, k)](O, L, C, D)
            gotf = np.asarray(ff).reshape(-1)
            check(f"{tag}_fused_vs_unfused", np.array_equal(gotf, got))
            check(f"{tag}_fused_vs_ref", np.array_equal(gotf, ref))
            check(f"{tag}_fused_no_drops",
                  int(sf["dropped_requests"]) == 0
                  and int(sf["dropped_elems"]) == 0)
        # the host executor moves the same pattern in byte units; its
        # files must reassemble to the same oracle bytes under the
        # placement x codec x depth cross
        breqs = _byte_requests(O, L, C, D)
        ref_bytes = ref.astype("<i4").view(np.uint8)
        hio = HostCollectiveIO(n_ranks=P_RANKS, n_nodes=2,
                               stripe_size=640, stripe_count=2)
        hd = tempfile.mkdtemp()
        for pi, pl in enumerate((None, "spread", (1, 0))):
            ptag = ("off", "spread", "swap")[pi]
            for codec in (None, "rle"):
                for k in (1, 2):
                    path = f"{hd}/{ptag}_{codec or 'raw'}_{k}"
                    hio.write(breqs, path, method="twophase",
                              cb_bytes=128, pipeline_depth=k,
                              slow_hop_codec=codec, placement=pl)
                    got = hio.read_file(path, FILE_LEN * 4)
                    check(f"fuzz{seed}/host/{ptag}_{codec or 'raw'}"
                          f"_k{k}_vs_spmd",
                          np.array_equal(got, ref_bytes))
        path = f"{hd}/tam"
        hio.write(breqs, path, method="tam", local_aggregators=2,
                  cb_bytes=128, pipeline_depth=2, slow_hop_codec="rle",
                  placement=(1, 0))
        check(f"fuzz{seed}/host/tam_swap_rle_k2_vs_spmd",
              np.array_equal(hio.read_file(path, FILE_LEN * 4),
                             ref_bytes))
        # unified-config host write with the fusion selected: the plan
        # carries kernel_fusion (shared field with the SPMD backend)
        # but the numpy executor has no Pallas hot path — bytes must
        # still match the oracle exactly
        cfg_host = IOConfig(req_cap=32, data_cap=DATA_CAP,
                            coalesce_cap=32, cb_buffer_size=128,
                            pipeline=True, pipeline_depth=2,
                            slow_hop_codec="rle", placement="spread",
                            kernel_fusion="fused_round")
        path = f"{hd}/fusedcfg"
        hio.write(breqs, path, method="twophase", config=cfg_host)
        check(f"fuzz{seed}/host/config_fused_vs_spmd",
              np.array_equal(hio.read_file(path, FILE_LEN * 4),
                             ref_bytes))
        # planned collective reads back through the same striping
        # (PR 8): read x placement x codec x depth x cache on/off,
        # every row's per-rank payloads byte-identical to the write
        # oracle's spans; the node cache must never model slower than
        # the per-rank baseline it replaces, and the two modes must
        # account for the SAME delivery count (hits+misses on == the
        # per-rank misses off)
        rreqs = [(o, ln) for o, ln, _ in breqs]
        exp = [(np.concatenate([ref_bytes[o:o + l]
                                for o, l in zip(oo, ll)])
                if oo.size else np.zeros(0, np.uint8))
               for oo, ll in rreqs]
        for ptag, pl in (("off", None), ("spread", "spread")):
            for codec in (None, "rle"):
                for k in (1, 2):
                    src = f"{hd}/{ptag}_{codec or 'raw'}_{k}"
                    tr = {}
                    for nc in (True, False):
                        outs, tr[nc] = hio.read(
                            rreqs, src, cb_bytes=128, pipeline_depth=k,
                            slow_hop_codec=codec, placement=pl,
                            node_cache=nc)
                        ok = all(np.array_equal(a, b)
                                 for a, b in zip(outs, exp))
                        check(f"fuzz{seed}/host_read/{ptag}_"
                              f"{codec or 'raw'}_k{k}_cache{int(nc)}"
                              f"_vs_oracle", ok)
                    check(f"fuzz{seed}/host_read/{ptag}_"
                          f"{codec or 'raw'}_k{k}_cache_not_slower",
                          tr[True].total <= tr[False].total + 1e-12)
                    check(f"fuzz{seed}/host_read/{ptag}_"
                          f"{codec or 'raw'}_k{k}_delivery_conserved",
                          tr[True].cache_hits + tr[True].cache_misses
                          == tr[False].cache_misses)
        # the mp transport runs the SAME plans on real worker
        # processes (arena fast hop, socket slow hop) — byte identity
        # against the oracle across placement x codec x depth, both
        # directions. One seed only: each run forks a process fleet,
        # and the per-combination coverage above already rotates
        # patterns across seeds.
        if seed == 0:
            for pi2, pl in enumerate((None, (1, 0))):
                ptag = ("off", "swap")[pi2]
                for codec in (None, "rle"):
                    for k in (1, 2):
                        path = f"{hd}/mp_{ptag}_{codec or 'raw'}_{k}"
                        hio.write(breqs, path, method="twophase",
                                  cb_bytes=128, pipeline_depth=k,
                                  slow_hop_codec=codec, placement=pl,
                                  transport="mp")
                        check(f"fuzz{seed}/mp/{ptag}_{codec or 'raw'}"
                              f"_k{k}_vs_oracle",
                              np.array_equal(
                                  hio.read_file(path, FILE_LEN * 4),
                                  ref_bytes))
            path = f"{hd}/mp_tam"
            hio.write(breqs, path, method="tam", local_aggregators=2,
                      cb_bytes=128, pipeline_depth=2,
                      slow_hop_codec="rle", placement=(1, 0),
                      transport="mp")
            check(f"fuzz{seed}/mp/tam_swap_rle_k2_vs_oracle",
                  np.array_equal(hio.read_file(path, FILE_LEN * 4),
                                 ref_bytes))
            for nc in (True, False):
                outs, tmp_t = hio.read(
                    rreqs, f"{hd}/mp_off_rle_2", cb_bytes=128,
                    pipeline_depth=2, slow_hop_codec="rle",
                    node_cache=nc, transport="mp")
                check(f"fuzz{seed}/mp_read/rle_k2_cache{int(nc)}"
                      f"_vs_oracle",
                      all(np.array_equal(a, b)
                          for a, b in zip(outs, exp)))

    # overflow observability: one rank pushes 2x identical 32-element
    # requests into one 32-element window -> 64 elems > the round
    # bucket's min(data_cap, cb)=32 -> dropped_elems must be reported.
    O = np.full((P_RANKS, REQ_CAP), 2**31 - 1, np.int32)
    L = np.zeros((P_RANKS, REQ_CAP), np.int32)
    C = np.zeros(P_RANKS, np.int32)
    D = np.zeros((P_RANKS, DATA_CAP), np.int32)
    O[0, 0] = O[0, 1] = 0
    L[0, 0] = L[0, 1] = 32
    C[0] = 2
    D[0, :64] = np.tile(np.arange(32) % 97 + 1, 2)
    _, s = writers[32][0](O, L, C, D)
    check("overflow/dropped_elems_reported", int(s["dropped_elems"]) > 0)

    print(f"{len(FAILURES)} failures", flush=True)
    raise SystemExit(1 if FAILURES else 0)


if __name__ == "__main__":
    main()
