"""Device time per round-loop stage, and the write's slow-hop counters.

Every stage of the round loop runs under a flat ``jax.named_scope``
(``src/repro/core/rounds.py``: ``io.split``, ``io.select``,
``io.stage1``, ``io.exchange``, ``io.drain``, ``io.merge`` in the
writes; ``io.index``, ``io.fetch``, ``io.scatter`` in the read). The
scope reaches the compiled program's text as each instruction's
``metadata={op_name=".../io.<stage>/..."}``; the profiler's device ops
carry no metadata, but each is named by its instruction (``%fusion.45
= ...``), which the program's text defines. So:

* :func:`stage_map` parses a compiled program's ``as_text()`` into
  ``{instruction name: stage}``: the innermost ``io.`` component of the
  instruction's ``op_name``; for a fusion without one, the first stage
  found among the instructions of its fused computation; for an
  instruction the compiler made with no scope of its own, the stage of
  the nearest user, else operand, that has one (:func:`stage_rules`);
  anything else is ``other``.
* :func:`stage_ns` sums, per device, the self time of the ops that
  start inside a call's program executions (``tracefile.call_windows``)
  per stage.
* :func:`idle_in_program` lists the longest idle gaps inside program
  executions, each labelled ``<call>:<stage of the op that ends it>``:
  both ends are on the device's own clock.
* :func:`counter_shares` reads the writes' own counters
  (``slow_hop_live_elems`` / ``slow_hop_shipped_elems`` and TAM's
  ``requests_before_coalesce`` / ``requests_after_coalesce``).

Run as a script, it sets up one cell as the harness does (compiling
both programs afresh, past the persistent cache, whose key leaves out
the ops' metadata), records the HLO text of both programs, runs one write and one read untraced and
then one of each under the profiler, and prints one JSON object: the
wall time of each call with and without the profiler, the seconds of
each stage per call kind (averaged over the chips), the device busy
time they add up to, the ``other`` ops with most time, the idle gaps
inside the programs, and the counters::

    python3 benchmarks/chip/stages.py --workload e3sm_g_node.tam_cycle \\
        --seed 2147483701

``--record DIR`` also keeps the trace and the programs' text there
(the test data of ``tests/test_bench_stages.py`` was made so, from the
rehearsal's tiny node cell). Without a TPU it exits 2 before any work,
unless ``--allow-cpu`` (CPU rehearsals: the CPU trace has no device
plane, so only the counters and wall times come out).
"""
from __future__ import annotations

import argparse
import bisect
import json
import re
import statistics
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
if __name__ == "__main__":
    sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import tracefile  # noqa: E402

OTHER = "other"
UNRESOLVED = "unresolved"   # a trace op the program's text does not define
WRITE_STAGES = ("split", "select", "stage1", "exchange", "drain", "merge")
READ_STAGES = ("index", "fetch", "scatter")
STAGES = {"write": WRITE_STAGES, "read": READ_STAGES}

_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")
_CONTROL = re.compile(r"\s(?:while|conditional|call)\(")
_TRACE_NAME = re.compile(r"^%?([\w.\-]+)\s*=")


def scope_stage(op_name: str) -> str | None:
    """The innermost ``io.`` component of an ``op_name``, without the
    prefix; ``None`` if it has none."""
    stage = None
    for part in op_name.split("/"):
        if part.startswith("io."):
            stage = part[3:]
    return stage


def stage_rules(hlo_text: str) -> dict:
    """``{instruction name: (stage, rule)}`` of every instruction in a
    compiled program's text (``Compiled.as_text()``). The rules, in
    order: ``scope``, the innermost ``io.`` component of the
    instruction's own ``op_name``; ``fused``, for a fusion, the first
    stage found among the instructions of its fused computation;
    ``dataflow``, the stage of the nearest user in the same computation
    that has one, else of the nearest operand (the compiler's copies,
    and the scan that ``jnp.cumsum`` lowers to on a TPU, which loses the
    caller's scopes), except for control flow (``while``,
    ``conditional``, ``call``: their self time is the loop's own);
    ``other`` where none applies."""
    own: dict = {}        # instruction -> stage from its own op_name
    calls: dict = {}      # instruction -> computation it calls
    members: dict = {}    # computation -> its instructions, in order
    operands: dict = {}   # instruction -> instructions it reads
    control = set()       # while, conditional and call instructions
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m and comp is not None:
            name = m.group(1)
            members[comp].append(name)
            head, _, meta = line.partition(", metadata=")
            op = _OP_NAME.search(meta)
            own[name] = scope_stage(op.group(1)) if op else None
            c = _CALLS.search(head)
            if c:
                calls[name] = c.group(1)
            if _CONTROL.search(head):
                control.add(name)
            operands[name] = [r for r in _REF.findall(head.split("=", 1)[1])
                              if r != name]
            continue
        h = _HEADER.match(line)
        if h:
            comp = h.group(1)
            members[comp] = []

    def fused(name, seen=()):
        if own.get(name):
            return own[name]
        if name in calls and calls[name] not in seen:
            for inner in members.get(calls[name], []):
                stage = fused(inner, seen + (calls[name],))
                if stage:
                    return stage
        return None

    out = {}
    for comp, names in members.items():
        local = set(names)
        users: dict = {n: [] for n in names}
        for n in names:
            operands[n] = [o for o in operands[n] if o in local]
            for o in operands[n]:
                users[o].append(n)
        for n in names:
            if own[n]:
                out[n] = (own[n], "scope")
            elif fused(n):
                out[n] = (fused(n), "fused")
        # breadth first: an instruction with a stage hands it to the
        # operands it reads (their nearest user), then to its users
        for step in (operands, users):
            frontier = [n for n in names if n in out]
            while frontier:
                nxt = []
                for n in frontier:
                    for m in step[n]:
                        if m not in out and m not in control:
                            out[m] = (out[n][0], "dataflow")
                            nxt.append(m)
                frontier = nxt
        for n in names:
            out.setdefault(n, (OTHER, OTHER))
    return out


def stage_map(hlo_text: str) -> dict:
    """``{instruction name: stage}`` by :func:`stage_rules`."""
    return {n: s for n, (s, _) in stage_rules(hlo_text).items()}


def instruction(op: str) -> str:
    """The instruction name of a trace op (``%fusion.45 = s32[...]...``
    gives ``fusion.45``)."""
    m = _TRACE_NAME.match(op)
    return m.group(1) if m else op.split()[0].lstrip("%")


def _inside(evs, windows):
    """The events of ``evs`` that start inside one of ``windows``."""
    wins = sorted(windows)
    los = [lo for lo, _ in wins]
    out = []
    for ev in evs:
        i = bisect.bisect_right(los, ev[0]) - 1
        if i >= 0 and ev[0] < wins[i][1]:
            out.append(ev)
    return out


def op_ns(trace, windows: dict) -> dict:
    """Per device, the self time of each op (by trace name) that starts
    inside that device's ``windows``."""
    return {dev: tracefile.self_ns(_inside(evs, windows.get(dev, [])))
            for dev, evs in trace.ops.items()}


def stage_ns(trace, windows: dict, stages: dict) -> dict:
    """Per device, ``{stage: self time}`` of the ops inside ``windows``;
    ``stages`` maps instruction names to stages (:func:`stage_map`). An
    op the program's text does not define counts as ``unresolved``."""
    out = {}
    for dev, per_op in op_ns(trace, windows).items():
        acc: dict = {}
        for op, ns in per_op.items():
            stage = stages.get(instruction(op), UNRESOLVED)
            acc[stage] = acc.get(stage, 0.0) + ns
        out[dev] = acc
    return out


def stage_seconds(trace, windows: dict, stages: dict, kind: str) -> dict:
    """Seconds of each stage of a call ``kind`` and of ``other`` (and of
    ``unresolved`` ops, if any), summed over the calls and averaged over
    the chips."""
    per_dev = stage_ns(trace, windows, stages)
    names = STAGES[kind] + (OTHER,)
    if any(UNRESOLVED in d for d in per_dev.values()):
        names += (UNRESOLVED,)
    return {s: statistics.fmean(d.get(s, 0.0) for d in per_dev.values())
            / 1e9 for s in names}


def top_ops(trace, windows: dict, stages: dict, stage: str,
            top: int = 5) -> list:
    """The ``top`` ops of one stage with most self time, as ``[op,
    seconds averaged over the chips]``."""
    acc: dict = {}
    per_dev = op_ns(trace, windows)
    for per_op in per_dev.values():
        for op, ns in per_op.items():
            if stages.get(instruction(op), UNRESOLVED) == stage:
                acc[op] = acc.get(op, 0.0) + ns / 1e9 / len(per_dev)
    return [[op, s] for op, s in sorted(acc.items(),
                                        key=lambda kv: -kv[1])[:top]]


def idle_in_program(trace, windows_by_kind: dict, stages_by_kind: dict,
                    top: int = 10) -> list:
    """The ``top`` longest idle gaps of any device inside a program
    execution, each as ``["<call>:<stage>", seconds]``: the stage of
    the op that ends the gap, or ``end`` for a gap that closes the
    execution."""
    gaps = []
    for kind, windows in windows_by_kind.items():
        for dev, evs in trace.ops.items():
            for lo, hi in windows.get(dev, []):
                t = lo
                for s, e, name in _inside(evs, [(lo, hi)]):
                    if s > t:
                        gaps.append((s - t, kind, stages_by_kind[kind].get(
                            instruction(name), UNRESOLVED)))
                    t = max(t, e)
                if hi > t:
                    gaps.append((hi - t, kind, "end"))
    gaps.sort(key=lambda g: -g[0])
    return [[f"{kind}:{stage}", ns / 1e9] for ns, kind, stage in gaps[:top]]


def counter_shares(calls: list) -> dict:
    """The writes' counters over the calls (each a dict with ``kind``
    and the write's ``stats``): ``slow_hop_useful.write`` = 100 × Σ live
    ÷ Σ shipped slow-hop elements, ``coalesce_ratio.write`` = 100 × Σ
    requests after ÷ Σ before TAM's coalescing; a key is left out where
    the program returns no such counter."""
    writes = [c["stats"] for c in calls
              if c["kind"] == "write" and "stats" in c]
    out = {}
    for name, num, den in (
            ("slow_hop_useful.write", "slow_hop_live_elems",
             "slow_hop_shipped_elems"),
            ("coalesce_ratio.write", "requests_after_coalesce",
             "requests_before_coalesce")):
        if writes and all(num in w and den in w for w in writes):
            total = sum(int(w[den]) for w in writes)
            if total:
                out[name] = 100.0 * sum(int(w[num]) for w in writes) / total
    return out


# ---------------------------------------------------------------------------
# the report: one cell set up as the harness does, one iteration untraced
# and one traced
# ---------------------------------------------------------------------------

def _iteration(state, span) -> list:
    """One write and one read of its file, as the driver's iteration,
    keeping the write's stats."""
    import jax

    calls = []
    with span("write"):
        t0 = time.perf_counter()
        with span("dispatch"):
            out = state.write(*state.args)
        with span("wait"):
            file, stats = jax.block_until_ready(out)
        calls.append({"kind": "write", "wall_s": time.perf_counter() - t0})
    with span("read"):
        t0 = time.perf_counter()
        with span("dispatch"):
            got = state.read(*state.args[:3], file)
        with span("wait"):
            jax.block_until_ready(got)
        calls.append({"kind": "read", "wall_s": time.perf_counter() - t0})
    calls[0]["stats"] = {k: v.tolist() for k, v in
                         jax.device_get(stats).items()}
    return calls


def report(trace, hlo: dict) -> dict:
    """Stage seconds, busy time, ``other`` ops and idle gaps of a trace
    of one write and one read, from the programs' texts ``hlo``;
    ``by_rule`` gives the seconds whose stage each rule of
    :func:`stage_rules` decided."""
    rules = {k: stage_rules(t) for k, t in hlo.items()}
    stages = {k: {n: s for n, (s, _) in r.items()} for k, r in rules.items()}
    windows = {k: tracefile.call_windows(trace, k) for k in hlo}
    out = {"stages": {}, "by_rule": {}, "busy_s": {}, "other_ops": {}}
    for kind in hlo:
        out["stages"][kind] = stage_seconds(trace, windows[kind],
                                            stages[kind], kind)
        per_dev = stage_ns(trace, windows[kind],
                           {n: r for n, (_, r) in rules[kind].items()})
        out["by_rule"][kind] = {
            r: statistics.fmean(d.get(r, 0.0) for d in per_dev.values())
            / 1e9 for r in sorted(set().union(*per_dev.values()))}
        out["busy_s"][kind] = statistics.fmean(
            tracefile.busy_ns(trace, windows[kind]).values()) / 1e9
        out["other_ops"][kind] = top_ops(trace, windows[kind],
                                         stages[kind], OTHER)
    out["idle_in_program"] = idle_in_program(trace, windows, stages)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--table", default=None,
                    help="a BENCHMARK.json other than the checkout's")
    ap.add_argument("--record", default=None,
                    help="directory to keep the trace and programs in")
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)

    import tempfile

    import harness

    table = harness.load_table(*([args.table] if args.table else []))
    cell, config, traffic = harness.resolve(table, args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" and not args.allow_cpu:
        print(f"stages: no TPU (JAX found {devices[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 2
    devices = devices[:cell["chips"]]

    # compile afresh: the persistent cache's key leaves out op metadata,
    # so a cached executable may carry another version's scopes
    jax.config.update("jax_enable_compilation_cache", False)
    driver = harness.load_module(HERE / "drivers" / f"{traffic['driver']}.py")
    state = driver.Cell(config, traffic, devices, args.seed)
    hlo = {"write": state.write.as_text(), "read": state.read.as_text()}

    untraced = _iteration(state, harness.span)
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        with harness.span("window"):
            traced = _iteration(state, harness.span)
        jax.profiler.stop_trace()
        (pb,) = Path(tmp).glob("plugins/profile/*/*.xplane.pb")
        trace = tracefile.load(pb)
        if args.record:
            rec = Path(args.record)
            rec.mkdir(parents=True, exist_ok=True)
            (rec / "trace.xplane.pb").write_bytes(pb.read_bytes())
            for kind, text in hlo.items():
                (rec / f"{kind}.hlo.txt").write_text(text)

    result = {"workload": cell["name"], "seed": args.seed,
              "device": {"platform": devices[0].platform,
                         "kind": devices[0].device_kind,
                         "count": len(devices)},
              "wall_s": {c["kind"]: c["wall_s"] for c in untraced},
              "wall_s_traced": {c["kind"]: c["wall_s"] for c in traced},
              "stats": [c["stats"] for c in untraced + traced
                        if "stats" in c],
              "counters": counter_shares(untraced + traced)}
    if trace.ops:
        result.update(report(trace, hlo))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
