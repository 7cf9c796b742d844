"""Run one benchmark cell once and print its result line.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``
(its configuration, traffic and chips), the configuration in the file
that ``BENCHMARK.json`` gives for it, the traffic in
``traffic/<name>.json``, the driver of the traffic's kind in
``drivers/<kind>.py``, and each metric's reader in
``metrics/<metric>.py``. A later cell, configuration, traffic kind or
metric is added as files.

A run: set-up (inputs from the seed, both programs planned and compiled
through the persistent cache), then a window that starts iterations
while less than ``--seconds`` has passed and finishes every call it
started, then the comparison with the plain reference, once the device
state is freed. ``--trace 1`` records the window with the profiler and
reports the per-layer metrics instead of the end-to-end ones.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import statistics
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def load_table(path=ROOT / "BENCHMARK.json") -> dict:
    return json.loads(Path(path).read_text())


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(table: dict, workload: str):
    """``(cell, configuration, traffic)`` of the named cell."""
    cells = {c["name"]: c for c in table["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    (conf,) = [c for c in table["configs"] if c["name"] == cell["config"]]
    config = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads(
        (HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    return cell, config, traffic


def metrics_for(table: dict, cell: dict, trace: bool) -> list:
    """The cell's metric entries: end-to-end ones, or per-layer ones
    with ``trace``."""
    kind = "per_layer" if trace else "end_to_end"
    return [m for m in table[kind]
            if cell["name"] in m.get("workloads", [cell["name"]])]


def span(name: str):
    """A host span in the profiler's own trace, on the device's clock."""
    import jax

    return jax.profiler.TraceAnnotation("bench." + name)


class Context:
    """What a metric's reader may read: the window's calls, the set-up
    timings, the peak device memory, the bytes each call needs, and the
    reduced trace (``None`` without ``--trace 1``)."""

    def __init__(self, calls, timings, memory_peak_bytes, device_kind,
                 n_chips, work, trace):
        self.calls = calls
        self.timings = timings
        self.memory_peak_bytes = memory_peak_bytes
        self.device_kind = device_kind
        self.n_chips = n_chips
        self.work = work
        self.trace = trace

    def calls_of(self, kind: str) -> list:
        return [c for c in self.calls if c["kind"] == kind]

    def peaks(self) -> dict:
        from peaks import peaks_of

        return peaks_of(self.device_kind)

    def call_windows(self, kind: str):
        """Per device, the program executions of the calls of ``kind``;
        ``None`` without a device trace. A trace whose executions do not
        match the calls raises."""
        import tracefile

        if self.trace is None or not self.trace.ops:
            return None
        return tracefile.call_windows(self.trace, kind)

    def device_busy_s(self, kind: str):
        """Seconds in which an op ran on the device during the calls
        of ``kind``, averaged over the chips; ``None`` without a device
        trace."""
        import tracefile

        windows = self.call_windows(kind)
        if windows is None:
            return None
        return statistics.fmean(
            tracefile.busy_ns(self.trace, windows).values()) / 1e9

    def wall_s(self, kind: str) -> float:
        """Host seconds of the calls of ``kind``."""
        return sum(c["wall_s"] for c in self.calls_of(kind))


def reduce_trace(trace, kinds=("write", "read")):
    """``(device extras, breakdown)`` of a reduced trace. Each device op
    in the breakdown is named after the call (``kinds``) whose program
    execution holds its start, or ``other``."""
    import tracefile

    (window,) = trace.span_intervals("window")
    window_s = (window[1] - window[0]) / 1e9
    if not trace.ops:
        return {"busy_s": 0.0, "window_s": window_s}, None
    busy = tracefile.busy_ns(trace, [window])
    execs = {k: tracefile.call_windows(trace, k) for k in kinds}
    per_op: dict = {}
    for dev, evs in trace.ops.items():
        mine = [(lo, hi, k) for k in kinds for lo, hi in execs[k].get(dev, [])]

        def call_of(start):
            return next((k for lo, hi, k in mine if lo <= start < hi),
                        "other")

        inside = [(s, e, f"{call_of(s)}: {n}") for s, e, n in evs
                  if s < window[1] and e > window[0]]
        for name, ns in tracefile.self_ns(inside).items():
            per_op[name] = per_op.get(name, 0.0) + ns / 1e9 / len(trace.ops)
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    return ({"busy_s": statistics.fmean(busy.values()) / 1e9,
             "window_s": window_s},
            {"device_ops": [[n, s] for n, s in ops],
             "idle_gaps": tracefile.idle_gaps(trace, window)})


def parse_args(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, t_start: float, table: dict | None = None,
         allow_cpu: bool = False, wrap=None) -> int:
    """Run the cell ``argv`` names once. ``t_start`` is the process's
    start on ``time.perf_counter``'s clock. ``allow_cpu`` (rehearsals on
    the CPU only) lifts the refusal to run without a TPU; ``wrap`` is
    handed to the driver (control and fault tests)."""
    args = parse_args(argv)
    table = load_table() if table is None else table
    cell, config, traffic = resolve(table, args.workload)

    import jax

    devices = jax.devices()
    devices_s = time.perf_counter() - t_start
    if devices[0].platform != "tpu" and not allow_cpu:
        print(f"benchmark: no TPU (JAX found {devices[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"benchmark: {cell['name']} needs {cell['chips']} chips, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    devices = devices[:cell["chips"]]

    from repro.launch.cache import enable_compilation_cache

    enable_compilation_cache(ROOT)
    compiles = {"window": 0}
    in_window = [False]

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration" \
                and in_window[0]:
            compiles["window"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    driver = load_module(HERE / "drivers" / f"{traffic['driver']}.py")
    state = driver.Cell(config, traffic, devices, args.seed, wrap=wrap)
    setup_s = time.perf_counter() - t_start

    calls: list = []
    with contextlib.ExitStack() as stack:
        if args.trace:
            tmp = Path(stack.enter_context(tempfile.TemporaryDirectory()))
            jax.profiler.start_trace(str(tmp))
        in_window[0] = True
        with span("window"):
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < args.seconds:
                calls += state.iterate(span)
        in_window[0] = False
        if args.trace:
            jax.profiler.stop_trace()
            import tracefile

            (pb,) = tmp.glob("plugins/profile/*/*.xplane.pb")
            trace = tracefile.load(pb)
        else:
            trace = None

    stats = [d.memory_stats() or {} for d in devices]
    peaks_in_use = [s["peak_bytes_in_use"] for s in stats
                    if "peak_bytes_in_use" in s]
    memory_peak = max(peaks_in_use) if peaks_in_use else None
    numbers, failed = state.check()

    ctx = Context(calls, {"setup_s": setup_s, "plan_s": state.plan_s,
                          "compile_s": state.compile_s},
                  memory_peak, devices[0].device_kind, len(devices),
                  {"write": state.work, "read": state.work}, trace)
    metrics = {}
    for m in metrics_for(table, cell, bool(args.trace)):
        value = load_module(HERE / "metrics" / f"{m['name']}.py").read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    result = {"correct": all(v <= lim for v, lim in numbers.values()),
              "attempted": len(calls), "failed": failed,
              "metrics": metrics, "device": device}
    if trace is not None:
        extra, breakdown = reduce_trace(trace)
        device.update(extra)
        if breakdown is not None:
            result["breakdown"] = breakdown
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in numbers.items()}

    for c in calls[:20]:
        print(f"call {c['kind']} wall_s={c['wall_s']:.6f}", file=sys.stderr)
    print(f"setup_s={setup_s:.3f} (to_devices={devices_s:.3f} "
          f"inputs={state.inputs_s:.3f} plan={state.plan_s:.3f} "
          f"compile={state.compile_s:.3f}) "
          f"window_compiles={compiles['window']}", file=sys.stderr)
    for k, (v, lim) in numbers.items():
        print(f"check {k}={v} limit={lim}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
