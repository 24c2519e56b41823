"""What every runner shares: finding the cell's files by name, the chip
check, JAX's persistent compilation cache, counter snapshots, the profiler
window, per-layer metric readers, and the contract's result line.

Nothing here knows a workload, a configuration or a metric by name: a cell
is three files found through ``BENCHMARK.json``.
"""
from __future__ import annotations

import glob
import importlib
import json
import math
import os
import statistics
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
OUT_DIR = os.path.join(HERE, ".out")          # git-ignored run products


class BenchmarkError(Exception):
    """The run cannot give a result: exit non-zero, print no result line."""


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with its configuration, traffic mix, and
    the metrics ``BENCHMARK.json`` says it reports."""

    def __init__(self, bench: dict, name: str, rehearse: bool = False):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise BenchmarkError(f"no workload {name!r} in BENCHMARK.json; "
                                 f"it has {sorted(cells)}")
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        cfg_entry = {c["name"]: c for c in bench["configs"]}[
            self.entry["config"]]
        self.config = load_json(REPO, cfg_entry["file"])
        self.traffic = load_json(HERE, "traffic",
                                 self.entry["traffic"] + ".json")
        self.rehearse = rehearse
        if rehearse:
            # tiny sizes for a CPU run of the control flow; never a metric
            _merge(self.config, self.config.get("rehearsal", {}))
            _merge(self.traffic, self.traffic.get("rehearsal", {}))
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [
            m for m in bench["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]


def _merge(base: dict, over: dict) -> None:
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(base.get(k), dict):
            _merge(base[k], v)
        else:
            base[k] = v


# -- the device --------------------------------------------------------------

def use_compile_cache() -> str:
    """JAX's persistent compilation cache at ``JAX_COMPILATION_CACHE_DIR``
    if the environment sets it, else at one fixed path inside the checkout
    (the path is part of the cache's key). Everything is cached, however
    fast it compiled, so that a second run compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(HERE, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def find_chips(cell: Cell) -> dict:
    """The devices the cell runs on, as JAX reports them, with their
    published peaks. No accelerator, too few chips, or a device that
    ``peaks.json`` does not list is an error: there is no CPU fallback."""
    import jax

    devs = jax.devices()
    peaks = load_json(HERE, "peaks.json")["devices"]
    if cell.rehearse:
        return {"devices": devs[:cell.chips], "platform": devs[0].platform,
                "kind": devs[0].device_kind, "peaks": None}
    if devs[0].platform != "tpu":
        raise BenchmarkError(f"JAX found no accelerator: {devs}")
    if len(devs) < cell.chips:
        raise BenchmarkError(f"cell {cell.name} needs {cell.chips} chips, "
                             f"JAX found {len(devs)}: {devs}")
    kind = devs[0].device_kind
    if kind not in peaks:
        raise BenchmarkError(f"device kind {kind!r} is not in peaks.json "
                             f"({sorted(peaks)}); add it with its source")
    return {"devices": devs[:cell.chips], "platform": devs[0].platform,
            "kind": kind, "peaks": peaks[kind]}


def memory_peak_bytes(devices) -> int:
    """Peak bytes on the fullest of the cell's chips, from the allocator's
    own counters. On this TPU runtime ``peak_bytes_in_use`` counts live
    buffers only; the scratch of loaded programs is reserved apart, at the
    bottom of memory, and shows as ``peak_bytes_reserved`` (a BERT step with
    over 10 GB of compiler-reported temporaries reads 2.0 GB in use, PR 21
    and PR 24). The peak is the larger of the live peak and the bytes in
    use now plus the largest reservation, which the programs of the window
    held while those buffers were live."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        say(f"memory_stats of {d}: {stats}")
        live_peak = int(stats.get("peak_bytes_in_use", 0))
        with_scratch = (int(stats.get("bytes_in_use", 0))
                        + int(stats.get("peak_bytes_reserved", 0)))
        peak = max(peak, live_peak, with_scratch)
    return peak


def plant_weights(scope, weights: dict) -> None:
    """Put the benchmark's seeded weights where the startup program put its
    own, after checking that shapes and types agree."""
    for name, w in weights.items():
        have = scope.find_var(name)
        if (have is None or tuple(have.shape) != tuple(w.shape)
                or have.dtype != w.dtype):
            raise BenchmarkError(
                f"parameter {name}: program has "
                f"{getattr(have, 'shape', None)} "
                f"{getattr(have, 'dtype', None)}, reference {w.shape} "
                f"{w.dtype}")
        scope.set_var(name, w)


def check_parameter_names(program, spec: dict) -> None:
    names = {p.name for p in program.global_block.all_parameters()}
    if names != set(spec):
        raise BenchmarkError(
            f"the program's parameters and the reference's differ: "
            f"{sorted(names ^ set(spec))[:8]}")


# -- counters -----------------------------------------------------------------

def counters() -> dict:
    """A flat snapshot of the program's monitor registry:
    ``name{label=value,...}`` -> number; a histogram gives ``..._count`` and
    ``..._sum``, which are exact (its percentiles are bucket estimates and
    are not read)."""
    from paddle_tpu import monitor

    flat = {}
    for name, fam in monitor.get_registry().to_dict().items():
        for child in fam["values"]:
            labels = ",".join(f"{k}={v}"
                              for k, v in sorted(child["labels"].items()))
            key = f"{name}{{{labels}}}"
            val = child["value"]
            if isinstance(val, dict):
                flat[key + "_count"] = float(val["count"])
                flat[key + "_sum"] = float(val["sum"])
            else:
                flat[key] = float(val)
    flat["recompiles_total{}"] = float(monitor.recompile_count())
    return flat


def counter_delta(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


def sum_matching(values: dict, name: str, **labels) -> float:
    """Sum of every child of family ``name`` whose labels include
    ``labels`` (a histogram's ``_count`` / ``_sum`` is part of ``name``)."""
    total = 0.0
    for key, v in values.items():
        fam, _, rest = key.partition("{")
        lab, _, suffix = rest.partition("}")
        if fam + suffix != name:
            continue
        have = dict(kv.split("=", 1) for kv in lab.split(",") if kv)
        if all(have.get(k) == str(want) for k, want in labels.items()):
            total += v
    return total


# -- the traced window ----------------------------------------------------------

class TraceWindow:
    """With ``--trace 1``: the program's span collection on for the whole
    run, and a ``jax.profiler`` trace of ``seconds`` from ``start_at`` into
    the measured window. A runner calls ``poll(elapsed)`` as the window
    goes; everything else is a no-op with ``--trace 0``."""

    def __init__(self, on: bool, run_seconds: float, tag: str):
        self.on = on
        self.start_at = run_seconds / 3.0
        self.seconds = min(4.0, max(run_seconds / 3.0, 0.5))
        self.dir = os.path.join(OUT_DIR, "trace", tag)
        self.state = "idle" if on else "off"
        self.t_started = self.t_stopped = None
        self.anchor = None
        self._lock = threading.Lock()    # poll and stop come from two threads

    def enable_spans(self) -> None:
        if self.on:
            import paddle_tpu as fluid

            fluid.set_flags({"FLAGS_trace": True})

    def poll(self, elapsed: float) -> float:
        """Starts or stops the profiler when it is time; returns the
        seconds that took, which a single-threaded loop takes out of its
        own clock."""
        t0 = time.perf_counter()
        self._poll(elapsed)
        return time.perf_counter() - t0

    def _poll(self, elapsed: float) -> None:
        with self._lock:
            start = self.state == "idle" and elapsed >= self.start_at
            if start:
                self.state = "starting"
        if start:
            import shutil

            import jax

            shutil.rmtree(self.dir, ignore_errors=True)
            os.makedirs(self.dir, exist_ok=True)
            jax.profiler.start_trace(self.dir)
            # one host event whose host-clock time is known: it ties the
            # trace's clock to the clock the program's spans carry
            with jax.profiler.TraceAnnotation("benchmark.clock_anchor"):
                self.anchor = {"perf_counter": time.perf_counter(),
                               "epoch_ns": time.time_ns()}
            self.t_started = time.perf_counter()
            with self._lock:
                self.state = "tracing"
        elif elapsed >= self.start_at + self.seconds:
            self.stop()

    def stop(self) -> None:
        with self._lock:
            if self.state == "tracing":
                import jax

                self.t_stopped = time.perf_counter()
                jax.profiler.stop_trace()
                self.state = "done"

    def xplane_path(self):
        if self.state != "done":
            return None
        found = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None


def program_spans() -> list:
    """The program's closed spans as plain dicts, on the host's monotonic
    clock (``t0``, ``t1`` in seconds of ``time.perf_counter``)."""
    from paddle_tpu import trace

    out = []
    for s in trace.spans():
        if s.duration_s is None:
            continue
        out.append({"name": s.name, "trace_id": s.trace_id,
                    "span_id": s.span_id, "parent_id": s.parent_id,
                    "t0": s.t0_mono, "t1": s.t0_mono + s.duration_s,
                    "thread": s.thread_name, "attrs": dict(s.attrs)})
    return out


# -- per-layer metrics -------------------------------------------------------------

def read_layer_metrics(cell: Cell, ctx: dict) -> dict:
    """Each per-layer metric of the cell through its reader: the metric's
    file names a module of ``readers/`` and its arguments. A reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer:
        spec = load_json(HERE, "layer_metrics", m["name"] + ".json")
        reader = importlib.import_module(f"readers.{spec['reader']}")
        value = reader.read(ctx, **spec.get("args", {}))
        if value is not None and math.isfinite(value):
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# -- statistics ----------------------------------------------------------------------

def percentile(values, q: float) -> float:
    """The q-th percentile by the nearest-rank rule on the sorted sample
    (no interpolation: a tail is one of the requests)."""
    if not values:
        return float("nan")
    s = sorted(values)
    k = max(0, min(len(s) - 1, math.ceil(q / 100.0 * len(s)) - 1))
    return float(s[k])


def median(values) -> float:
    return float(statistics.median(values)) if values else float("nan")


# -- the result --------------------------------------------------------------------------

def say(*parts) -> None:
    """A line above the last: free text for whoever reads the run."""
    print("[benchmark]", *parts, flush=True)


def print_result(cell: Cell, chips: dict, result: dict, trace: bool) -> int:
    """The contract's last line. In a rehearsal no metric is printed under
    any name: a CPU number never stands where a device metric would."""
    checks = result["checks"]
    for c in checks:
        # on both streams: the free text above the result line, and the
        # last lines of standard error, which is what is kept of a run
        # that is not correct
        text = (f"compared {c['name']}: {c['value']!r} against limit "
                f"{c['limit']!r} ({c['rule']}) -> "
                f"{'ok' if c['ok'] else 'NOT CORRECT'}")
        say(text)
        print("[benchmark]", text, file=sys.stderr, flush=True)
    correct = bool(checks) and all(c["ok"] for c in checks)
    if cell.rehearse:
        print(json.dumps({
            "rehearsal": True, "correct": correct,
            "attempted": result["attempted"], "failed": result["failed"],
            "would_report": sorted(
                m["name"] for m in cell.end_to_end + cell.per_layer
                if m["name"] in result["metrics"])}), flush=True)
        return 0 if correct else 1
    want = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in want:
        if m["name"] in result["metrics"]:
            v = result["metrics"][m["name"]]
            v = v["value"] if isinstance(v, dict) else v
            if math.isfinite(v):
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": chips["platform"], "kind": chips["kind"],
              "count": len(chips["devices"]),
              "memory_peak_bytes": result["memory_peak_bytes"]}
    line = {"correct": correct, "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics,
            "device": device}
    if trace:
        device["busy_s"] = result["busy_s"]
        device["window_s"] = result["window_s"]
        if result.get("breakdown"):
            line["breakdown"] = result["breakdown"]
    # each number compared beside its limit, under a key that comes last
    line["compared"] = {c["name"]: {"value": c["value"], "limit": c["limit"],
                                    "rule": c["rule"], "ok": c["ok"]}
                        for c in checks}
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


def reduce_trace(trace: TraceWindow, spans: list):
    """The traced part of the window reduced to busy time, top operations
    and idle gaps by host span; None where no device plane was recorded."""
    path = trace.xplane_path()
    if not path:
        return None
    from readers import xplane

    loaded = xplane.load(path)
    window, host = None, []
    if loaded["anchor_ns"] is not None:
        pc = trace.anchor["perf_counter"]
        to_ns = lambda t: loaded["anchor_ns"] + (t - pc) * 1e9
        window = (to_ns(trace.t_started), to_ns(trace.t_stopped))
        host = xplane.spans_on_trace_clock(spans, pc, loaded["anchor_ns"])
    return xplane.reduce(loaded, window, host)


def finish_traced(cell: Cell, chips: dict, trace: TraceWindow,
                  result: dict, **gathered) -> None:
    """Adds the device's busy time, the breakdown and the cell's per-layer
    metrics to a traced run's result. ``gathered``: ``counters``,
    ``counters_total`` and ``series`` from the runner's window."""
    spans = program_spans()
    ctx = dict(gathered, spans=spans, end_to_end=result["metrics"],
               config=cell.config, traffic=cell.traffic,
               peaks=chips["peaks"], chips=len(chips["devices"]),
               trace=reduce_trace(trace, spans))
    t = ctx["trace"]
    if t is None:
        if not cell.rehearse:
            raise BenchmarkError("the traced run recorded no operation on "
                                 "the device")
        result["busy_s"] = result["window_s"] = None
    else:
        result["busy_s"], result["window_s"] = t["busy_s"], t["window_s"]
        result["breakdown"] = {"device_ops": t["device_ops"],
                               "idle_gaps": t["idle_gaps"]}
    result["metrics"].update(read_layer_metrics(cell, ctx))
