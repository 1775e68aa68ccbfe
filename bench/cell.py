"""One run of one benchmark cell: set-up, measured window, check, metrics.

Everything here is driven by data found by name:

  * ``BENCHMARK.json``               the cell (configuration, traffic mix),
                                     its end-to-end and per-layer metrics;
  * ``bench/configs/<config>.json``  sizes, kernel, sampler and solver;
  * ``bench/traffic/<mix>.json``     the mix's parameters, among them the
                                     unit of work the window repeats;
  * ``bench/units/<unit>.py``        that unit: its set-up, one unit of work,
                                     its end-to-end values, and the
                                     comparison with the plain reference;
  * ``bench/limits/<cell>.json``     the limit of each number compared;
  * ``bench/data/<generator>.py``    the data, made on the device from the seed;
  * ``bench/metrics/<metric>.py``    one reader per per-layer metric.

A metric split by cell so that each part keeps a bound of its own
(``fit_s`` and ``fit_s.msd``) is one quantity: the part is read by the
reader, or reported from the unit's value, of its name's longest dotted
prefix that has one (``by_prefix``).

A unit of work is one call into the system's public API (``repro.api``)
with ``backend=None``. Set-up makes the data, runs the unit's own set-up
and then the unit once, so that every program the window runs is compiled
before it opens. The window repeats the unit while less than ``seconds``
have passed since it opened, and closes when the last unit that started
has finished. After it, the peak device memory is read, the unit's last
result is compared with the plain reference (``bench/reference.py``), and,
in a traced run, the per-layer metrics are read from the trace.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from collections import Counter

import jax
import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import flops  # noqa: E402
import trace as tracing  # noqa: E402

#: Fired around every compile request, whether XLA compiles or the
#: persistent cache answers it; the cache-hit event tells the two apart.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class CompileCounter:
    """Counts, by phase, compile requests and the persistent-cache loads
    that answered some of them; the rest were compiled."""

    def __init__(self):
        self.phase = "setup"
        self.counts: Counter = Counter()
        self.compiled: dict[str, list] = {}
        jax.monitoring.register_event_duration_secs_listener(self._request)
        jax.monitoring.register_event_listener(self._hit)

    def _request(self, name, _secs, fun_name="?", **_kw):
        if name == COMPILE_EVENT:
            self.counts[(self.phase, "requests")] += 1
            self.compiled.setdefault(self.phase, []).append(fun_name)

    def _hit(self, name, **_kw):
        if name == CACHE_HIT_EVENT:
            self.counts[(self.phase, "cache_loads")] += 1

    def loads(self, phase: str) -> int:
        return self.counts[(phase, "cache_loads")]

    def compiles(self, phase: str) -> int:
        return self.counts[(phase, "requests")] - self.loads(phase)


@dataclasses.dataclass
class Cell:
    """What one run needs to know about its cell."""

    name: str
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list


def find_cell(root: str, workload: str) -> Cell:
    spec = load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(root, configs[w["config"]]["file"])
    traffic = load_json(BENCH, "traffic", w["traffic"] + ".json")
    limits = load_json(BENCH, "limits", workload + ".json")
    e2e = [m for m in spec["end_to_end"] if workload in m.get("workloads", [workload])]
    moved = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if workload in m.get("workloads", [workload] if m["moves"] in moved else [])]
    return Cell(workload, config, traffic, limits, e2e, layer)


def make_data(config: dict, seed: int):
    """(x, y, x_test, y_test) on the device, from one jitted draw."""
    data = config["data"]
    gen = load_module(os.path.join(BENCH, "data", data["generator"] + ".py"),
                      "bench_data_" + data["generator"])
    n, n_test = data["n_train"], data["n_test"]
    xa, ya = jax.jit(gen.generate, static_argnums=(0, 1))(n + n_test, data["d"],
                                                        np.uint32(seed))
    split = jax.jit(lambda a, b: (a[:n], b[:n], a[n:], b[n:]))
    out = split(xa, ya)
    jax.block_until_ready(out)
    return out


def load_unit(api, cell: Cell, data, seed: int):
    """The unit of work the cell's traffic mix names, from ``units/<unit>.py``."""
    name = cell.traffic["unit"]
    mod = load_module(os.path.join(BENCH, "units", name + ".py"), "bench_unit_" + name)
    return mod.Unit(api, cell, data, seed)


def verdict(values: dict, limits: dict) -> tuple[dict, bool]:
    """Each number compared beside its limit, and whether every one keeps
    to it."""
    checks = {name: {"value": value, "limit": limits[name]} for name, value in values.items()}
    return checks, all(c["value"] <= c["limit"] for c in checks.values())


def judge(unit, limits: dict) -> tuple[dict, bool]:
    """Compare the unit's last result with the plain reference."""
    prog = unit.program_outputs()
    return verdict(unit.compare(prog, unit.reference_outputs(prog)), limits)


# ---------------------------------------------------------------------------
# Per-layer metrics
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Context:
    """What a per-layer metric may read."""

    unit: str
    trace: tracing.Reduced
    shapes: dict
    units: int
    unit_s: float
    peak: dict
    flops: object = flops


def by_prefix(name: str, have) -> str | None:
    """``name`` if ``have`` holds it, else the longest dotted prefix of
    ``name`` that it holds. A metric split by cell, so that each part has
    a bound of its own (``fit_s.msd``), is the quantity of its prefix
    (``fit_s``): read by the same reader, or reported from the same value."""
    while name not in have:
        if "." not in name:
            return None
        name = name.rsplit(".", 1)[0]
    return name


def reader(name: str):
    """The reader of a per-layer metric, from ``metrics/<name>.py`` or the
    file of the longest dotted prefix of ``name`` that has one."""
    files = {f[:-3] for f in os.listdir(os.path.join(BENCH, "metrics")) if f.endswith(".py")}
    own = by_prefix(name, files)
    if own is None:
        raise FileNotFoundError(f"no reader for metric {name!r} in {BENCH}/metrics")
    return load_module(os.path.join(BENCH, "metrics", own + ".py"),
                       "bench_metric_" + own.replace(".", "_"))


def read_metrics(metrics: list, ctx: Context) -> dict:
    out = {}
    for m in metrics:
        value = reader(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------


def device_info(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool, *, t_start: float,
             counter: CompileCounter, keep_trace: str | None = None,
             log=print) -> dict:
    """Set up, measure, check; returns the result object of the run."""
    import repro.api as api

    devices = jax.devices()
    peak = flops.peak(devices[0].device_kind) if devices[0].platform == "tpu" else None
    kind = cell.traffic["unit"]
    unit = load_unit(api, cell, make_data(cell.config, seed), seed)
    unit.setup()
    unit.run()  # warm-up: the window's own unit, with its own settings
    setup_s = time.perf_counter() - t_start
    counter.phase = "window"

    tdir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    if traced:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    attempted = failed = 0
    w0 = time.perf_counter()
    while attempted == 0 or time.perf_counter() - w0 < seconds:
        attempted += 1
        try:
            with jax.profiler.TraceAnnotation(f"bench.{kind}"):
                unit.run()
        except Exception as e:  # a unit that raises is counted, not fatal
            failed += 1
            log(f"unit {attempted} failed: {type(e).__name__}: {e}")
    window_s = time.perf_counter() - w0
    if traced:
        jax.profiler.stop_trace()
    counter.phase = "after"
    log(f"window: {attempted} units in {window_s:.6f} s; compiles inside it "
        f"{counter.compiles('window')}, programs loaded from the persistent cache "
        f"inside it {counter.loads('window')} "
        f"({sorted(set(counter.compiled.get('window', [])))}); set-up compiles "
        f"{counter.compiles('setup')}, set-up cache loads {counter.loads('setup')}")

    device = device_info(devices)
    shapes = unit.shapes() if unit.last is not None else None
    checks, correct = {}, False
    if unit.last is not None and failed == 0:
        checks, correct = judge(unit, cell.limits)

    if traced:
        red = tracing.reduce(tracing.find_xplane(tdir), f"bench.{kind}")
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(tracing.find_xplane(tdir), keep_trace)
        shutil.rmtree(tdir, ignore_errors=True)
        ctx = Context(kind, red, shapes or {}, attempted, window_s / attempted, peak)
        metrics = read_metrics(cell.per_layer, ctx)
        device.update(busy_s=red.busy_s(), window_s=red.window_s)
        breakdown = {"device_ops": red.top_ops(10), "idle_gaps": red.idle_gaps(10)}
    else:
        values = dict(unit.end_to_end(window_s, attempted), setup_s=setup_s)
        metrics = {m["name"]: {"value": values[by_prefix(m["name"], values)], "unit": m["unit"]}
                   for m in cell.end_to_end if by_prefix(m["name"], values)}
        breakdown = None
    result = {"correct": bool(correct), "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result
