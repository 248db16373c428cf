"""The benchmark harness: one cell, one run, one result line.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Everything that
belongs to it is found by name, as files of their own:

* ``bench/configs/<config>.json``: the deployment (topology, analog model,
  noise, guarantee);
* ``bench/traffic/<traffic>.json``: the mix, whose ``generator`` names the
  file ``bench/generators/<generator>.py`` that drives one entry point of
  the program (see ``bench/load.py``);
* ``bench/metrics/<metric>.py``: one reader per per-layer metric, with
  ``read(ctx)`` returning a number or None.  Where a metric is split by the
  end-to-end metric it moves (``device_idle_share.sample``,
  ``device_idle_share.cd``) and has no file of its own, the reader of the
  name before the first dot (``device_idle_share.py``) reads it.

A run: refuse anything but a TPU with enough chips, set up (counted in
``setup_s``), measure for ``seconds`` with tracing off, or for the
traffic's ``trace_seconds`` under the profiler with ``--trace 1``; read the
peak device
memory, free the program's state, compare what the window produced with the
plain reference, and print the result as the last line of standard output.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


class Refused(RuntimeError):
    """The run cannot be made here (no chip, a missing file)."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    root: Path


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    entry = {w["name"]: w for w in bench["workloads"]}.get(name)
    if entry is None:
        raise Refused(f"no workload {name!r} in {root / 'BENCHMARK.json'}")
    cfgs = {c["name"]: c for c in bench["configs"]}
    conf = cfgs[entry["config"]]
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if m["moves"] in e2e_names and _reports(m, name)]
    return Cell(
        name=name, chips=int(entry["chips"]),
        config=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads(
            (root / "bench" / "traffic" / f"{entry['traffic']}.json")
            .read_text()),
        end_to_end=e2e, per_layer=layer, root=root)


def reader(cell: Cell, metric: str):
    d = cell.root / "bench" / "metrics"
    path = d / f"{metric}.py"
    if not path.is_file():
        path = d / f"{metric.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def devices(chips: int, require_tpu: bool = True):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX platform is {devs[0].platform!r}; "
                      f"this benchmark measures only on the chip")
    if len(devs) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX sees "
                      f"{len(devs)}")
    return devs[:chips]


def peaks(kind: str, root: Path = ROOT) -> dict:
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if kind not in table:
        raise Refused(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


class Tracer:
    """The profiler around the window, with the ``bench.window`` span."""

    def __init__(self, directory: Path):
        import jax
        jax.profiler.start_trace(str(directory))
        self.span = jax.profiler.TraceAnnotation("bench.window")
        self.span.__enter__()

    def stop(self) -> None:
        import jax
        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()


class JaxEvents:
    """JAX's duration events while armed, by name: (count, seconds).
    Backend compilations, persistent-cache reads and traces show here."""

    def __init__(self):
        import jax
        self.by_name, self.armed = {}, False

        def on(event, duration, **kw):
            if self.armed:
                n, s = self.by_name.get(event, (0, 0.0))
                self.by_name[event] = (n + 1, s + duration)

        jax.monitoring.register_event_duration_secs_listener(on)

    @property
    def compiles(self) -> int:
        return sum(n for e, (n, _) in self.by_name.items()
                   if "backend_compile" in e)


def memory_peak(devs) -> int:
    peak = 0
    for d in devs:
        try:
            stats = d.memory_stats() or {}
        except Exception:  # some backends report none
            stats = {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def run(name: str, seed: int, seconds: float, trace: bool, *,
        root: Path = ROOT, require_tpu: bool = True, t_start=None,
        log=sys.stderr) -> dict:
    """One run of one cell; returns the result line as a dict."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = load_cell(name, root)
    t_imports = time.perf_counter()
    import jax
    import load
    devs = devices(cell.chips, require_tpu)
    kind = devs[0].device_kind
    peak = peaks(kind, root) if require_tpu else None
    t_devices = time.perf_counter()
    events = JaxEvents()
    try:
        gen = load.generator(cell, seed)
    except FileNotFoundError as e:
        raise Refused(str(e)) from None
    setup_s = time.perf_counter() - t_start
    phases = {"start": t_imports - t_start, "jax": t_devices - t_imports,
              **gen.phases}
    print(f"[{name}] seed {seed}: {len(devs)} x {kind}, compile cache "
          f"{jax.config.jax_compilation_cache_dir}, set-up {setup_s:.3f} s "
          f"{ {k: round(v, 3) for k, v in phases.items()} }, "
          f"program {gen.info}", file=log, flush=True)

    trace_dir = root / ".bench_traces" / f"{name}.{seed}"
    if trace:
        # a traced run's window is the traced span: the first
        # trace_seconds, with the profiler stopped after the window
        seconds = min(seconds, cell.traffic.get("trace_seconds", 3))
    events.armed = True
    tracer = Tracer(trace_dir) if trace else None
    t0 = time.perf_counter()
    gen.run(seconds)
    elapsed = time.perf_counter() - t0
    if tracer is not None:
        tracer.stop()
    events.armed = False
    e2e = gen.end_to_end(elapsed)
    e2e["setup_s"] = setup_s
    attempted, failed = gen.attempted()
    layer_counters = getattr(gen, "layer_counters", dict)()
    mem = memory_peak(devs)
    jax_events = {k: (n, round(t, 4)) for k, (n, t) in
                  events.by_name.items()}
    print(f"[{name}] window {elapsed:.3f} s, {gen.counters()}, "
          f"compilations in window {events.compiles}, JAX events "
          f"{jax_events}, peak device memory {mem} B, {layer_counters}",
          file=log, flush=True)

    breakdown = None
    if trace:
        from trace_reduce import find_trace, reduce_trace
        red = reduce_trace(find_trace(trace_dir),
                           cell.traffic.get("kernels", {}))
        work = getattr(gen, "work", lambda: None)()
        ctx = {"trace": red, "counters": gen.counters(),
               "layer": layer_counters, "peak": peak, "work": work}
        metrics = {}
        for m in cell.per_layer:
            v = reader(cell, m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = {"device_ops": red["device_ops"][:10],
                     "idle_gaps": red["idle_gaps"][:10]}
        print(f"[{name}] trace: window {red['window_s']} s, busy "
              f"{red['busy_s']} s, kernels {red['kernels']}", file=log,
              flush=True)
    else:
        metrics = {}
        for m in cell.end_to_end:
            if m["name"] not in e2e:
                raise RuntimeError(f"generator gave no {m['name']!r}")
            metrics[m["name"]] = {"value": e2e[m["name"]],
                                  "unit": m["unit"]}

    gen.free()
    import gc
    gc.collect()
    readings = gen.check()
    limits = cell.traffic["limits"]
    checks = {k: {"value": readings[k], "limit": limits[k]}
              for k in limits}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": mem}
    if trace:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    return line
