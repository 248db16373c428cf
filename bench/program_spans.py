"""The program's own spans in a benchmark trace.

    python bench/program_spans.py <trace.xplane.pb>

The program marks its layers with ``repro.*`` host spans
(``src/repro/runtime/spans.py``), on the profiler's clock like the device's
operations.  Inside the harness's ``bench.window`` span this reduction
gives:

* per span name (the event's name without the ``repro.`` prefix and
  anything after a ``#``): count, total time and self time (the time in
  which it is the innermost ``repro.*`` span);
* the device's idle time split exactly: each idle stretch is cut at span
  edges, and each piece goes to the innermost ``repro.*`` span that covers
  it, or to ``outside`` where none does; per device, averaged over the
  devices, as ``trace_reduce.py`` averages busy time;
* the same idle time under each innermost ``bench.*`` span of the harness,
  split by ``repro.*`` span, which says how much of a benchmark label the
  program's spans account for;
* device time per ``XLA Modules`` name, with the ``(<hash>)`` stripped:
  ``jit_<name>`` for the Session's named functions.

Per-layer readers call ``load(ctx)``: the harness passes no trace path, so
it reads the newest trace under ``<root>/.bench_traces`` and returns None
unless that trace's window is the one in ``ctx["trace"]``, or where the
program wrote no ``repro.*`` span (a program without them).
"""
from __future__ import annotations

import functools
import json
import math
import re
import sys
from collections import defaultdict
from pathlib import Path

import trace_reduce as tr

ROOT = Path(__file__).resolve().parent.parent
PREFIX = "repro."
OUTSIDE = "outside"
MODULES = "XLA Modules"
NS = 1e-9


def _name(event_name: str) -> str:
    return event_name.split("#", 1)[0][len(PREFIX):]


def program_spans(data) -> list:
    """(name, start, end) in ns of every ``repro.*`` span on the host."""
    out = []
    for p in data.planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            for name, s, d in tr._events(ln):
                if name.startswith(PREFIX):
                    out.append((_name(name), s, s + d))
    return out


def split(spans, bench, busy, w0, w1):
    """Sweep ``[w0, w1]`` once, cut at every span and busy edge.

    ``spans``, ``bench``: (name, start, end) of the program's and the
    harness's spans; ``busy``: the device's disjoint busy intervals.
    Returns (idle time per innermost program span or ``outside``; self time
    per program span; idle time per innermost bench span and program span,
    ``harness`` where no bench span covers it).  Idle and busy pieces sum
    to the window."""
    events = []
    for fam, group in ((0, spans), (1, bench)):
        for i, (_, s, e) in enumerate(group):
            s, e = max(s, w0), min(e, w1)
            if e > s:
                events += [(s, 1, fam, i), (e, 0, fam, i)]
    for s, e in busy:
        events += [(s, 1, 2, 0), (e, 0, 2, 0)]
    events.sort()
    active = (set(), set())
    nbusy = 0
    idle, self_t = defaultdict(float), defaultdict(float)
    under = defaultdict(lambda: defaultdict(float))
    t = w0

    def innermost(group, act):
        if not act:
            return None
        return group[min(act, key=lambda i: group[i][2] - group[i][1])][0]

    for x, start, fam, i in events + [(w1, 0, 3, 0)]:
        if x > t:
            seg = x - t
            name = innermost(spans, active[0])
            if name is not None:
                self_t[name] += seg
            if not nbusy:
                idle[name or OUTSIDE] += seg
                label = innermost(bench, active[1])
                label = label[len(tr.SPAN_PREFIX):] if label else "harness"
                under[label][name or OUTSIDE] += seg
            t = x
        if fam == 2:
            nbusy += 1 if start else -1
        elif fam < 2:
            (active[fam].add if start else active[fam].discard)(i)
    return idle, self_t, under


def _device_lines(plane):
    ops, modules = [], []
    for ln in plane.lines:
        if ln.name in tr.OP_LINES:
            ops += list(tr._events(ln))
        elif ln.name == MODULES:
            modules += list(tr._events(ln))
    return ops, modules


def reduce_trace(path) -> dict:
    """The program's spans, the idle split and the module times of the
    trace at ``path``, in seconds."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    bench = tr.host_spans(data)
    win = [(s, e) for n, s, e in bench if n == tr.WINDOW]
    if not win:
        raise ValueError(f"{path}: no host span named {tr.WINDOW!r}")
    w0, w1 = min(s for s, _ in win), max(e for _, e in win)
    bench = [b for b in bench if b[0] != tr.WINDOW]
    spans = program_spans(data)
    planes = tr._device_planes(data)
    if not planes:
        raise ValueError(f"{path}: no device plane with an op line")
    idle = defaultdict(float)
    under = defaultdict(lambda: defaultdict(float))
    modules = defaultdict(float)
    for p in planes:
        ops, mods = _device_lines(p)
        busy = tr._union((max(s, w0), min(s + d, w1)) for _, s, d in ops
                         if min(s + d, w1) > max(s, w0))
        i, self_t, u = split(spans, bench, busy, w0, w1)
        for k, v in i.items():
            idle[k] += v
        for label, by in u.items():
            for k, v in by.items():
                under[label][k] += v
        for name, s, d in mods:
            dt = min(s + d, w1) - max(s, w0)
            if dt > 0:
                modules[re.sub(r"\(\d+\)$", "", name)] += dt
    n = len(planes)
    stats = defaultdict(lambda: {"count": 0, "total_s": 0.0})
    kept = []
    for name, s, e in spans:
        s, e = max(s, w0), min(e, w1)
        if e > s:
            stats[name]["count"] += 1
            stats[name]["total_s"] += (e - s) * NS
            kept.append((name, s, e))
    for name, st in stats.items():
        st["self_s"] = self_t.get(name, 0.0) * NS

    def per_device(d):
        return dict(sorted(((k, v / n * NS) for k, v in d.items()),
                           key=lambda kv: -kv[1]))

    return {
        "window_s": (w1 - w0) * NS,
        "spans": dict(stats),
        "idle_s": per_device(idle),
        "idle_in_bench": {k: per_device(v) for k, v in under.items()},
        "modules": per_device(modules),
        "instances": kept,
    }


@functools.lru_cache(maxsize=2)
def _reduce_cached(path: str, mtime_ns: int) -> dict:
    return reduce_trace(path)


def newest_trace(root: Path):
    found = list((root / ".bench_traces").rglob("*.xplane.pb"))
    return max(found, key=lambda p: p.stat().st_mtime_ns) if found else None


def load(ctx):
    """The reduction of the trace whose window ``ctx["trace"]`` read, or
    None (no such trace, another window, or no program spans)."""
    path = newest_trace(ROOT)
    if path is None:
        return None
    red = _reduce_cached(str(path), path.stat().st_mtime_ns)
    if not red["spans"] or not math.isclose(
            red["window_s"], ctx["trace"]["window_s"], rel_tol=0.0,
            abs_tol=1e-9):
        return None
    return red


def program_idle_s(red) -> float:
    """Idle device time under any ``repro.*`` span."""
    return sum(v for k, v in red["idle_s"].items() if k != OUTSIDE)


def nested(red, parent: str, child: str) -> list:
    """(duration, summed duration of ``child`` spans inside it) in seconds
    for each ``parent`` span of the window, by start."""
    inst = sorted(red["instances"], key=lambda x: x[1])
    kids = [(s, e) for n, s, e in inst if n == child]
    out = []
    for n, s, e in inst:
        if n == parent:
            inside = sum(ke - ks for ks, ke in kids if s <= ks and ke <= e)
            out.append(((e - s) * NS, inside * NS))
    return out


def report(metric: str, value, red, top: int = 8, log=sys.stderr) -> None:
    """One line to standard error: the metric, the idle split by program
    span, the idle under each bench span and the device time per
    module."""
    def fmt(d):
        return ", ".join(f"{k} {v:.4g}" for k, v in list(d.items())[:top])

    bench = "; ".join(
        f"{label} {sum(by.values()):.4g} s "
        f"({100 * (1 - by.get(OUTSIDE, 0.0) / sum(by.values())):.1f}% "
        f"under repro.*)"
        for label, by in sorted(red["idle_in_bench"].items(),
                                key=lambda kv: -sum(kv[1].values())))
    count = sum(s["count"] for s in red["spans"].values())
    print(f"{metric}: {value!r}; idle s by span: {fmt(red['idle_s'])}; "
          f"idle s by bench span: {bench}; device s by module: "
          f"{fmt(red['modules'])}; {count} program spans in "
          f"{red['window_s']:.4g} s", file=log, flush=True)


def main(argv=None) -> int:
    path = (argv or sys.argv[1:])[0]
    red = reduce_trace(path)
    red.pop("instances")
    json.dump(red, sys.stdout, indent=1)
    print()
    return 0


if __name__ == "__main__":
    sys.exit(main())
