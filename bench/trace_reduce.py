"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

    python bench/trace_reduce.py <trace.xplane.pb> [--kernel REGEX]

The traced window is the host span named ``bench.window`` that the harness
opens around the measured calls.  Inside it:

* busy time: per device, the union of the intervals in which an operation
  ran (the device plane's op line), averaged over the devices;
* device operations: the summed duration of each op name;
* kernel time: the summed duration, and count, of the ops whose name
  matches a kernel's pattern;
* idle gaps: each stretch of the window in which no op ran on the device,
  named by the innermost ``bench.*`` host span that covers its middle, and
  summed per name.

Only ``jax.profiler.ProfileData`` is needed to read the file.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
from collections import defaultdict

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
OP_LINES = ("XLA Ops",)


def _events(line):
    for ev in line.events:
        yield ev.name, float(ev.start_ns), float(ev.duration_ns)


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _device_planes(data):
    planes = [p for p in data.planes if p.name.startswith("/device:")
              and "CPU" not in p.name]
    return [p for p in planes
            if any(ln.name in OP_LINES for ln in p.lines)]


def host_spans(data):
    """(name, start, end) of every ``bench.*`` span on the host."""
    spans = []
    for p in data.planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            for name, s, d in _events(ln):
                if name.startswith(SPAN_PREFIX):
                    spans.append((name, s, s + d))
    return spans


def reduce_trace(path, kernels: dict | None = None) -> dict:
    """Window, busy time, op totals, kernel totals and idle gaps, in
    seconds.  ``kernels`` maps a kernel name to a regex over op names."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    spans = host_spans(data)
    win = [(s, e) for n, s, e in spans if n == WINDOW]
    if not win:
        raise ValueError(f"{path}: no host span named {WINDOW!r}")
    w0, w1 = min(s for s, _ in win), max(e for _, e in win)
    planes = _device_planes(data)
    if not planes:
        raise ValueError(f"{path}: no device plane with an op line")
    ops = defaultdict(float)
    kern = {k: [0, 0.0] for k in (kernels or {})}
    pats = {k: re.compile(v) for k, v in (kernels or {}).items()}
    busy_total, gaps = 0.0, defaultdict(float)
    inner = [(n, s, e) for n, s, e in spans if n != WINDOW]
    for p in planes:
        iv = []
        for ln in p.lines:
            if ln.name not in OP_LINES:
                continue
            for name, s, d in _events(ln):
                s0, e0 = max(s, w0), min(s + d, w1)
                if e0 <= s0:
                    continue
                iv.append((s0, e0))
                ops[name] += (e0 - s0)
                for k, pat in pats.items():
                    if pat.search(name):
                        kern[k][0] += 1
                        kern[k][1] += (e0 - s0)
        busy = _union(iv)
        busy_total += sum(e - s for s, e in busy)
        edges = [w0] + [x for b in busy for x in b] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps[_label(inner, 0.5 * (s + e))] += e - s
    n = len(planes)
    ns = 1e-9
    return {
        "window_s": (w1 - w0) * ns,
        "busy_s": busy_total / n * ns,
        "devices": [p.name for p in planes],
        "device_ops": sorted(([k, v / n * ns] for k, v in ops.items()),
                             key=lambda kv: -kv[1]),
        "idle_gaps": sorted(([k, v / n * ns] for k, v in gaps.items()),
                            key=lambda kv: -kv[1]),
        "kernels": {k: {"count": c // n, "seconds": t / n * ns}
                    for k, (c, t) in kern.items()},
    }


def _label(spans, t) -> str:
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0][len(SPAN_PREFIX):] if best else "harness"


def find_trace(directory) -> str:
    from pathlib import Path
    found = sorted(Path(directory).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return str(found[-1])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace")
    ap.add_argument("--kernel", default=None,
                    help="regex of the kernel's op names")
    a = ap.parse_args(argv)
    out = reduce_trace(a.trace, {"kernel": a.kernel} if a.kernel else None)
    out["device_ops"] = out["device_ops"][:20]
    json.dump(out, sys.stdout, indent=1)
    print()
    return 0


if __name__ == "__main__":
    sys.exit(main())
