"""What the traffic generators share, and the lookup of a generator by name.

A traffic file (``bench/traffic/<name>.json``) names its ``generator`` and
holds every parameter of the mix.  The generator is a file of its own,
``bench/generators/<generator>.py``, whose class ``Generator`` builds the
inputs from the seed, drives one entry point of the program, counts the work
and keeps what the correctness check needs.  A new entry point or arrival
process is a new file there: nothing here or in the harness knows a
generator, a mix or a cell by name.

Every generator has the same shape:

* ``__init__(cell, seed)``: set-up — the chip, the inputs, and a warm-up of
  exactly the shapes the window will use; it times its parts in
  ``self.phases`` (a ``Phases``) and describes its program in ``self.info``;
* ``run(seconds)``: the measured window, one unit of work at a time (a
  call, a training run, a pass of the serving loop);
* ``end_to_end(elapsed)``, ``attempted()`` and ``counters()``: what the
  window did; optionally ``layer_counters()`` (numbers for per-layer
  readers) and ``work()`` (operations and HBM bytes of the window's sweeps,
  from ``bench/roofline.py``);
* ``free()`` then ``check()``: drop the program's state, then compare what
  the window produced with the plain reference (``bench/reference.py``).

Keys: every random draw comes from ``keys(seed, *names)``, so the same seed
gives the same inputs.
"""
from __future__ import annotations

import contextlib
import importlib.util
import math
import statistics
import sys
import time
import zlib
from pathlib import Path

import numpy as np

import reference as ref


def _module(path: Path, name: str):
    if name in sys.modules and getattr(sys.modules[name], "__file__",
                                       None) == str(path):
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def generator_module(root: Path, name: str):
    """The module ``<root>/bench/generators/<name>.py``."""
    path = root / "bench" / "generators" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no generator {name!r}: {path} is missing")
    return _module(path, "bench_generator_" + name.replace("-", "_"))


def generator(cell, seed: int):
    """Set up the generator that the cell's traffic names."""
    mod = generator_module(cell.root, cell.traffic["generator"])
    return mod.Generator(cell, seed)


class Phases(dict):
    """Seconds of named set-up phases: ``with phases("chip"): ...``."""

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self[name] = self.get(name, 0.0) + time.perf_counter() - t0


class Longest:
    """The ``k`` longest timed calls of a window: (wall ms, thread CPU ms,
    *tags), to tell a host or device stall (CPU far below wall) from long
    work."""

    def __init__(self, k: int = 5):
        self.k, self.items = k, []

    @contextlib.contextmanager
    def __call__(self, *tags):
        w0, c0 = time.perf_counter(), time.thread_time()
        try:
            yield
        finally:
            ms = 1e3 * (time.perf_counter() - w0)
            if len(self.items) < self.k or ms > self.items[-1][0]:
                cpu = 1e3 * (time.thread_time() - c0)
                self.items.append((round(ms, 3), round(cpu, 3), *tags))
                self.items = sorted(self.items, key=lambda x: -x[0])[:self.k]


def keys(seed: int, *names) -> np.random.Generator:
    """A generator for one named stream of the run's seed."""
    tags = [zlib.crc32(str(n).encode()) for n in names]
    return np.random.default_rng([int(seed) % (2 ** 63)] + tags)


def jkey(seed: int, *names):
    import jax
    return jax.random.PRNGKey(int(keys(seed, *names).integers(0, 2 ** 31)))


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation("bench." + name)


def hw_dict(cfg: dict) -> dict:
    return {k: float(v) for k, v in cfg["hardware"].items()}


def program_graph(cfg: dict):
    from repro.core.chimera import make_chimera
    return make_chimera(cfg["cell_rows"], cfg["cell_cols"],
                        masked_cells=[tuple(c) for c in cfg["masked_cells"]])


def machine(cfg: dict, key):
    from repro.core.cd import PBitMachine
    from repro.core.hardware import HardwareConfig
    return PBitMachine.create(
        program_graph(cfg), key, HardwareConfig(**hw_dict(cfg)),
        noise=cfg["noise"], backend=cfg["backend"],
        w_scale=float(cfg["w_scale"]), beta=float(cfg["beta"]))


def ref_graph(cfg: dict) -> ref.Graph:
    return ref.chimera(cfg["cell_rows"], cfg["cell_cols"],
                       cfg["masked_cells"])


def ref_programmer(g, cfg, *, per_pair: bool):
    """The reference's programming of graph ``g``, jitted:
    (chip, J codes, h codes) -> slot couplings and per-spin constants."""
    import jax
    hw, ws = hw_dict(cfg), float(cfg["w_scale"])
    return jax.jit(lambda chip, J, h: ref.program(g, chip, hw, ws, J, h,
                                                  per_pair=per_pair))


def roofline():
    """The module ``bench/roofline.py`` (the algorithm's counts)."""
    return _module(Path(__file__).resolve().parent / "roofline.py",
                   "bench_roofline")


class Reservoir:
    """A uniform sample of ``k`` items from a stream (algorithm R),
    drawn from the seed."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k, self.rng, self.items, self.seen = k, rng, [], 0

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(item)
        else:
            j = int(self.rng.integers(0, self.seen))
            if j < self.k:
                self.items[j] = item


def rel_gap(got, want) -> float:
    """Largest |got - want| / |want| over a sequence; a length mismatch
    is a gap of infinity."""
    got, want = list(got), list(want)
    if len(got) != len(want):
        return math.inf
    return max((abs(a - b) / max(abs(b), 1e-12)
                for a, b in zip(got, want)), default=0.0)


def leaf_norm_gap(got, want) -> float:
    """Worst leaf's gap between the program's norm and the reference's,
    over the larger of that leaf's reference norm and the median leaf's."""
    norms = [float(np.linalg.norm(np.asarray(w, np.float64))) for w in want]
    med = statistics.median(norms)
    gaps = []
    for g, w, n in zip(got, want, norms):
        gn = float(np.linalg.norm(np.asarray(g, np.float64)))
        gaps.append(abs(gn - n) / max(n, med, 1e-30))
    return max(gaps)
