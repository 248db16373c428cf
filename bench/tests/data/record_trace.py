"""Record the small TPU trace that test_trace_reduce.py reads.

    python bench/tests/data/record_trace.py <out.xplane.pb>

Three chip440 sampling calls inside a ``bench.window`` span, traced with
the JAX profiler on the chip.
"""
import glob
import shutil
import sys
import tempfile
from pathlib import Path

REPO = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(REPO / "bench"), str(REPO / "src")]

import jax  # noqa: E402

import harness  # noqa: E402
import load  # noqa: E402

cell = harness.load_cell("chip440.sample")
drv = load.generator(cell, 7)
d = tempfile.mkdtemp()
jax.profiler.start_trace(d)
with jax.profiler.TraceAnnotation("bench.window"):
    for k in range(3):
        drv._call(k)
jax.profiler.stop_trace()
shutil.copy(glob.glob(d + "/**/*.xplane.pb", recursive=True)[0], sys.argv[1])
