"""Record the small TPU trace of three serving pumps that
test_program_spans.py reads.

    python bench/tests/data/record_serve_trace.py <out.xplane.pb>

The chip440.serve_overload generator is set up and a launch of 8 sweeps
on the 1x1 bucket is warmed up.  Then six such requests, two for each of
three problems, are submitted, and three ``SamplerService.pump`` calls
each launch one problem's pair, inside a ``bench.window`` span, each pump
inside ``bench.pump``, as the harness traces them on the chip.  One small
bucket program, and the profiler's Python tracer and HLO protos off, keep
the file under 1 MB.
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

cell = harness.load_cell("chip440.serve_overload")
drv = load.generator(cell, 7)
SWEEPS = 8
t0 = drv.tenants[0]  # on the 1x1 bucket
problems = [{**t0, "J": t0["J"] // k} for k in (1, 2, 3)]
drv.svc.submit(drv._request(problems[0], 2, SWEEPS))
drv.svc.drain()
d = tempfile.mkdtemp()
options = jax.profiler.ProfileOptions()
options.python_tracer_level = 0
options.enable_hlo_proto = False
jax.profiler.start_trace(d, profiler_options=options)
with jax.profiler.TraceAnnotation("bench.window"):
    for p in problems + problems:
        with load.span("submit"):
            drv.svc.submit(drv._request(p, 2, SWEEPS))
    for _ in range(3):
        with load.span("pump"):
            drv.svc.pump()
jax.profiler.stop_trace()
shutil.copy(glob.glob(d + "/**/*.xplane.pb", recursive=True)[0], sys.argv[1])
