"""Record the small TPU trace that ``tests/test_trace_reduce.py`` reads.

    python3 benchmarks/tpu/testdata/record_trace.py   # on one chip

Runs a few jitted programs inside the harness's window annotation, with
one host-side pause between them, and writes ``tpu_window.xplane.pb``
beside this file.
"""
import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
import trace_reduce  # noqa: E402

if jax.devices()[0].platform != "tpu":
    sys.exit("needs a TPU")
out = os.path.join(HERE, "_trace")
shutil.rmtree(out, ignore_errors=True)
f = jax.jit(lambda x: jnp.sort(x * 2.0 + 1.0))
g = jax.jit(lambda x: (x @ x.T).sum())
x = jnp.arange(1 << 20, dtype=jnp.float32)
y = jnp.ones((1024, 1024), jnp.float32)
f(x).block_until_ready()
g(y).block_until_ready()
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
jax.profiler.start_trace(out, profiler_options=opts)
with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
    for _ in range(3):
        f(x).block_until_ready()
    time.sleep(0.05)  # an idle gap of at least 50 ms
    for _ in range(3):
        g(y).block_until_ready()
jax.profiler.stop_trace()
src = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)[0]
shutil.copy(src, os.path.join(HERE, "tpu_window.xplane.pb"))
shutil.rmtree(out)
print(trace_reduce.reduce_file(os.path.join(HERE, "tpu_window.xplane.pb")))
