"""How ``tests/benchmark/data/small_trace.xplane.pb`` was made (on the
chip, once; run again only if the profiler's format changes):

    python tests/benchmark/record_small_trace.py <output directory>

Three units of a small jitted matrix chain with a 20 ms host sleep
between them, under the spans ``xplane.py`` reads: ``bench/window``
around everything, ``bench/unit`` around each blocking call,
``bench/sleep`` around each sleep.  So the trace must show a device
that is idle for about 60 ms of its window, the idle gaps attributed to
``bench/sleep``, and a dot or fusion as the top operation.
"""

import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import jax.profiler

out = sys.argv[1]
os.makedirs(out, exist_ok=True)


@jax.jit
def chain(x):
    for _ in range(8):
        x = jnp.tanh(x @ x) * 0.01
    return x.sum()


x = jnp.ones((1024, 1024), jnp.bfloat16)
chain(x).block_until_ready()
options = jax.profiler.ProfileOptions()
options.python_tracer_level = 0
options.host_tracer_level = 2
jax.profiler.start_trace(os.path.join(out, "raw"), profiler_options=options)
with jax.profiler.TraceAnnotation("bench/window"):
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench/unit"):
            chain(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench/sleep"):
            time.sleep(0.02)
jax.profiler.stop_trace()
found = glob.glob(os.path.join(out, "raw", "**", "*.xplane.pb"),
                  recursive=True)[0]
shutil.copy(found, os.path.join(out, "small_trace.xplane.pb"))
print(found, os.path.getsize(found), "bytes")
