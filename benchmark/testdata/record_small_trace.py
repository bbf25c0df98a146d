"""How testdata/small_trace.xplane.pb was made (on one v5e chip, PR 23):

    python3 benchmark/testdata/record_small_trace.py <out_dir>

Six dispatches of a small jitted program (two matmuls and a reduction),
each under a `bench:step` span, with a host sleep between them under
`bench:generator_sleep`, all inside `bench:window`: a trace with known
structure — six busy stretches, gaps that belong to the sleep — small
enough to keep in the repository. check_trace_reduce.py reads it.
"""
import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp

out = sys.argv[1]
os.makedirs(out, exist_ok=True)
f = jax.jit(lambda a, b: jnp.sum(jnp.tanh(a @ b) @ b))
a = jnp.ones((1024, 1024), jnp.bfloat16)
b = jnp.ones((1024, 1024), jnp.bfloat16)
f(a, b).block_until_ready()
tmp = os.path.join(out, "_trace")
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
jax.profiler.start_trace(tmp, profiler_options=opts)
with jax.profiler.TraceAnnotation("bench:window"):
    for _ in range(6):
        with jax.profiler.TraceAnnotation("bench:step"):
            f(a, b).block_until_ready()
        with jax.profiler.TraceAnnotation("bench:generator_sleep"):
            time.sleep(0.002)
jax.profiler.stop_trace()
src = glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))[0]
shutil.copy(src, os.path.join(out, "small_trace.xplane.pb"))
shutil.rmtree(tmp)
print("wrote", os.path.getsize(os.path.join(out, "small_trace.xplane.pb")),
      "bytes")
