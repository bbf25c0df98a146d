"""How testdata/scoped_trace.xplane.pb was made (on one v5e chip, PR 24):

    python3 benchmark/testdata/record_scoped_trace.py <out_dir>

Six steps of a small jitted train step whose operations lie under
named scopes — `block/proj` and `block/out` (two nested scopes, forward
and, through the gradient, backward), `loss` (which also reduces a
2048 x 2048 f32 table no matmul touches, so that XLA cannot fuse all
of it into a matmul) and `optimizer` (that table's update is a fusion
of its own) — each step under the program's own phase spans, written
through `Telemetry.timed` with the bus off (`ff:train_step` holding
`ff:dispatch`, which carries arguments, and `ff:fetch`), a host sleep
between steps under `bench:generator_sleep`, all inside `bench:window`.
A trace with known structure, small enough to keep:
check_program_trace.py reads it. (As recorded, XLA still fused every
operation of `loss` into fusions rooted under `block` or `optimizer`: a
fusion carries one name, its root's, and the check says so.)
"""
import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))
from flexflow_tpu.utils.telemetry import telemetry_for  # noqa: E402

KV_BYTES = 1_000_000        # dispatch span i carries KV_BYTES + i


def step(w, x):
    def loss_of(w):
        with jax.named_scope("block"):
            with jax.named_scope("proj"):
                h = jnp.tanh(x @ w["proj"])
            with jax.named_scope("out"):
                y = h @ w["out"]
        with jax.named_scope("loss"):
            return jnp.mean(jnp.square(y.astype(jnp.float32))) \
                + 1e-4 * jnp.mean(jnp.square(w["table"]))
    loss, grads = jax.value_and_grad(loss_of)(w)
    with jax.named_scope("optimizer"):
        return loss, jax.tree_util.tree_map(
            lambda a, g: a - 0.01 * g.astype(a.dtype), w, grads)


def main(out: str) -> None:
    os.makedirs(out, exist_ok=True)
    f = jax.jit(step)
    key = jax.random.PRNGKey(0)
    w = {k: jax.random.normal(key, (1024, 1024), jnp.bfloat16) * 0.03
         for k in ("proj", "out")}
    w["table"] = jax.random.normal(key, (2048, 2048), jnp.float32)
    x = jax.random.normal(key, (1024, 1024), jnp.bfloat16)
    loss, w = f(w, x)
    loss.block_until_ready()
    timed, track = telemetry_for().timed, ("train", "dispatch")
    tmp = os.path.join(out, "_trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench:window"):
        for i in range(6):
            with timed(track, "train_step"):
                with timed(track, "dispatch",
                           {"step": i, "kv_bytes": KV_BYTES + i}):
                    loss, w = f(w, x)
                with timed(track, "fetch"):
                    loss.block_until_ready()
            with jax.profiler.TraceAnnotation("bench:generator_sleep"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                 "*.xplane.pb"))[0]
    dst = os.path.join(out, "scoped_trace.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(tmp)
    print("wrote", os.path.getsize(dst), "bytes")


if __name__ == "__main__":
    main(sys.argv[1])
