"""Records the small device trace that test_trace_reduce.py reads: a few
launches of two tiny jitted programs with gaps between them, python tracer
off. Run once on the chip; writes chiprun_out/small.xplane.pb."""

import glob
import os
import shutil
import time

import jax
import jax.numpy as jnp


@jax.jit
def route_step_stub(x):
    return jnp.sort(x @ x.T, axis=-1)[:, :8]


@jax.jit
def readback_stub(x):
    return x[:3] + 1


def main():
    x = jnp.ones((512, 512), jnp.float32)
    route_step_stub(x).block_until_ready()
    readback_stub(x).block_until_ready()
    out = "chiprun_out/_trace"
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(out, profiler_options=opts)
    for _ in range(5):
        with jax.profiler.TraceAnnotation("host_pause"):
            time.sleep(0.02)
        route_step_stub(x).block_until_ready()
        readback_stub(x).block_until_ready()
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(src, "chiprun_out/small.xplane.pb")
    shutil.rmtree(out)
    print("recorded", os.path.getsize("chiprun_out/small.xplane.pb"), "bytes")


if __name__ == "__main__":
    main()
