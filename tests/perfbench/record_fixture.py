"""Record the small device trace the tests reduce.

    python tests/perfbench/record_fixture.py <out.xplane.pb>

Runs on a TPU host (four chips for `fixtures/v5e_4chips.xplane.pb`):
three calls of one jitted program, a matrix product per chip and a
cross-chip sum of the results, under the benchmark's "bench:window"
annotation. Not collected by pytest, never reached from the
benchmark's command. (`fixtures/v5e_gpt2_train.xplane.pb` is 12 ms cut
out of the trace of a `gpt2-train-seq1024` run, PR 22: the device's
two operation lines and the benchmark's annotations, nothing else.)
"""
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(out):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from perfbench.harness import xplane

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit("record_fixture.py needs a TPU")
    mesh = Mesh(np.array(devices), ("data",))

    def body(q, w):
        return w + jax.lax.psum(jnp.tanh(q[0] @ q[0]) @ q[0], "data")

    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("data"), P()),
                              out_specs=P()))
    q = jnp.full((len(devices), 1024, 1024), 0.01, jnp.bfloat16)
    w = f(q, jnp.zeros((1024, 1024), jnp.bfloat16)).block_until_ready()
    logdir = out + ".dir"
    shutil.rmtree(logdir, ignore_errors=True)
    jax.profiler.start_trace(logdir)
    with jax.profiler.TraceAnnotation("bench:window"):
        for _ in range(3):
            w = f(q, w)
            with jax.profiler.TraceAnnotation("bench:loss read"):
                w.block_until_ready()
    jax.profiler.stop_trace()
    shutil.copy(xplane.newest_xplane(logdir), out)
    shutil.rmtree(logdir)
    print(out, os.path.getsize(out))
    print(xplane.describe(out))


if __name__ == "__main__":
    main(sys.argv[1])
