"""Record the small chip trace that tests/test_tracing.py reads.

    python3 chipbench/tests/record_trace.py     # on a TPU

Inside a ``bench.window`` span: five calls of one jitted program
(``jit_probe``), each followed by 20 ms of host sleep inside a
``bench.wait`` span.  Writes tests/data/probe.xplane.pb.
"""
import glob
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))


def main() -> None:
    assert jax.devices()[0].platform == "tpu"

    @jax.jit
    def probe(x):
        for _ in range(8):
            x = jnp.tanh(x @ x)
        return x

    x = jnp.ones((1024, 1024), jnp.float32) / 1024
    probe(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(5):
            probe(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    os.makedirs(os.path.join(HERE, "data"), exist_ok=True)
    shutil.copy(src, os.path.join(HERE, "data", "probe.xplane.pb"))
    print(os.path.getsize(src), "bytes")


if __name__ == "__main__":
    main()
