#!/usr/bin/env python3
"""Record a small profiler trace on the chip, for a test of the reduction.

    python3 bench/record_trace.py OUT.xplane.pb

On the chip: three annotated ``bench.step`` calls of a small jitted
program, each followed by a 2 ms ``bench.wait``, all inside one
``bench.window``, traced with the options of a traced run
(``xtrace.options``): a trace small enough to commit beside the
hand-written one of ``bench/tests/test_trace_reduction.py``, so the
reduction can be checked on a real TPU trace's planes and lines.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    import jax
    import jax.numpy as jnp
    from bench.lib import xtrace
    if jax.default_backend() != "tpu":
        print("record_trace: JAX found no TPU", file=sys.stderr)
        return 3
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum(axis=0))
    x = jnp.ones((1024, 1024), jnp.float32)
    f(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    try:
        with jax.profiler.trace(tmp, profiler_options=xtrace.options()):
            with jax.profiler.TraceAnnotation("bench.window"):
                for _ in range(3):
                    with jax.profiler.TraceAnnotation("bench.step"):
                        f(x).block_until_ready()
                    with jax.profiler.TraceAnnotation("bench.wait"):
                        time.sleep(0.002)
        path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                         recursive=True)[0]
        shutil.copy(path, sys.argv[1])
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"record_trace: wrote {sys.argv[1]} "
          f"({os.path.getsize(sys.argv[1])} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
