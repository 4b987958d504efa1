"""Record the small trace that the trace reduction is tested on.

    python3 benchmarks/chip/tools/record_trace.py OUT.xplane.pb

On a TPU: two jitted programs run five times each, in turn, inside the
harness's window span; before each round the host sleeps 10 ms inside a
``bench/recv_gradient`` span, so the chip idles at least that long.
The test in ``tests/test_bench_yardstick.py`` reads the copy kept in
``tests/data/tiny.xplane.pb``.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp

    import xplane

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace: needs a TPU")

    def square(x):
        return jnp.sin(x) @ x

    def total(x):
        return jnp.sum(jnp.exp(x) * 2.0)

    # named functions: the trace names each module after its function
    square, total = jax.jit(square), jax.jit(total)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    jax.block_until_ready((square(x), total(x)))
    d = tempfile.mkdtemp(prefix="bench-trace-")
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
        for _ in range(5):
            with jax.profiler.TraceAnnotation("bench/recv_gradient"):
                time.sleep(0.010)
            jax.block_until_ready(square(x))
            jax.block_until_ready(total(x))
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    shutil.copy(path, out)
    shutil.rmtree(d, ignore_errors=True)
    s = xplane.summarize(out)
    print("modules", s.module_n, "busy", s.busy_s, "window", s.window_s,
          "idle by span", s.idle_by_span)


if __name__ == "__main__":
    main(sys.argv[1])
