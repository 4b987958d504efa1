"""Run one cell of the chip benchmark.

    python3 benchmarks/chip/run.py --workload danube4l.k1.t256 \
        --seed 7 --seconds 30 --trace 0

Prints one JSON object as the last line of stdout (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
also ``breakdown``, and last ``checks``: each number compared beside its
limit).  Exits non-zero and prints no result off a TPU, with fewer chips
than the cell asks for, without the program beside it, or when the run
left the chip's path.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import harness
    try:
        import repro  # noqa: F401
    except ImportError:
        raise harness.Refused("the program (src/repro) is not in this "
                              "checkout")
    result = harness.run(args.workload, args.seed, args.seconds,
                         bool(args.trace), T_START)
    harness.report(result)


if __name__ == "__main__":
    main()
