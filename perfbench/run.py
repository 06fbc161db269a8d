"""perfbench: one run of one benchmark cell, as the driver calls it.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints, as the last line of standard output, one JSON object with `correct`,
`attempted`, `failed`, `metrics` and `device` (and, with `--trace 1`,
`breakdown`). `--trace 0` reports the cell's end-to-end metrics, `--trace 1`
its per-layer metrics. Exits non-zero and prints no result where JAX finds no
accelerator, fewer chips than the cell asks for, no program to measure, or a
configuration file out of step with its entry (`manifest.config_problems`).

Not for the driver: `--rehearsal` (toy sizes from the files' `rehearsal`
blocks, any backend, numbers never reported as metrics), `--manifest` (a
BENCHMARK.json elsewhere, whose directory may add files), `--set key=json`
(one traffic parameter, for the knee sweep).
"""

import time

T_START = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--manifest", default=None)
    ap.add_argument("--set", action="append", metavar="KEY=JSON")
    return ap.parse_args(argv)


if __name__ == "__main__":
    from perfbench.harness import main
    sys.exit(main(parse(), T_START))
