"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs on the machine it is started on and needs the chips the cell names in
``BENCHMARK.json``: with no TPU, or too few, it exits non-zero and prints
no result.  Diagnostics go to standard error, ending with each number the
correctness check compared and its limit; the last line of standard output
is one JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and ``checks`` last).

``--baseline`` instead times each of the cell's served functions through
the server and under plain ``jax.jit`` on the same chip, and prints that
table; it reports no metric.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]
# the TPU runtime would otherwise log under a fixed path in /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--baseline", action="store_true")
    a = p.parse_args(argv)
    os.makedirs(os.path.join(_ROOT, ".bench_out"), exist_ok=True)
    from bench import harness
    try:
        if a.baseline:
            from bench import baseline
            out = baseline.run(a.workload, a.seed, t_process=T_PROCESS)
        else:
            out = harness.run(a.workload, a.seed, a.seconds, bool(a.trace),
                              t_process=T_PROCESS)
    except harness.NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
