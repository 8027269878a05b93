"""Small-size runs of the benchmark's cells on the CPU (interpret mode)."""

import json
import time

from bench import harness

SEED = 2**33 + 12345       # wider than 32 bits, as the driver's seeds are
# the smallest traffic that still forms groups and decodes through the cache
SMALL_TRAFFIC = {
    "phi4mini-l1.chat-p128-o16": {"rate": 2.0, "prompt_len": 8,
                                  "output_len": 3, "check_every": 1},
    "yolov3tiny-448.stream": {"rate": 2.0, "images": 4, "check_every": 2},
}


def run_small(workload, seconds=1.5, trace=False, seed=SEED):
    lines = []
    out = harness.run(workload, seed, seconds, trace,
                      t_process=time.monotonic(), allow_cpu=True,
                      small=True, traffic=SMALL_TRAFFIC[workload],
                      log=lines.append)
    json.dumps(out, allow_nan=False)      # the result line is plain JSON
    return out, lines
