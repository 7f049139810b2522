#!/usr/bin/env python3
"""One run of one benchmark cell on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells, their configurations, traffic, limits and per-layer readers are
named in ``BENCHMARK.json``.  The last line of stdout is the result as one
JSON object; the last lines of stderr are the numbers of the correctness
check beside their limits.  Without a TPU, or with fewer chips than the
cell asks for, it exits with code 3 and prints no result.
"""
import os
import sys
import time


def _process_age() -> float:
    """Seconds since this process started (Linux), so that set-up counts
    the interpreter's start and the imports too."""
    try:
        with open("/proc/self/stat") as f:
            start = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return max(0.0, up - start / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


T0 = time.perf_counter() - _process_age()
# libtpu logs to a fixed /tmp path unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")
# the checkout's root, not this directory, goes on the path: the package
# is ``bench``, and its module names must not shadow the standard library's
sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T0))
