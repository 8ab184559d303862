"""Run one benchmark cell once; the last line of standard output is the
result as JSON.  See ``harness.py``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
"""
import os
import sys
import time


def _process_start() -> float:
    """``time.perf_counter()`` at the moment this process was started."""
    now = time.perf_counter()
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
    except (OSError, ValueError, IndexError):
        return now
    return now - max(0.0, uptime - ticks / os.sysconf("SC_CLK_TCK"))


if __name__ == "__main__":
    T_START = _process_start()
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path[:0] = [here, os.path.join(os.path.dirname(here), "src")]
    import harness

    sys.exit(harness.main(t_start=T_START))
