#!/usr/bin/env python3
"""Run one cell of the benchmark once and print its result line.

  python bench/run.py --workload granite-3-2b.chat --seed 7 --seconds 45 \
      --trace 0

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each number compared beside its limit.
The same checks are the last lines of standard error.  Without a TPU, or
with fewer chips than the cell asks for, it prints no result and exits
with 3; outside a checkout of the repository, with 2.
"""

import time

T_PROCESS = time.monotonic()

import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]

if __name__ == "__main__":
    if not (ROOT / "src" / "repro").is_dir():
        print(f"[bench] {ROOT} holds no src/repro: nothing to measure",
              file=sys.stderr)
        sys.exit(2)
    sys.path[:0] = [str(ROOT / "src")]
    import harness
    sys.exit(harness.main(t_process=T_PROCESS))
