#!/usr/bin/env python
"""Cluster-scale envelope / chaos soak driver (ROADMAP open item 1).

Thin runnable wrapper over :mod:`ray_tpu._private.envelope` — the same
driver backs ``ray-tpu envelope`` and ``bench_runtime.py
--envelope-smoke``.  Typical runs:

    # The 50-host soak (writes envelope.json unless --out names a file):
    python tools/envelope.py --hosts 50 --actors 10000 --pgs 1000

    # Quick smoke (4 hosts, small everything, one fault):
    python tools/envelope.py --hosts 4 --actors 40 --pgs 8 \
        --broadcast 8:2 --chaos-events 2 --out /tmp/envelope.json
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from ray_tpu._private.envelope import main  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(main())
