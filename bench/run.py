"""Run one benchmark cell once, on the chip the machine holds.

    python3 bench/run.py --workload stablelm3b.chat_offline --seed 7 \\
        --seconds 30 --trace 0

From the root of a checkout.  The cells, metrics and bounds are in
``BENCHMARK.json``; a cell's configuration, the block it names
(``bench/blocks``), its traffic mix, limits and per-layer readers are
files under ``bench/`` found by name.  The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the end-to-end metrics with ``--trace 0``, the
per-layer ones with ``--trace 1``), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number the correctness check
compared, beside its limit.  Without a TPU, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

CHECKOUT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]
# the TPU runtime logs to /tmp/tpu_logs unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import cell

    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    try:
        result = cell.run(bench, args.workload, args.seed, args.seconds,
                          bool(args.trace), T_START)
    except cell.NoChip as e:
        cell.log(f"bench: {e}")
        return 3
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
