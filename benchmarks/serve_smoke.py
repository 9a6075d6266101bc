"""Serve-path smoke timings: the three CI serve configurations as bench
rows, so the end-to-end serving hot path (submit -> trusted jit step ->
fused drain -> fence verify) is *gated*, not just exercised.

CI's tier-1 job runs the same three configs via ``repro.launch.serve``
with ``--bench-out``; this suite mirrors them through the same
entrypoint so a local ``benchmarks.run serve_smoke`` reproduces the CI
rows (``serve.smoke.*`` in ``results/bench.csv``) byte-for-byte in
shape.  Per-token wall time includes trace/compile (cold start, fresh
engines per config) — the gate normalizes by the median fresh/baseline
ratio, so only *relative* drift between configs fires it.

Not part of ``--quick``: three cold-start serves are ~a minute of wall
time, and the quick set must stay fast enough to run on every push.
"""

from __future__ import annotations

import os
from typing import List

from repro.launch.serve import main as serve_main

#: name suffix -> serve argv (mirrors .github/workflows/ci.yml tier1)
CONFIGS = [
    ("mixed_policies",
     ["--arch", "stablelm-3b", "--reduced", "--tenants", "3",
      "--requests", "3", "--tokens", "4", "--policies", "modulo,check"]),
    ("baseline",
     ["--arch", "stablelm-3b", "--reduced", "--tenants", "2",
      "--requests", "2", "--tokens", "4"]),
    ("multi_engine",
     ["--arch", "stablelm-3b", "--reduced", "--engines", "2",
      "--tenants", "1", "--requests", "2", "--tokens", "4"]),
]


def main(out: List[str], path: str = "/tmp/serve.smoke.csv") -> None:
    if os.path.exists(path):
        os.remove(path)
    for suffix, argv in CONFIGS:
        serve_main(argv + ["--bench-out", path,
                           "--bench-name", f"serve.smoke.{suffix}"])
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if line:
                out.append(line)
                print(line)


if __name__ == "__main__":
    rows: List[str] = []
    main(rows)
