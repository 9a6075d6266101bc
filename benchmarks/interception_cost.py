"""Table 5 — cost of CUDA-call interception per kernel launch.

Paper: lookup 214-900 cycles, augment 300-600 cycles, ~957 cycles total
per cudaLaunchKernel (~10% of a 9000-cycle launch).  Here: nanoseconds
per phase from the GuardianManager's launch-stats instrumentation, plus
the dispatch cost for perspective.
"""

from __future__ import annotations

from typing import List

import jax.numpy as jnp
import numpy as np

from repro.core import FencePolicy, GuardianManager, SharingMode


def main(out: List[str]):
    mgr = GuardianManager(total_slots=4096, mode=SharingMode.TIME_SHARE,
                          policy=FencePolicy.BITWISE,
                          standalone_fast_path=False)
    c = mgr.register_tenant("a", 1024)
    mgr.register_tenant("b", 1024)  # so fencing is active

    def k(arena, ptr, n):
        idx = ptr + jnp.arange(n, dtype=jnp.int32)
        return arena.at[idx].add(1.0), None

    c.module_load("bump", k)
    p = c.malloc(64)
    for _ in range(200):
        c.launch_kernel("bump", ptrs=[p], args=(64,))
    c.synchronize()
    # drop the first (tracing) samples
    stats = mgr.launch_stats
    lookup = float(np.median(list(stats.lookup_ns)[10:]))
    augment = float(np.median(list(stats.augment_ns)[10:]))
    dispatch = float(np.median(list(stats.dispatch_ns)[10:]))
    total = lookup + augment
    out.append(f"table5.lookup_ns,{lookup / 1e3:.3f},paper=214-900cycles")
    out.append(f"table5.augment_ns,{augment / 1e3:.3f},paper=300-600cycles")
    out.append(f"table5.dispatch_ns,{dispatch / 1e3:.3f},"
               "paper_launch=~9000cycles")
    out.append(f"table5.interception_total_ns,{total / 1e3:.3f},"
               f"pct_of_dispatch={100 * total / max(dispatch, 1):.1f}%"
               "(paper:~10%)")
    for line in out[-4:]:
        print(line)


if __name__ == "__main__":
    main([])
