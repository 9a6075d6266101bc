"""Serve-path smoke per model family: every registered arch either
serves end-to-end through a reduced ServeEngine or is skipped with the
concrete API gap that makes it unservable.

One parametrized case per ``list_archs()`` entry, so adding an arch to
the registry automatically adds its serve obligation (or forces an
explicit skip entry here).  Each served arch also exercises the request
span ledger: one completed span whose phase components reconcile to its
end-to-end latency.
"""

import numpy as np
import pytest

from repro.configs import get_config, list_archs
from repro.launch.serve import ServeEngine

#: families the text serve path cannot drive yet, with the exact reason
UNSERVABLE = {
    "vlm": ("prefill requires the vision 'patches' input the text-only "
            "serve path does not synthesize"),
}


@pytest.mark.parametrize("arch", list_archs())
def test_family_serves_reduced(arch):
    cfg = get_config(arch).reduced()
    if cfg.family in UNSERVABLE:
        pytest.skip(f"{arch} ({cfg.family}): {UNSERVABLE[cfg.family]}")
    eng = ServeEngine(cfg, max_batch=2, max_len=16, seed=0)
    eng.register_tenant("t", 2)
    prompt = np.arange(1, 5, dtype=np.int32) % cfg.vocab
    rid = eng.submit("t", prompt)
    out = eng.run(max_new_tokens=2)
    assert len(out[rid]) == 2
    assert all(0 <= t < cfg.vocab for t in out[rid])

    tel = eng.manager.telemetry
    assert tel.spans.totals == {"complete": 1}
    assert tel.spans.open_count() == 0
    sp = tel.spans.closed[-1]
    assert sum(sp.components().values()) == sp.e2e_cycles


def test_unservable_reasons_are_current():
    """The skip table must not go stale: every listed family still
    exists in the registry, and every family is either served by the
    parametrized case above or listed with a reason."""
    families = {get_config(a).family for a in list_archs()}
    assert set(UNSERVABLE) <= families
    assert families - set(UNSERVABLE) >= {"dense", "moe", "ssm", "hybrid",
                                          "encdec"}
