"""Chip smoke test: serve stablelm-3b at its published widths on one TPU.

Drives the serving path a user calls -- ``make_shared_manager``,
``ServeEngine(paged=True)`` and ``serve_continuous`` -- at the full
widths of ``configs/stablelm_3b.py`` (32 layers, d_model 2560, 32 heads
of 80, d_ff 6912, vocab 50304, bf16 params) with random weights made from
``SEED``.  Three tenants submit six 16-token prompts with staggered
arrivals; each request asks for 8 tokens.  Checks:

* every request returns its 8 token ids, all in ``[0, vocab)``;
* a cold one-token request gives the same first token as the same prompt
  served in the busy batch;
* two of the requests, served again alone on the same engine, give the
  same tokens as in the busy batch (the isolation invariant that
  ``tests/test_continuous.py`` pins on the CPU).

Run from the checkout root, on a machine with one TPU:

    python3 chip_smoke.py

The last line of standard output is one JSON object naming the device.
When the first device is not a TPU, or any check fails, the script exits
non-zero without that line.
"""

from __future__ import annotations

import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import place_compile_cache  # noqa: E402

ARCH = "stablelm-3b"
SEED = 0
MAX_BATCH = 8
MAX_LEN = 256
PROMPT_LEN = 16          # one prompt length: one prefill shape
MAX_NEW = 8
N_REQUESTS = 6
TENANTS = ("t0", "t1", "t2")
TENANT_PAGES = 16        # virtual pages per tenant extent (4 requests)
SOLO = (1, 4)            # requests served again alone


def fail(msg: str) -> int:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr)
    return 1


def main() -> int:
    cache_dir = place_compile_cache()
    import jax
    import numpy as np

    devices = jax.devices()
    dev = devices[0]
    print(f"jax {jax.__version__}")
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}")
    if dev.platform != "tpu":
        return fail(f"first device is {dev.platform!r}, not a TPU")
    print(f"compile cache: {cache_dir}")

    from repro.configs import get_config
    from repro.launch.serve import (
        ServeEngine,
        make_shared_manager,
        serve_continuous,
    )

    cfg = get_config(ARCH)
    print(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads x {cfg.head_dim}, d_ff {cfg.d_ff}, "
          f"vocab {cfg.vocab}, params {cfg.dtype}")
    t_setup = time.perf_counter()
    mgr = make_shared_manager(1, max_batch=MAX_BATCH, max_len=MAX_LEN,
                              paged=True)
    eng = ServeEngine(cfg, max_batch=MAX_BATCH, max_len=MAX_LEN,
                      manager=mgr, paged=True, seed=SEED)
    for t in TENANTS:
        eng.register_tenant(t, TENANT_PAGES)
    jax.block_until_ready(eng.params)
    print(f"set-up (params + pool on the device): "
          f"{time.perf_counter() - t_setup:.3f} s")

    rng = np.random.default_rng(SEED)
    prompts = rng.integers(0, cfg.vocab, size=(N_REQUESTS, PROMPT_LEN),
                           dtype=np.int32)

    # cold start: one request, one token -- the prefill step compiles here
    t0 = time.perf_counter()
    rid = eng.submit(TENANTS[0], prompts[0], max_new=1)
    cold = serve_continuous([eng], max_new_tokens=1)[0].get(rid)
    cold_s = time.perf_counter() - t0
    print(f"cold start (not a metric): first token after {cold_s:.3f} s, "
          f"prefill compile included")
    if cold is None or len(cold) != 1:
        return fail(f"cold request returned {cold!r}")

    # the busy batch: staggered arrivals across three tenants
    rids = [eng.submit(TENANTS[i % len(TENANTS)], prompts[i],
                       max_new=MAX_NEW, arrive=i // 2)
            for i in range(N_REQUESTS)]
    t0 = time.perf_counter()
    out = serve_continuous([eng], max_new_tokens=MAX_NEW)[0]
    busy_s = time.perf_counter() - t0
    served = 0
    for i, r in enumerate(rids):
        toks = out.get(r)
        if toks is None or len(toks) != MAX_NEW:
            return fail(f"request {i} returned {toks!r}, "
                        f"want {MAX_NEW} tokens")
        if not all(0 <= t < cfg.vocab for t in toks):
            return fail(f"request {i} has ids outside [0, {cfg.vocab}): "
                        f"{toks}")
        served += len(toks)
        print(f"request {i} ({TENANTS[i % len(TENANTS)]}, arrive "
              f"{i // 2}): {toks}")
    print(f"busy batch: {N_REQUESTS} requests, {served} tokens in "
          f"{busy_s:.3f} s (decode compile included)")
    if out[rids[0]][0] != cold[0]:
        return fail(f"cold first token {cold[0]} != busy first token "
                    f"{out[rids[0]][0]}")

    for i in SOLO:
        rid = eng.submit(TENANTS[i % len(TENANTS)], prompts[i],
                         max_new=MAX_NEW)
        solo = serve_continuous([eng], max_new_tokens=MAX_NEW)[0].get(rid)
        if solo != out[rids[i]]:
            return fail(f"request {i} alone gave {solo}, in the busy "
                        f"batch {out[rids[i]]}")
        served += len(solo)
        print(f"request {i} alone: same {MAX_NEW} tokens")

    stats = dev.memory_stats() or {}
    print(f"tokens served: {served + len(cold)}")
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
