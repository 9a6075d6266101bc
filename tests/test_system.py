"""End-to-end behaviour tests: training convergence + restart-exactness,
multi-tenant serving isolation, fence-mode equivalence for honest
workloads."""

import dataclasses
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.fence import FencePolicy
from repro.models import get_model


def test_training_reduces_loss(tmp_path):
    """50 steps of the real train driver on a reduced config learns the
    synthetic grammar."""
    from repro.launch import train as T
    argv = sys.argv
    sys.argv = ["train", "--arch", "stablelm-3b", "--reduced",
                "--steps", "50", "--batch", "4", "--seq", "64",
                "--lr", "5e-3", "--log-every", "100"]
    try:
        summary = T.main()
    finally:
        sys.argv = argv
    assert summary["final_loss"] < summary["first_loss"] - 0.3


def test_training_restart_exact(tmp_path):
    """Checkpoint at step 20, restart, arrive at the same step-40 params
    as an uninterrupted run (fault-tolerance contract)."""
    from repro.data import DataConfig, SyntheticLM
    from repro.checkpoint import CheckpointStore
    from repro.optim import adamw, apply_updates, constant

    cfg = get_config("minicpm-2b").reduced()
    api = get_model(cfg)
    opt = adamw(constant(1e-3))
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=32,
                                  global_batch=4, seed=0))

    @jax.jit
    def step_fn(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: api.loss(p, batch, remat=False))(params)
        u, opt_state = opt.update(grads, opt_state, params)
        return apply_updates(params, u), opt_state, loss

    def run(start, stop, params, opt_state):
        for s in range(start, stop):
            batch = {k: jnp.asarray(v) for k, v in data.batch(s).items()}
            params, opt_state, _ = step_fn(params, opt_state, batch)
        return params, opt_state

    p0 = api.init(jax.random.PRNGKey(0))
    s0 = opt.init(p0)
    # uninterrupted 0..40
    pA, sA = run(0, 40, p0, s0)
    # interrupted at 20 with checkpoint roundtrip
    pB, sB = run(0, 20, p0, s0)
    store = CheckpointStore(str(tmp_path))
    store.save(20, (pB, sB))
    (pB, sB), step = store.restore((pB, sB))
    assert step == 20
    pB, sB = run(20, 40, pB, sB)
    for a, b in zip(jax.tree.leaves(pA), jax.tree.leaves(pB)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-6)


def test_fence_modes_equivalent_for_honest_tenant():
    """For in-partition workloads, BITWISE / MODULO / CHECK / native all
    produce identical losses — the fences are semantic no-ops (§4.4)."""
    from repro.launch.steps import make_guard
    from repro.configs import ShapeConfig

    cfg = get_config("qwen3-moe-30b-a3b").reduced()
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0,
                              cfg.vocab)
    shape = ShapeConfig("t", "train", 32, 2)
    losses = {}
    for name, policy, enabled in [
            ("native", FencePolicy.BITWISE, False),
            ("bitwise", FencePolicy.BITWISE, True),
            ("modulo", FencePolicy.MODULO, True),
            ("check", FencePolicy.CHECK, True)]:
        guard = make_guard(cfg, shape, policy, enabled)
        losses[name] = float(api.loss(params, {"tokens": toks},
                                      guard=guard, remat=False))
    base = losses["native"]
    for name, v in losses.items():
        assert abs(v - base) < 1e-5, losses


def test_serve_engine_multi_tenant_isolation():
    """Two tenants share the engine; tenant B's requests do not perturb
    tenant A's generations (vs A running alone)."""
    from repro.launch.serve import ServeEngine

    cfg = get_config("stablelm-3b").reduced()
    rng = np.random.default_rng(0)
    prompt_a = rng.integers(0, cfg.vocab, size=12).astype(np.int32)
    prompt_b = rng.integers(0, cfg.vocab, size=12).astype(np.int32)

    # A alone
    eng1 = ServeEngine(cfg, max_batch=4, max_len=128)
    eng1.register_tenant("a", 2)
    rid_a1 = eng1.submit("a", prompt_a)
    out1 = eng1.run(max_new_tokens=8)[rid_a1]

    # A + B co-located
    eng2 = ServeEngine(cfg, max_batch=4, max_len=128)
    eng2.register_tenant("a", 2)
    eng2.register_tenant("b", 2)
    rid_a2 = eng2.submit("a", prompt_a)
    rid_b = eng2.submit("b", prompt_b)
    out2 = eng2.run(max_new_tokens=8)
    assert out2[rid_a2] == out1, "tenant B perturbed tenant A"


def test_serve_guard_blocks_forged_slots():
    """A forged slot id (scheduler compromise) wraps inside the owner's
    partition: the victim tenant's cache rows stay untouched."""
    from repro.launch.serve import ServeEngine

    cfg = get_config("stablelm-3b").reduced()
    eng = ServeEngine(cfg, max_batch=8, max_len=128)
    vp = eng.register_tenant("victim", 4)
    eng.register_tenant("attacker", 4)
    rng = np.random.default_rng(1)
    rid_v = eng.submit("victim", rng.integers(0, cfg.vocab, 8))
    eng.run(max_new_tokens=2)
    sl = slice(vp.base, vp.base + vp.size)
    victim_rows = np.asarray(eng.cache.k[:, sl]).copy()
    assert (victim_rows != 0).any()   # victim actually wrote its slots

    # attacker submits; then we forge its slot to point at the victim
    rid_a = eng.submit("attacker",
                       rng.integers(0, cfg.vocab, 8).astype(np.int32))
    req = [r for r in eng._requests if r.rid == rid_a][0]
    req.slot = vp.base   # forged: victim's slot!
    eng.run(max_new_tokens=2)
    # fence wrapped the write into the attacker's own partition:
    after = np.asarray(eng.cache.k[:, sl])
    np.testing.assert_array_equal(victim_rows, after)


def test_multi_engine_fused_decode_matches_solo():
    """Two engines sharing one GuardianManager: each lockstep drain fuses
    the engines' steps into ONE compiled device step (width 2), and every
    engine's generations are bit-identical to running it solo on its own
    manager — fusion changes dispatch, never semantics."""
    from repro.launch.serve import (
        ServeEngine,
        make_shared_manager,
        serve_engines,
    )

    cfg = get_config("stablelm-3b").reduced()
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, cfg.vocab, 10, np.int32) for _ in range(2)]
    tokens = 5

    solo_outs = []
    for i, prompt in enumerate(prompts):
        eng = ServeEngine(cfg, max_batch=4, max_len=64)
        eng.register_tenant(f"t{i}", 2)
        rid = eng.submit(f"t{i}", prompt)
        solo_outs.append(eng.run(max_new_tokens=tokens)[rid])

    mgr = make_shared_manager(2, max_batch=4)
    engines = [ServeEngine(cfg, max_batch=4, max_len=64, manager=mgr)
               for _ in range(2)]
    rids = []
    for i, (eng, prompt) in enumerate(zip(engines, prompts)):
        eng.register_tenant(f"t{i}", 2)
        rids.append(eng.submit(f"t{i}", prompt))
    outs = serve_engines(engines, max_new_tokens=tokens)

    for i in range(2):
        assert outs[i][rids[i]] == solo_outs[i], f"engine {i} perturbed"
    st = mgr.scheduler.stats
    # 1 prefill + `tokens` decodes, every lockstep fused at width 2
    assert st.fused_steps == 1 + tokens
    assert st.mean_batch_width == 2.0
    assert st.single_steps == 0
    # both engines share one symbol entry (same model fingerprint)
    assert engines[0]._steps.decode_name == engines[1]._steps.decode_name


def test_multi_engine_quarantine_stays_scoped():
    """Quarantining a tenant of one co-hosted engine drops only that
    engine's requests; the sibling engine keeps serving through the same
    shared manager."""
    from repro.launch.serve import (
        ServeEngine,
        make_shared_manager,
        serve_engines,
    )

    cfg = get_config("stablelm-3b").reduced()
    rng = np.random.default_rng(7)
    mgr = make_shared_manager(2, max_batch=4)
    engines = [ServeEngine(cfg, max_batch=4, max_len=64, manager=mgr)
               for _ in range(2)]
    rids = []
    for i, eng in enumerate(engines):
        eng.register_tenant(f"t{i}", 2)
        rids.append(eng.submit(f"t{i}",
                               rng.integers(0, cfg.vocab, 8, np.int32)))
    dropped = engines[0].quarantine_tenant("t0", reason="abuse")
    assert dropped == [rids[0]]
    outs = serve_engines(engines, max_new_tokens=3)
    assert outs[0] == {}                      # engine 0 had nothing left
    assert rids[1] in outs[1] and len(outs[1][rids[1]]) == 3


def test_dryrun_cli_single_cell(tmp_path):
    """The dry-run entrypoint runs standalone for a small arch."""
    import os
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "xlstm-350m", "--shape", "decode_32k", "--out-dir",
         str(tmp_path)],
        capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": "src"}, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


def test_serve_pool_resizes_with_tenant():
    """Elastic serving: a tenant out of KV slots grows through the
    elastic control plane at submit time — the pool partition doubles
    (relocating if the buddy is taken), its existing requests re-address,
    and generations match a tenant that was sized big enough up front."""
    from repro.launch.serve import ServeEngine

    cfg = get_config("stablelm-3b").reduced()
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, cfg.vocab, 10, np.int32) for _ in range(3)]

    def run(initial_slots):
        eng = ServeEngine(cfg, max_batch=8, max_len=64)
        eng.register_tenant("a", initial_slots)
        eng.register_tenant("b", 2)
        rids = [eng.submit("a", p) for p in prompts]
        out = eng.run(max_new_tokens=4)
        return [out[r] for r in rids], eng

    small, eng = run(2)          # 3rd submit forces a grow
    big, _ = run(4)              # pre-sized control
    assert small == big
    part = eng.manager.bounds.lookup("a")
    assert part.size == 4
    assert any(e.startswith("grow a") for e in eng.manager.elastic.events)
