"""Mellum2 (64-expert top-8 MoE, 3:1 sliding/full GQA, YaRN on full
layers) served through ``ServeEngine(paged=True)`` + ``serve_continuous``
against the benchmark block's plain float32 reference
(``bench/blocks/moe_window.py``), at a tiny size on the CPU: 4 layers
(3 sliding, 1 full), window 8, 8 of 16 experts held with top-4, YaRN over
16 original positions, prompts past the window and past a page.

Tolerances: the program runs here in float32, as the reference does, so
the two differ only by the order of float32 sums: about 1e-6 of the
logits' scale.  ``ATOL`` = 1e-4 is a hundred times that, and a hundred
times below a bfloat16 rounding of the same logits (about 1e-2).
"""

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import model as bench_model
from bench.model import BENCH_DIR
from bench.tests.tiny import mellum as tiny_mellum
from bench.weights import seed_key
from repro.configs import FULL, SLIDING, get_config
from repro.core.fence import FenceParams, FencePolicy
from repro.launch.serve import (
    ServeEngine,
    make_shared_manager,
    serve_continuous,
)
from repro.models import get_model
from repro.models.api import KEYS_FULL, KEYS_WINDOW
from repro.models import kvcache as KV
from repro.models import moe as MOE
from repro.models.guard import GuardSpec

ATOL = 1e-4
MAX_LEN = 256            # 4 pages a row: a window's pages are 2 of them
WINDOW = 8               # the tiny configuration's window


def _tiny(**keys):
    """The benchmark's Mellum2 configuration at the tiny size, and the
    program's configuration built from the fields it states."""
    cfg = bench_model.parse(tiny_mellum(**keys))
    m, block = cfg.model, cfg.block
    pc = dataclasses.replace(get_config(m.arch), name="mellum2-tiny",
                             d_ff=128, **block.program_fields(m))
    return m, block, pc


@pytest.fixture(scope="module")
def tiny():
    m, block, pc = _tiny()
    w = block.make_weights(m, seed_key(2**31 + 16))
    like = jax.eval_shape(get_model(pc).init, jax.random.PRNGKey(0))
    return m, block, pc, w, block.to_program_tree(m, w, like)


def _reference(m, block, w, rows, outs):
    """Reference logits where each served token was chosen: the prompt,
    then the served tokens fed back."""
    n = max(len(o) for o in outs)
    toks = np.zeros((len(rows), MAX_LEN), np.int32)
    read = np.zeros((len(rows), n), np.int32)
    for i, (prompt, out) in enumerate(zip(rows, outs)):
        seq = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
        toks[i, :len(seq)] = seq
        read[i] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
    return np.asarray(block.logits_at(m, w, toks, read))


def test_paged_logits_match_the_reference(tiny):
    """Prefill, then paged decode over the global pool with the engine's
    kind of guard (per-row page extents, the page map): the logits at
    every position equal the reference's full forward."""
    m, block, pc, w, params = tiny
    api = get_model(pc)
    B, S, p0 = 2, 170, 140          # past the window and past two pages
    cache = KV.init_global_kv_cache(pc, B, MAX_LEN, total_pages=16)
    cache = dataclasses.replace(cache, page_table=jnp.asarray(
        [[3, 7, 1, 12], [0, 9, 4, 6]], jnp.int32))
    guard = GuardSpec(policy=FencePolicy.BITWISE,
                      vocab=FenceParams(base=0, size=256),
                      kv=FenceParams(base=0, size=16),
                      expert=FenceParams(base=0, size=pc.moe.held),
                      page=FenceParams(base=0, size=16),
                      page_map=jnp.arange(16, dtype=jnp.int32))
    toks = np.random.default_rng(1).integers(1, m.vocab, (B, S)) \
        .astype(np.int32)
    cache, lg = api.prefill(params, cache, {"tokens": jnp.asarray(
        toks[:, :p0])}, guard=guard)
    got = [lg]
    for t in range(p0, S):
        cache, lg = api.decode(params, cache, jnp.asarray(toks[:, t]),
                               guard=guard)
        got.append(lg)
    got = np.stack([np.asarray(g) for g in got], 1)
    read = np.tile(np.arange(p0 - 1, S, dtype=np.int32), (B, 1))
    ref = np.asarray(block.logits_at(m, w, toks, read))
    np.testing.assert_allclose(got, ref, rtol=0, atol=ATOL)


def test_served_tokens_are_the_references_best(tiny):
    """Served through ``ServeEngine(paged=True)`` + ``serve_continuous``:
    each served token's reference logit lies within ``ATOL`` of the
    reference's best at its position, and the engine counts every
    token-expert pair and every attended key."""
    m, block, pc, w, params = tiny
    mgr = make_shared_manager(1, max_batch=2, max_len=MAX_LEN, paged=True)
    eng = ServeEngine(pc, max_batch=2, max_len=MAX_LEN, manager=mgr,
                      paged=True, seed=0)
    eng.params = params
    for t in ("t0", "t1", "t2"):
        eng.register_tenant(t, MAX_LEN // KV.PAGE_SIZE)
    rng = np.random.default_rng(2)
    plen, new = 150, 20
    reqs = [("t0", 20), ("t1", 7), ("t2", 12)]
    prompts = [rng.integers(1, m.vocab, plen).astype(np.int32)
               for _ in reqs]
    rids = [eng.submit(t, p, max_new=n) for (t, n), p in zip(reqs, prompts)]
    out = serve_continuous([eng], max_new_tokens=new)[0]
    outs = [out[r] for r in rids]
    assert [len(o) for o in outs] == [n for _, n in reqs]
    ref = _reference(m, block, w, prompts, outs)
    for i, o in enumerate(outs):
        chosen = ref[i, np.arange(len(o)), o]
        gap = ref[i, :len(o)].max(-1) - chosen
        assert gap.max() <= ATOL, (i, gap)

    reg = mgr.telemetry.registry
    L, K = pc.n_layers, pc.moe.top_k
    for (t, n) in reqs:
        pairs = (plen + n - 1) * K * L
        held = reg.counter("moe.pairs_held", t)
        assert held + reg.counter("moe.pairs_elsewhere", t) == pairs
        assert 0 < held < pairs
        assert reg.counter("moe.pairs_dropped", t) == 0
        # queries at 0..plen+n-2: windowed keys on the 3 sliding layers
        q = plen + n - 1
        full = q * (q + 1) // 2
        window = WINDOW * (WINDOW + 1) // 2 + (q - WINDOW) * WINDOW
        assert reg.counter(KEYS_FULL, t) == 1 * full
        assert reg.counter(KEYS_WINDOW, t) == 3 * window


def test_the_served_steps_carry_the_named_scopes(tiny):
    """The decode step's operations carry ``guardian.moe`` and the
    attention scope of each layer kind, so a device trace names them."""
    m, block, pc, w, params = tiny
    api = get_model(pc)
    cache = KV.init_global_kv_cache(pc, 2, MAX_LEN, total_pages=16)
    text = jax.jit(lambda p, c, t: api.decode(p, c, t)).lower(
        params, cache, jnp.zeros((2,), jnp.int32)).compile().as_text()
    for scope in (MOE.SCOPE_MOE, *MOE.SCOPE_ATTN.values()):
        assert scope in text, scope


def _layer_input(m, seed):
    return jax.random.normal(jax.random.PRNGKey(seed), (2, 24, m.d_model),
                             jnp.float32)


def _expert_weights(m, w, layer=0):
    return {k: w[k][layer] for k in ("router", "wg", "wu", "wd")}


def _reference_experts(m, block, p, h):
    with jax.default_matmul_precision("highest"):
        return np.asarray(block._experts(m, None, h, p))


def test_held_shares_sum_to_the_uncut_layer():
    """Four chips, each holding 4 of the 16 experts: their partial
    outputs of one expert layer add up to the uncut reference layer."""
    m, block, pc = _tiny(num_experts=16)
    w = block.make_weights(m, seed_key(5))
    p = _expert_weights(m, w)
    h = _layer_input(m, 6)
    whole = _reference_experts(m, block, p, h)
    total = 0.0
    for base in range(0, 16, 4):
        share = dataclasses.replace(pc, moe=dataclasses.replace(
            pc.moe, experts_held=4, expert_base=base))
        part = {k: p[k][base:base + 4] if k != "router" else p[k]
                for k in p}
        out, pairs = MOE.moe_dropless(share, part, h)
        total = total + np.asarray(out)
        assert int(pairs[:, 2].sum()) == 0
    np.testing.assert_allclose(total, whole, rtol=0, atol=ATOL)


def test_dropless_under_a_router_that_sends_every_token_to_one_expert(
        tiny):
    """A router skewed so that every token's first choice is held expert
    3: the capacity path drops past its capacity, the served path drops
    nothing and equals the reference."""
    m, block, pc, w, _ = tiny
    p = _expert_weights(m, w)
    p["router"] = p["router"].at[:, 3].add(50.0)
    h = jnp.abs(_layer_input(m, 7))             # every token leans to 3
    ref = _reference_experts(m, block, p, h)
    out, pairs = MOE.moe_dropless(pc, p, h)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=0, atol=ATOL)
    assert int(pairs[:, 2].sum()) == 0
    capped, _ = MOE.moe_apply(pc, p, h, capacity_factor=0.5)
    assert np.abs(np.asarray(capped) - ref).max() > 100 * ATOL


def _one_kind(kind, pc, params):
    """A one-layer model of ``kind`` from the tiny model's first layer of
    that kind."""
    layer = pc.layer_kinds.index(kind)
    cfg = dataclasses.replace(pc, n_layers=1, layer_types=(kind,))
    p = dict(params, layers=jax.tree.map(lambda a: a[layer:layer + 1],
                                         params["layers"]))
    return cfg, p


@pytest.mark.parametrize("kind", [SLIDING, FULL])
def test_keys_older_than_the_window_reach_only_a_full_layer(tiny, kind):
    """Changing the cached keys and values older than the window leaves
    a sliding layer's decode output as it was, and changes a full
    layer's."""
    m, block, pc, w, params = tiny
    cfg, p = _one_kind(kind, pc, params)
    api = get_model(cfg)
    cache = KV.init_global_kv_cache(cfg, 2, MAX_LEN, total_pages=8)
    cache = dataclasses.replace(cache, page_table=jnp.asarray(
        [[0, 1, 2, 3], [4, 5, 6, 7]], jnp.int32))
    toks = jax.random.randint(jax.random.PRNGKey(8), (2, 140), 1, m.vocab)
    cache, _ = api.prefill(p, cache, {"tokens": toks})
    nxt = toks[:, -1]
    _, before = api.decode(p, cache, nxt)
    # positions 0..140-WINDOW are older than the window of the token at
    # 140: pages 0 and 1 of each row, and the head of page 2
    old = 141 - WINDOW
    pos = jnp.arange(KV.PAGE_SIZE * 4).reshape(4, KV.PAGE_SIZE)
    stale = (pos < old)[None, :, :, None, None]

    def scramble(pool, key):
        noise = jax.random.normal(key, pool.shape, pool.dtype)
        rows = pool.reshape(1, 2, 4, *pool.shape[2:])
        noise = noise.reshape(rows.shape)
        return jnp.where(stale[:, None], noise, rows).reshape(pool.shape)

    cache = dataclasses.replace(
        cache, k=scramble(cache.k, jax.random.PRNGKey(9)),
        v=scramble(cache.v, jax.random.PRNGKey(10)))
    _, after = api.decode(p, cache, nxt)
    diff = np.abs(np.asarray(after) - np.asarray(before)).max()
    if kind == SLIDING:
        assert diff == 0.0
    else:
        assert diff > 100 * ATOL


def test_an_absent_expert_never_reaches_a_held_one_under_the_guard():
    """With the guard's expert fence over the 8 held experts, an id
    routed to an absent expert (8-15) keeps the index past the bank: the
    fence never wraps it onto a held expert's rows, and it adds
    nothing."""
    m, block, pc = _tiny()
    guard = GuardSpec(policy=FencePolicy.BITWISE,
                      expert=FenceParams(base=0, size=pc.moe.held))
    ids = jnp.arange(16, dtype=jnp.int32)[None]
    local, held = MOE.held_experts(pc, guard, ids)
    assert np.asarray(held)[0].tolist() == [True] * 8 + [False] * 8
    assert np.asarray(local)[0].tolist() == list(range(8)) + [8] * 8
    # the fence alone would have wrapped 9 onto held expert 1
    from repro.models.guard import fence
    assert int(fence(guard, "expert", jnp.int32(9))) == 1

    w = block.make_weights(m, seed_key(11))
    p = _expert_weights(m, w)
    p["router"] = p["router"].at[:, 8:].add(50.0)    # only absent ones
    h = jnp.abs(_layer_input(m, 12))
    out, pairs = MOE.moe_dropless(pc, p, h, guard)
    assert np.abs(np.asarray(out)).max() == 0.0
    assert int(pairs[:, 0].sum()) == 0
    assert int(pairs[:, 1].sum()) == h.shape[0] * h.shape[1] * m.top_k


def test_yarn_matches_transformers():
    """YaRN inverse frequencies and the attention factor, in the program
    and in the reference, against the installed ``transformers``."""
    mru = pytest.importorskip("transformers.modeling_rope_utils")
    from transformers import PretrainedConfig

    from repro.models.layers import yarn_freqs

    pc = get_config("mellum2-12b-a2.5b")
    raw = json.loads((BENCH_DIR / "configs" / "mellum2.json").read_text())
    full = raw["rope_parameters"]["full_attention"]
    hf = PretrainedConfig()
    hf.rope_theta = full["rope_theta"]
    hf.head_dim = raw["head_dim"]
    hf.hidden_size = raw["hidden_size"]
    hf.num_attention_heads = raw["num_attention_heads"]
    hf.max_position_embeddings = raw["max_position_embeddings"]
    hf.rope_scaling = dict(full)
    inv, factor = mru._compute_yarn_parameters(hf, "cpu")
    inv = inv.numpy().astype(np.float64)
    # transformers computes in float32
    np.testing.assert_allclose(yarn_freqs(pc.head_dim, pc.rope_theta,
                                          pc.yarn), inv, rtol=1e-6)
    assert pc.yarn.attention_factor == pytest.approx(factor, rel=1e-12)
    m = bench_model.parse(raw).model
    ref_inv, ref_factor = bench_model.parse(raw).block.inverse_frequencies(
        m, FULL)
    np.testing.assert_allclose(ref_inv, inv, rtol=1e-6)
    assert ref_factor == pytest.approx(factor, rel=1e-12)


def test_a_held_pair_outside_its_experts_rows_counts_as_dropped(
        tiny, monkeypatch):
    """``moe.pairs_dropped`` reads where the group sizes place each sorted
    pair: with the pairs sorted the wrong way round, held pairs land in
    other experts' rows and count as dropped."""
    m, block, pc, w, _ = tiny
    p = _expert_weights(m, w)
    h = _layer_input(m, 13)
    _, pairs = MOE.moe_dropless(pc, p, h)
    assert int(pairs[:, 2].sum()) == 0
    argsort = MOE.jnp.argsort
    monkeypatch.setattr(MOE.jnp, "argsort",
                        lambda a, stable=True: argsort(-a, stable=stable))
    _, pairs = MOE.moe_dropless(pc, p, h)
    assert 0 < int(pairs[:, 2].sum()) <= int(pairs[:, 0].sum())


@pytest.mark.parametrize("field", ["layer_types", "yarn"])
def test_a_family_that_ignores_layer_kinds_refuses_them(field):
    """Only the moe family serves layers by kind and YaRN; a dense model
    given either is refused rather than served with every layer alike."""
    mellum = get_config("mellum2-12b-a2.5b")
    dense = dataclasses.replace(get_config("stablelm-3b"),
                                **{field: getattr(mellum, field)})
    with pytest.raises(ValueError, match=field):
        get_model(dense)


def test_qkv_bias_reaches_the_projections(tiny):
    """A moe configuration with q/k/v biases adds them, as the dense
    projections do."""
    m, block, pc, w, params = tiny
    pb = dataclasses.replace(pc, qkv_bias=True)
    p = jax.tree.map(lambda a: a[0], params["layers"])
    rng = np.random.default_rng(14)
    p["attn"] = dict(p["attn"], **{
        b: jnp.asarray(rng.standard_normal(p["attn"][wk].shape[-1]),
                       jnp.float32)
        for b, wk in (("bq", "wq"), ("bk", "wk"), ("bv", "wv"))})
    x = _layer_input(m, 15)
    pos = jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2])
    got = MOE._qkv(pb, p, x, pos, FULL)
    from repro.models import layers as L
    q, k, v = L.qkv_proj(pb, p["attn"], L.apply_norm(pb, p["norm1"], x))
    q, k = L.positions_rope(pb, q, k, pos, FULL)
    for g, r in zip(got, (q, k, v)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=ATOL)
    plain = MOE._qkv(pc, p, x, pos, FULL)
    assert np.abs(np.asarray(plain[2]) - np.asarray(v)).max() > 100 * ATOL


def test_steps_count_pairs_only_with_telemetry(tiny):
    """With telemetry off the served steps compute no per-row counts (a
    step of its own name), and serve the same tokens."""
    m, block, pc, w, params = tiny
    rng = np.random.default_rng(16)
    prompt = rng.integers(1, m.vocab, 40).astype(np.int32)
    out, names = [], []
    for telemetry in (True, False):
        eng = ServeEngine(pc, max_batch=2, max_len=MAX_LEN, paged=True,
                          seed=0, telemetry=telemetry)
        eng.params = params
        eng.register_tenant("t0", MAX_LEN // KV.PAGE_SIZE)
        rid = eng.submit("t0", prompt, max_new=6)
        out.append(serve_continuous([eng], max_new_tokens=6)[0][rid])
        names.append(eng._steps.decode_name)
    assert out[0] == out[1]
    assert names[0].endswith(":counts]")
    assert not names[1].endswith(":counts]")


# ---------------------------------------------------------------------------
# Expert products over the whole bank (no per-layer slice of the stacks)
# ---------------------------------------------------------------------------

#: the served products' two paths: ``ragged_dot`` (every platform but the
#: TPU) and the TPU's grouped matmul, interpreted here
PRODUCT_PATHS = {"ragged_dot": MOE.grouped_ragged,
                 "megablox": functools.partial(MOE.grouped_megablox,
                                               interpret=True)}


def _sliced_products(pc, p, h):
    """The layer's expert products as served before the bank: its experts
    sliced out of the stacks, one ``ragged_dot`` per product over them
    alone, the pairs held elsewhere weighted by zero."""
    m = pc.moe
    B, S, d = h.shape
    T, K, H = B * S, m.top_k, m.held
    xf = h.reshape(T, d)
    _, gate, ids = MOE._route(pc, p, xf)
    local, held = MOE.held_experts(pc, None, ids)
    flat = local.reshape(T * K)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.bincount(flat, length=H + 1)[:H].astype(jnp.int32)
    xs = jnp.take(xf, order // K, axis=0)

    def mm(a, w):
        return jax.lax.ragged_dot(a, w, sizes,
                                  preferred_element_type=jnp.float32)

    y = mm((jax.nn.silu(mm(xs, p["wg"])) * mm(xs, p["wu"])).astype(
        xf.dtype), p["wd"]).astype(xf.dtype)
    back = jnp.zeros((T * K,), jnp.int32).at[order].set(
        jnp.arange(T * K, dtype=jnp.int32))
    y_tk = jnp.take(y, back, axis=0).reshape(T, K, d)
    out = jnp.einsum("tkd,tk->td", y_tk.astype(jnp.float32),
                     jnp.where(held, gate, 0.0))
    return np.asarray(out.reshape(B, S, d)), np.asarray(held)


#: router biases: the seeded router as drawn, every token's first choice
#: on held expert 3, every pair on an expert held elsewhere (8-15), every
#: pair on a held one (0-7)
ROUTINGS = {"seeded": None, "one_expert": (3, 4), "all_elsewhere": (8, 16),
            "all_held": (0, 8)}


def _bank_layer(pc, params, layer, routing):
    """Layer ``layer``'s router (biased by ``routing``) over the tiny
    model's whole bank, and the same layer's experts sliced out."""
    moe = params["layers"]["moe"]
    router = moe["router"][layer]
    if ROUTINGS[routing] is not None:
        lo, hi = ROUTINGS[routing]
        router = router.at[:, lo:hi].add(50.0)
    bank = dict(MOE.expert_bank(moe), router=router)
    sliced = {k: moe[k][layer] for k in ("wg", "wu", "wd")}
    return bank, dict(sliced, router=router)


@pytest.mark.parametrize("routing", sorted(ROUTINGS))
@pytest.mark.parametrize("path", sorted(PRODUCT_PATHS))
@pytest.mark.parametrize("layer", [0, 2, 3])
def test_bank_products_equal_the_sliced_layers(tiny, monkeypatch, layer,
                                               path, routing):
    """The products over the whole bank, groups placed at the layer's
    experts (first, middle and last layer), equal the products over the
    layer's experts sliced out of the stacks, on both paths, under a
    seeded router, one that sends every token to one expert, one whose
    pairs are all held elsewhere (every group empty) and one whose pairs
    are all held."""
    m, block, pc, w, params = tiny
    monkeypatch.setattr(MOE, "grouped_ragged", PRODUCT_PATHS[path])
    bank, sliced = _bank_layer(pc, params, layer, routing)
    h = jnp.abs(_layer_input(m, 20 + layer))
    want, held = _sliced_products(pc, sliced, h)
    out, counts = MOE.moe_dropless(pc, bank, h, lidx=layer)
    np.testing.assert_allclose(np.asarray(out), want, rtol=0, atol=ATOL)
    counts = np.asarray(counts)
    n_held = int(held.sum())
    assert counts[:, 0].sum() == n_held
    assert counts[:, 2].sum() == 0
    if routing == "all_elsewhere":
        assert n_held == 0 and np.abs(np.asarray(out)).max() == 0.0
    if routing == "all_held":
        assert counts[:, 1].sum() == 0


def test_rows_the_kernel_leaves_unwritten_never_reach_the_output(
        tiny, monkeypatch):
    """The grouped matmul leaves the rows of pairs held elsewhere
    unwritten.  With those rows poisoned to NaN (the kernel's
    ``existing_out``), the layer's output stays finite and equal to the
    ``ragged_dot`` path's: the rows are selected away, not weighted by
    zero."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    m, block, pc, w, params = tiny
    bank, _ = _bank_layer(pc, params, 2, "seeded")
    # 2 x 32 tokens x top-4: 256 sorted rows, whole tiles of rows
    h = jax.random.normal(jax.random.PRNGKey(21), (2, 32, m.d_model))
    want, counts = MOE.moe_dropless(pc, bank, h, lidx=2)
    assert 0 < int(counts[:, 0].sum()) and 0 < int(counts[:, 1].sum())
    poisoned = []

    def poison(a, wt, sizes):
        rows, k = a.shape
        tiling = MOE.gmm_tiling(rows, k, wt.shape[2], wt.dtype.itemsize)
        assert rows % tiling[0] == 0
        out = gmm(a, wt, sizes, preferred_element_type=jnp.float32,
                  tiling=tiling, interpret=True,
                  existing_out=jnp.full((rows, wt.shape[2]), jnp.nan,
                                        jnp.float32))
        poisoned.append(int(np.isnan(np.asarray(out)).any(-1).sum()))
        return out

    monkeypatch.setattr(MOE, "grouped_ragged", poison)
    got, _ = MOE.moe_dropless(pc, bank, h, lidx=2)
    # every product left the rows of the pairs held elsewhere as NaN
    assert poisoned == [int(counts[:, 1].sum())] * 3
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=ATOL)


def _bank_leaves(args):
    """Indices, among the flattened ``args``, of the stacked experts'
    ``wg``, ``wu`` and ``wd``."""
    leaves = jax.tree_util.tree_flatten_with_path(args)[0]
    return [i for i, (path, _) in enumerate(leaves)
            if "'moe'" in jax.tree_util.keystr(path)
            and jax.tree_util.keystr(path).endswith(("['wg']", "['wu']",
                                                     "['wd']"))]


#: primitives that take a part of an array out of it
SLICING = {"dynamic_slice", "gather", "slice"}
#: the expert products: what reaches them may be the bank itself
PRODUCTS = {"ragged_dot_general", "pallas_call"}


def _bank_reads(jaxpr, tainted, found):
    """Walk ``jaxpr`` from the variables in ``tainted`` (the bank, then
    what is computed from it): record in ``found``, by equation, each
    slicing op and each expert product that takes from them, and return
    which of the outputs are computed from them."""
    from jax.extend.core import ClosedJaxpr, Jaxpr, Literal

    tainted = set(tainted)

    def inner(j, hit):
        j = j.jaxpr if isinstance(j, ClosedJaxpr) else j
        return _bank_reads(j, [v for v, t in zip(j.invars, hit) if t],
                           found)

    for eqn in jaxpr.eqns:
        hit = [not isinstance(v, Literal) and v in tainted
               for v in eqn.invars]
        if not any(hit):
            continue
        name, prm = eqn.primitive.name, eqn.params
        if name in PRODUCTS or name in SLICING:
            found[id(eqn)] = name
            continue
        outs = [True] * len(eqn.outvars)
        if name == "cond":
            outs = [any(o) for o in zip(*(inner(b, hit[1:])
                                           for b in prm["branches"]))]
        elif name in ("scan", "while"):
            if name == "scan":
                body, n0, n = prm["jaxpr"], prm["num_consts"], \
                    prm["num_carry"]
                if any(hit[n0 + n:]):
                    found[id(eqn)] = "scan over the bank"
            else:
                body, n0 = prm["body_jaxpr"], prm["body_nconsts"]
                hit, n = hit[prm["cond_nconsts"]:], len(eqn.outvars)
            while True:         # until the carry's taint settles
                outs = inner(body, hit)
                carry = [a or b for a, b in zip(hit[n0:n0 + n], outs[:n])]
                if carry == hit[n0:n0 + n]:
                    break
                hit = hit[:n0] + carry + hit[n0 + n:]
        else:
            subs = [v for v in prm.values()
                    if isinstance(v, (Jaxpr, ClosedJaxpr))]
            if subs:
                outs = inner(subs[0], hit)
        tainted.update(v for v, t in zip(eqn.outvars, outs) if t)
    return [not isinstance(v, Literal) and v in tainted
            for v in jaxpr.outvars]


@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_served_steps_take_no_expert_slice_out_of_the_bank(tiny, phase):
    """Mellum2's served steps hand the expert products the stacked bank
    itself: no dynamic slice or gather takes a layer's ``wg``, ``wu`` or
    ``wd`` out of the stacks before the products, on either path."""
    m, block, pc, w, params = tiny
    api = get_model(pc)
    cache = KV.init_global_kv_cache(pc, 2, MAX_LEN, total_pages=16)
    if phase == "prefill":
        args = (params, cache, {"tokens": jnp.ones((2, 24), jnp.int32)})
        step = api.prefill
    else:
        args = (params, cache, jnp.ones((2,), jnp.int32))
        step = api.decode
    closed = jax.make_jaxpr(lambda a: step(*a, counts=True))(args)
    bank = [closed.jaxpr.invars[i] for i in _bank_leaves(args)]
    assert len(bank) == 3
    for weights in bank:
        found = {}
        _bank_reads(closed.jaxpr, [weights], found)
        # each of wg, wu, wd reaches both paths' products, and only them
        assert sorted(set(found.values())) == ["pallas_call",
                                               "ragged_dot_general"]


def test_experts_read_counts_each_rows_reached_held_experts(tiny):
    """``moe.experts_read``: for each batch row, the held experts that at
    least one of its token-expert pairs reaches, summed over the layers,
    as a seeded router's top-k choices give them."""
    m, block, pc, w, params = tiny
    moe = params["layers"]["moe"]
    bank = MOE.expert_bank(moe)
    K, H = pc.moe.top_k, pc.moe.held
    x = _layer_input(m, 22)
    want = np.zeros(x.shape[0], np.int64)
    got = np.zeros(x.shape[0], np.int64)
    for layer in range(pc.n_layers):
        router = np.asarray(moe["router"][layer], np.float64)
        logits = np.asarray(x, np.float64) @ router          # (B, S, E)
        top = np.argsort(-logits, axis=-1)[..., :K]
        for b in range(x.shape[0]):
            want[b] += len({e for e in top[b].ravel() if e < H})
        _, counts = MOE.moe_dropless(pc, dict(bank, router=moe["router"][
            layer]), x, lidx=layer)
        got += np.asarray(counts)[:, 3]
    assert (want > 0).all()
    np.testing.assert_array_equal(got, want)


def test_experts_read_is_counted_only_with_telemetry(tiny):
    """Served with telemetry on, a tenant's ``moe.experts_read`` is
    positive and at most every held expert of every layer a step; with
    telemetry off the steps count nothing."""
    m, block, pc, w, params = tiny
    prompt = np.random.default_rng(23).integers(1, m.vocab, 40) \
        .astype(np.int32)
    new = 5
    for telemetry in (True, False):
        eng = ServeEngine(pc, max_batch=2, max_len=MAX_LEN, paged=True,
                          seed=0, telemetry=telemetry)
        eng.params = params
        eng.register_tenant("t0", MAX_LEN // KV.PAGE_SIZE)
        eng.submit("t0", prompt, max_new=new)
        serve_continuous([eng], max_new_tokens=new)
        counters = eng.manager.telemetry.registry.snapshot()["counters"]
        if not telemetry:
            assert "moe.experts_read" not in counters
            continue
        read = counters["moe.experts_read"]["t0"]
        steps = new          # one prefill, then new - 1 decode steps
        assert 0 < read <= pc.n_layers * pc.moe.held * steps
