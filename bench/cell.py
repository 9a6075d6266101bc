"""One run of one cell: set-up, a measured window, the correctness check
and the metrics, driven through the program's public serving API
(``make_shared_manager``, ``ServeEngine``, ``register_tenant``,
``submit``, ``serve_continuous``, ``ServeEngine.decode_steps``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import shutil
import statistics
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import flops, model as model_mod, traffic as traffic_mod
from bench.model import BENCH_DIR, Config

CHECKOUT = BENCH_DIR.parent
TRACE_DIR = CHECKOUT / ".bench_trace"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CHECK_ROWS = 4              # reference rows per compiled block
CHECK_TOKENS = 300          # served tokens the check compares, at least
CHECK_BLOCKS = 16
#: no drain cycle of these cells is shorter: it bounds how many calls a
#: window can make, so the warm-up knows which token trails to compile
MIN_CYCLE_S = 0.004
#: a traced window closes at the first call boundary past this many decode
#: steps, which the TPU's device trace is shown to hold whole (PERF.md);
#: past about 1,800 steps of stablelm3b it drops events.  A block with more
#: operations a step holds fewer: the traced result's ``device`` gives
#: ``decode_steps`` and ``decode_steps_traced``, which differ when it did
TRACE_DECODE_STEPS = 1200


class NoChip(RuntimeError):
    """The devices JAX found cannot run the cell."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Served:
    """One request of the window, as the check and the metrics see it."""

    rid: int
    tenant: str
    prompt: np.ndarray
    max_new: int
    out: List[int]
    latency_s: float


def find_workload(bench: Dict, name: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def device_info(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    dev = devs[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    if require_tpu and (dev.platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {dev.platform} device(s)")
    return devs[:chips]


def load_peaks(kind: str) -> Dict:
    table = json.loads((BENCH_DIR / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


# --------------------------------------------------------------------- #
# The program under test                                                #
# --------------------------------------------------------------------- #

def program_config(cfg: Config):
    """The program's registry entry for the configuration, checked against
    the sizes the configuration file states."""
    from repro.configs import get_config

    pc = get_config(cfg.model.arch)
    if cfg.raw.get("program_reduced"):
        pc = pc.reduced()
    want = cfg.block.program_fields(cfg.model)
    got = {k: getattr(pc, k) for k in want}
    if got != want:
        raise ValueError(f"program config {pc.name} {got} differs from "
                         f"bench config {cfg.name} {want}")
    return pc


def build_engine(cfg: Config, weights: Callable):
    """Manager, engine and tenants.  ``weights(like)`` returns the
    parameter tree that replaces the engine's own."""
    import jax

    from repro.launch.serve import ServeEngine, make_shared_manager

    pc = program_config(cfg)
    mgr = make_shared_manager(1, max_batch=cfg.max_batch,
                              max_len=cfg.max_len, paged=True)
    eng = ServeEngine(pc, max_batch=cfg.max_batch, max_len=cfg.max_len,
                      manager=mgr, paged=True, seed=0)
    like = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        eng.params)
    # the engine's own weights are freed before the benchmark's are made,
    # so that the two never share the chip's memory
    for leaf in jax.block_until_ready(jax.tree.leaves(eng.params)):
        leaf.delete()
    eng.params = weights(like)
    for t in cfg.tenants:
        eng.register_tenant(t.name, t.pages)
    if mgr.bounds.free_slots():
        # a full tenant grows into free pages (ServeEngine._alloc_pages),
        # and serve_cycles, which fixes the warm-up's shapes, assumes none
        raise ValueError(f"{cfg.name}: the tenants' extents leave "
                         f"{mgr.bounds.free_slots()} pool pages free; "
                         "they have to fill the pool")
    return mgr, eng


def serve_call(eng, reqs, annotate=contextlib.nullcontext) -> List[Served]:
    """Submit ``reqs`` and run one ``serve_continuous`` call."""
    from repro.launch.serve import serve_continuous

    with annotate("bench.submit"):
        sent = []
        for r in reqs:
            t = time.perf_counter()
            sent.append((eng.submit(r.tenant, r.prompt, max_new=r.max_new),
                         t))
    with annotate("bench.serve_call"):
        out = serve_continuous([eng], max_new_tokens=max(
            r.max_new for r in reqs))[0]
    done = time.perf_counter()
    with annotate("bench.results"):
        return [Served(rid, r.tenant, r.prompt, r.max_new, out.get(rid, []),
                       done - t0) for r, (rid, t0) in zip(reqs, sent)]


def serve_cycles(reqs, cfg: Config) -> int:
    """How many drain cycles dispatch work when ``serve_continuous`` serves
    ``reqs`` from idle: rows join FIFO while their tenant has pages free,
    every active row emits one token per cycle.  It fixes the size of the
    token trail that the call stacks at its end, whose shape the warm-up
    compiles."""
    per_req = cfg.max_len // flops.PAGE_SIZE
    cap = {t.name: t.pages // per_req for t in cfg.tenants}
    live = {t: 0 for t in cap}
    rows: List[Optional[traffic_mod.Req]] = [None] * cfg.max_batch
    left = [0] * cfg.max_batch
    waiting = list(reqs)
    n = 0
    while True:
        for i, r in enumerate(rows):
            if r is not None and left[i] <= 0:
                live[r.tenant] -= 1
                rows[i] = None
        if not waiting and all(r is None for r in rows):
            return n
        joined, wi = set(), 0
        for i in range(cfg.max_batch):
            if rows[i] is not None:
                continue
            while wi < len(waiting):
                r = waiting[wi]
                wi += 1
                if live[r.tenant] < cap[r.tenant]:
                    rows[i], left[i] = r, r.max_new
                    live[r.tenant] += 1
                    joined.add(id(r))
                    break
        waiting = [r for r in waiting if id(r) not in joined]
        if any(r is not None for r in rows):
            n += 1
        for i, r in enumerate(rows):
            if r is not None:
                left[i] -= 1


# --------------------------------------------------------------------- #
# Correctness                                                           #
# --------------------------------------------------------------------- #

def pick_sample(served: List[Served], seed: int) -> List[Served]:
    """Requests the check compares: the longest served output, then others
    in a seeded order, until ``CHECK_TOKENS`` tokens; whole blocks."""
    done = [s for s in served if len(s.out) == s.max_new]
    if not done:
        return []
    rng = np.random.default_rng([int(seed), 7])
    longest = max(range(len(done)), key=lambda i: len(done[i].out))
    order = [longest] + [int(i) for i in rng.permutation(len(done))
                         if i != longest]
    pick, tokens = [], 0
    for i in order:
        if len(pick) >= CHECK_ROWS * CHECK_BLOCKS:
            break
        if tokens >= CHECK_TOKENS and len(pick) % CHECK_ROWS == 0:
            break
        pick.append(done[i])
        tokens += len(done[i].out)
    return pick


def check_blocks(sample: List[Served], max_len: int):
    """Token rows (the prompt alone, then the served tokens fed back), the
    positions whose logits chose each served token, and those tokens;
    rows of ``CHECK_ROWS``, padded with empty rows."""
    k = max(len(s.out) for s in sample)
    blocks = []
    for b in range(0, len(sample), CHECK_ROWS):
        part = sample[b:b + CHECK_ROWS]
        toks = np.zeros((CHECK_ROWS, max_len), np.int32)
        read = np.zeros((CHECK_ROWS, k), np.int32)
        chosen = np.zeros((CHECK_ROWS, k), np.int32)
        mask = np.zeros((CHECK_ROWS, k), bool)
        for i, s in enumerate(part):
            n = len(s.out)
            p = len(s.prompt)
            seq = np.concatenate([s.prompt, np.asarray(s.out[:-1], np.int32)])
            toks[i, :len(seq)] = seq
            read[i, :n] = np.arange(p - 1, p - 1 + n)
            chosen[i, :n] = s.out
            mask[i, :n] = True
        blocks.append((toks, read, chosen, mask))
    return blocks


def gaps(ref_logits, tokens):
    """How far each chosen token's reference logit lies below the
    reference's best at that position."""
    import jax.numpy as jnp

    chosen = jnp.take_along_axis(ref_logits, jnp.asarray(tokens)[..., None],
                                 -1)[..., 0]
    return jnp.max(ref_logits, -1) - chosen


def argmax(logits):
    import jax.numpy as jnp

    return jnp.argmax(logits, -1).astype(jnp.int32)


def widest_gap(block, m, weights, blocks,
               quant: Optional[str] = None) -> float:
    """Widest gap by which a chosen token's reference logit (the block's
    ``logits_at``) lies below the reference's best.  With ``quant`` the
    tokens are those the lower-precision control puts first at the same
    positions."""
    worst = 0.0
    for toks, read, chosen, mask in blocks:
        if quant is not None:
            chosen = np.asarray(argmax(
                block.logits_at(m, weights, toks, read, quant=quant)))
        ref = block.logits_at(m, weights, toks, read)
        g = np.asarray(gaps(ref, np.asarray(chosen)))
        worst = max(worst, float(g[mask].max()))
        del ref
    return worst


def judge(gap: float, failed: int, limits: Dict):
    """The numbers compared, each beside its limit, and whether all hold."""
    checks = {
        "max_logit_gap": {"value": gap, "limit": limits["max_logit_gap"]},
        "failed_requests": {"value": failed, "limit": 0},
    }
    return checks, all(c["value"] <= c["limit"] for c in checks.values())


# --------------------------------------------------------------------- #
# Metrics                                                               #
# --------------------------------------------------------------------- #

@dataclasses.dataclass
class Context:
    """What a per-layer reader may read."""

    model: object
    cfg: Config
    peaks: Dict
    served: List[Served]
    decode_steps: int
    trace: object = None

    @property
    def block(self):
        """The configuration's block: its counts (``bench/blocks``)."""
        return self.cfg.block

    def decode_ctxs(self):
        """Context length of every decoded token: the request's own prompt
        plus the tokens before it (pads not counted)."""
        return [len(s.prompt) + j for s in self.served
                for j in range(1, len(s.out))]


def load_reader(name: str):
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metric_applies(metric: Dict, workload: str, e2e_names) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    return metric["moves"] in e2e_names


def end_to_end(metric: str, served: List[Served], window: Dict,
               setup_s: float) -> float:
    if metric == "setup_s":
        return setup_s
    if metric == "output_tok_per_s":
        return sum(len(s.out) for s in served) / window["seconds"]
    lat = sorted(s.latency_s for s in served)
    if metric == "latency_p50_s":
        return statistics.median(lat)
    if metric == "latency_p95_s":
        return float(np.percentile(lat, 95))
    raise KeyError(f"no end-to-end metric {metric!r}")


# --------------------------------------------------------------------- #
# The run                                                               #
# --------------------------------------------------------------------- #

def run(bench: Dict, workload: str, seed: int, seconds: float,
        traced: bool, t_start: float, *, require_tpu: bool = True,
        config: Optional[Config] = None,
        mix: Optional[Dict] = None,
        limits: Optional[Dict] = None,
        control: bool = False, persist: bool = True) -> Dict:
    """One run; returns the result line's object.  ``config``, ``mix`` and
    ``limits`` let a test run a tiny configuration; ``persist=False``
    leaves JAX's compilation cache as it is.  ``control`` also puts the
    lower-precision control's tokens in the program's place on the same
    sample and judges them against the same limits (``bench/control.py``):
    the result's ``control`` holds its ``checks`` and ``correct``."""
    wl = find_workload(bench, workload)
    cfg = config or model_mod.load(wl["config"])
    mix = mix or traffic_mod.load_mix(wl["traffic"])
    limits = limits or model_mod.load_json("cells", workload)["limits"]
    e2e_all = {m["name"]: m for m in bench["end_to_end"]}
    e2e = [n for n, m in e2e_all.items()
           if workload in m.get("workloads", [workload])]
    layer_metrics = [m for m in bench["per_layer"]
                     if metric_applies(m, workload, e2e)]

    devs = device_info(wl["chips"], require_tpu)
    import jax

    from repro.launch.compile_cache import place_compile_cache
    from repro.launch.serve import serve_continuous

    if persist:
        place_compile_cache()
        # persist every program, tiny ones too: a later run of the cell
        # loads what this one compiled
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compiles = _compile_counter()

    from bench.weights import seed_key

    m, block = cfg.model, cfg.block
    key = seed_key(seed)
    holder = {}

    def make(like):
        w = holder["w"] = block.make_weights(m, key)
        return block.to_program_tree(m, w, like)

    mgr, eng = build_engine(cfg, make)
    traffic = traffic_mod.Traffic(mix, cfg, seed)

    # -- warm-up: every prefill length, the decode step, the merge of a
    # prefill with a decode, and the trail stack of every call the window
    # can make
    lo = min(traffic.buckets)
    for b in sorted(traffic.buckets):
        serve_call(eng, [traffic_mod.Req(cfg.tenants[0].name,
                                         np.ones(b, np.int32), 2)])
    eng.submit(cfg.tenants[0].name, np.ones(lo, np.int32), max_new=3)
    eng.submit(cfg.tenants[-1].name, np.ones(lo, np.int32), max_new=2,
               arrive=1)
    serve_continuous([eng], max_new_tokens=3)
    import jax.numpy as jnp

    trails, k, cycles = set(), 0, 0
    while cycles * MIN_CYCLE_S < seconds:
        n = serve_cycles(traffic.batch(k), cfg)
        trails.add(n)
        cycles += n
        k += 1
    row = jnp.zeros((cfg.max_batch,), jnp.int32)
    for n in sorted(trails):
        jnp.stack([row] * n).block_until_ready()
    jax.block_until_ready(eng.params)
    setup_s = time.perf_counter() - t_start
    log(f"setup_s {setup_s:.3f}")

    # -- the window
    annotate = jax.profiler.TraceAnnotation if traced \
        else contextlib.nullcontext
    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    served: List[Served] = []
    steps0 = eng.decode_steps
    compiles["open"], compiles["window"] = True, 0
    t0 = time.perf_counter()
    k = 0
    with annotate("bench.window"):
        while k == 0 or (time.perf_counter() - t0 < seconds and not (
                traced and eng.decode_steps - steps0 >= TRACE_DECODE_STEPS)):
            served.extend(serve_call(eng, traffic.batch(k), annotate))
            k += 1
    t1 = time.perf_counter()
    compiles["open"] = False
    if traced:
        jax.profiler.stop_trace()
    window = {"seconds": t1 - t0, "calls": k}
    decode_steps = eng.decode_steps - steps0
    log(f"window: {k} calls, {len(served)} requests, "
        f"{window['seconds']:.3f} s, {decode_steps} decode steps")
    log(f"compiles_in_window {compiles['window']}")
    stats = devs[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    in_use = int(stats.get("bytes_in_use", 0))

    # -- free the program's state, then the check
    weights = holder.pop("w")
    del mgr, eng
    gc.collect()
    failed = sum(1 for s in served
                 if len(s.out) != s.max_new
                 or not all(0 <= t < m.vocab for t in s.out))
    sample = pick_sample(served, seed)
    blocks = check_blocks(sample, cfg.max_len) if sample else []
    gap = widest_gap(block, m, weights, blocks) if sample \
        else float("inf")
    compared = sum(len(s.out) for s in sample)
    log(f"check: {len(sample)} requests, {compared} served tokens compared")
    checks, correct = judge(gap, failed, limits)

    # -- metrics
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak,
              "memory_in_use_bytes": in_use}
    result = {"correct": correct, "attempted": len(served),
              "failed": failed}
    if not traced:
        result["metrics"] = {
            n: {"value": end_to_end(n, served, window, setup_s),
                "unit": e2e_all[n]["unit"]} for n in e2e}
    else:
        from bench import trace as trace_mod

        summary = trace_mod.summarize(trace_mod.load(str(TRACE_DIR)))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        busiest = sorted(summary.modules.items(), key=lambda kv: -sum(kv[1]))
        log("trace programs: " + ", ".join(
            f"{n} x{len(d)} {sum(d):.3f}s" for n, d in busiest[:6]))
        held = summary.module_seconds("decode_step")[0]
        log(f"trace holds {held} of {decode_steps} decode steps")
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        # a trace that dropped events shows here, beside the readers
        # that then read nothing
        device["decode_steps"] = decode_steps
        device["decode_steps_traced"] = held
        peaks = load_peaks(dev.device_kind) if require_tpu else \
            {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
        ctx = Context(model=m, cfg=cfg, peaks=peaks, served=served,
                      decode_steps=decode_steps, trace=summary)
        result["metrics"] = {}
        for lm in layer_metrics:
            v = load_reader(lm["name"])(ctx)
            if v is not None:
                result["metrics"][lm["name"]] = {"value": v,
                                                 "unit": lm["unit"]}
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary.top_ops],
            "idle_gaps": [[n, s] for n, s in summary.idle_gaps]}
    result["device"] = device
    if control:
        c_checks, c_correct = judge(
            widest_gap(block, m, weights, blocks, quant="fp8") if sample
            else float("inf"), failed, limits)
        result["control"] = {"correct": c_correct, "checks": c_checks}
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return result


_COMPILES: Dict = {}


def _compile_counter() -> Dict:
    """Counts backend compiles while ``open`` (one listener per process)."""
    if not _COMPILES:
        import jax

        _COMPILES.update(open=False, window=0)

        def on_duration(event, _secs, **_kw):
            if event == COMPILE_EVENT and _COMPILES["open"]:
                _COMPILES["window"] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
    return _COMPILES
