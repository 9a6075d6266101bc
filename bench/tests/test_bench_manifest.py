"""Every name in BENCHMARK.json resolves to the files the harness finds it
by, and the manifest keeps the shape the harness reads."""

import json
import re

import pytest

from bench import blocks, cell, model, traffic
from bench.model import BENCH_DIR

BENCH = json.loads((BENCH_DIR.parent / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
WORKLOADS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_a_cell_finds_its_config_mix_and_limits(workload):
    wl = cell.find_workload(BENCH, workload)
    cfg = model.load(wl["config"])
    traffic.Traffic(traffic.load_mix(wl["traffic"]), cfg, 1)
    limits = model.load_json("cells", workload)["limits"]
    assert 0 < limits["max_logit_gap"] < 1
    assert wl["chips"] == 1
    e2e = [m["name"] for m in BENCH["end_to_end"]
           if workload in m.get("workloads", [workload])]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(cell.metric_applies(m, workload, e2e)
               for m in BENCH["per_layer"])


def test_configs_name_their_files():
    for c in BENCH["configs"]:
        raw = json.loads((BENCH_DIR.parent / c["file"]).read_text())
        assert raw["name"] == c["name"]
        assert sorted(raw["reduced"]) == sorted(c["reduced"])
        assert sorted(raw["cuts"]) == sorted(raw["reduced"])
        model.parse(raw)


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_a_config_names_a_block_that_provides_the_interface(config):
    raw = json.loads((BENCH_DIR.parent / config["file"]).read_text())
    assert (blocks.BLOCKS_DIR / f"{raw['block']}.py").is_file()
    block = blocks.load(raw["block"])
    assert model.parse(raw).block is block
    for name in blocks.INTERFACE:
        assert callable(getattr(block, name)), name
    # no default: a missing key or a block with no file fails at load
    with pytest.raises(LookupError, match=r"bench/blocks/nope\.py"):
        model.parse(dict(raw, block="nope"))
    with pytest.raises(LookupError, match=rf"{config['name']}\.json names "
                       "no block"):
        model.parse({k: v for k, v in raw.items() if k != "block"})


def test_departures_overlay_the_published_values():
    raw = json.loads((BENCH_DIR / "configs" / "stablelm3b.json").read_text())
    assert raw["rope_pct"] == 0.25                  # as published
    assert model.parse(raw).model.rope_fraction == 1.0
    with pytest.raises(ValueError, match="modelled"):
        model.parse(dict(raw, departures={}, scale_emb=12))
    mini = json.loads((BENCH_DIR / "configs" / "minicpm2b.json").read_text())
    assert mini["scale_emb"] == 12 and mini["rms_norm_eps"] == 1e-5
    assert model.parse(mini).model.norm_eps == 1e-6


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_a_per_layer_metric_has_its_reader(metric):
    assert NAME.match(metric["name"])
    assert callable(cell.load_reader(metric["name"]))
    moves = {m["name"]: m for m in BENCH["end_to_end"]}[metric["moves"]]
    for w in metric.get("workloads", []):
        assert w in WORKLOADS
        assert w in moves.get("workloads", WORKLOADS)


def test_end_to_end_metrics_and_bounds():
    names = [m["name"] for m in BENCH["end_to_end"]]
    assert names[0] == "setup_s" and len(set(names)) == len(names)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    served = [cell.Served(0, "t0", None, 2, [1, 2], 0.5)]
    for n in names:
        assert cell.end_to_end(n, served, {"seconds": 2.0}, 3.0) > 0
