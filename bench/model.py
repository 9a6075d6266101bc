"""A model configuration, read from ``bench/configs/<name>.json``.

The file names its ``block``, the module ``bench/blocks/<block>.py`` that
reads it, and holds the published ``config.json`` keys with their
published values; ``departures`` gives the values the program runs
instead (the run, and the reference with it, takes the published keys
overlaid with them; ``departure_notes`` says why).  A ``serve`` block
gives the serving shape: rows, context, tenants; ``reduced`` names the
serving keys cut for the chip and ``cuts`` gives the reason for each.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib
import types
from typing import Any, Dict, Tuple

from bench import blocks

BENCH_DIR = pathlib.Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Tenant:
    name: str
    pages: int                # virtual pages of the tenant's extent
    popularity: float         # share of offline requests


@dataclasses.dataclass(frozen=True)
class Config:
    model: Any                # the block's model
    block: types.ModuleType   # bench/blocks/<block>.py
    max_batch: int
    max_len: int
    tenants: Tuple[Tenant, ...]
    raw: Dict

    @property
    def name(self) -> str:
        return self.model.name


def parse(raw: Dict) -> Config:
    """The configuration as it runs: the published keys overlaid with the
    file's ``departures``, read by the block the file names."""
    if "block" not in raw:
        path = BENCH_DIR / "configs" / f"{raw.get('name')}.json"
        raise LookupError(f"{path} names no block: its \"block\" key names "
                          "the file bench/blocks/<block>.py that reads it")
    block = blocks.load(raw["block"])
    raw = {**raw, **raw.get("departures", {})}
    serve = raw["serve"]
    tenants = tuple(Tenant(t["name"], t["pages"], t["popularity"])
                    for t in serve["tenants"])
    return Config(model=block.parse(raw), block=block,
                  max_batch=serve["max_batch"], max_len=serve["max_len"],
                  tenants=tenants, raw=raw)


def load(name: str) -> Config:
    path = BENCH_DIR / "configs" / f"{name}.json"
    return parse(json.loads(path.read_text()))


def load_json(kind: str, name: str) -> Dict:
    """``bench/<kind>/<name>.json``: a traffic mix or a cell's limits."""
    return json.loads((BENCH_DIR / kind / f"{name}.json").read_text())
