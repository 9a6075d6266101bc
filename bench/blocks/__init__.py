"""A configuration's block: the mathematics of one kind of decoder, found
by name.

Every file under ``bench/configs/`` names its block (``"block":
"dense"``), and the block is the module ``bench/blocks/<block>.py``.
There is no default: a configuration can never be judged by another
block's reference.  Outside this package the harness reaches a model's
weights, its reference and its counts only through the module that
``load`` returns (``Config.block``, and ``Context.block`` for the
per-layer readers), so a new architecture is a new file here.

A block module provides:

``parse(raw) -> model``
    The model that the configuration's keys describe (``raw``: the file
    with its ``departures`` applied).  A frozen, hashable dataclass, since
    ``make_weights`` takes it as a static argument, with at least
    ``name``, ``arch`` (the program's registry name) and ``vocab``.  It
    raises ``ValueError`` on a published key the block does not model.
``program_fields(model) -> dict``
    The program's ``ModelConfig`` fields, with the values the
    configuration states; the harness refuses a program entry that
    differs.
``make_weights(model, key) -> dict``
    Random weights from a PRNG key, made on the device in one jitted call
    in the dtype they are served in.
``to_program_tree(model, w, like)``
    Those weights in the program's parameter tree (``like`` is the tree
    the engine built, as shapes); refuses a tree that differs.
``logits_at(model, w, tokens, read, quant=None)``
    The plain reference: float32 under
    ``jax.default_matmul_precision("highest")``, a full causal forward
    over ``tokens`` (S, T), logits (S, K, vocab) at positions ``read``
    (S, K).  It imports nothing of the program.  ``quant="fp8"`` is the
    lower-precision control.
Counts, from the shapes, of what the algorithm needs on one chip:
    ``weight_bytes_per_step(model)``, ``decode_token_flops(model, ctx)``,
    ``decode_token_bytes(model, ctx)`` (KV read and written) and
    ``prefill_flops(model, prompt)``.  A multiply-add is two operations.

The interface is what the harness calls, and nothing more: a count that
no reader takes yet stays inside its block until one does.
"""

from __future__ import annotations

import importlib
import importlib.util
import pathlib
import re
import types

BLOCKS_DIR = pathlib.Path(__file__).resolve().parent
INTERFACE = ("parse", "program_fields", "make_weights", "to_program_tree",
             "logits_at", "weight_bytes_per_step", "decode_token_flops",
             "decode_token_bytes", "prefill_flops")
NAME = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")


def load(name: str) -> types.ModuleType:
    """The block module ``name``, checked for the whole interface."""
    path = BLOCKS_DIR / f"{name}.py"
    if not (isinstance(name, str) and NAME.fullmatch(name)) or \
            importlib.util.find_spec(f"{__name__}.{name}") is None:
        raise LookupError(f"no block {name!r}: the lookup wanted {path}")
    block = importlib.import_module(f"{__name__}.{name}")
    missing = [n for n in INTERFACE if not callable(getattr(block, n, None))]
    if missing:
        raise TypeError(f"{block.__file__} does not provide {missing}")
    return block
