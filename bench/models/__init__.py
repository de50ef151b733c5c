"""Where a configuration finds its model code.

A configuration file (``bench/configs/<name>.json``) keeps the keys of its
published ``config.json``, ``model_type`` among them (``"mixtral"``,
``"deepseek_v3"``).  :func:`family` imports ``bench/models/<model_type>.py``,
the family module, which knows the architecture; nothing else in the
harness does.  A family module defines:

* ``arch(cfg)``: the sizes, as an object.  Code outside the family reads
  only ``name``, ``vocab``, ``d_model``, ``num_layers``, ``first_k_dense``,
  ``moe_layers``, ``num_experts`` (the routed experts this chip holds),
  ``d_expert`` and ``top_k``; a family adds any field of its own.  It
  refuses what it cannot compute as the file states it.
* ``program_config(cfg, a)``: the program's ``ModelConfig``.
* ``served_init(a, want, slots)``: ``key -> the program's parameter tree``,
  checked against ``want`` (the tree of shapes the program's own init
  returns); ``slots`` is the (servers, slots) expert id of each server
  slot.  ``served_params(a, seed, want, slots)``: that tree, drawn on the
  device from the seed.
* ``forward(a, seed, seqs, reads, quant=None, pad_to=512, batch=0)``: the
  float32 reference's logits at each sequence's read positions; ``quant``
  (``"int8"``, ``"fp8"``) computes the lower-precision control.
* ``reference_layer(a)``: ``(draw, apply)``, one layer of the reference:
  ``draw(key, layer, moe, quant)`` its float32 weights, ``apply(w, x,
  quant=None)`` the layer over a (batch, sequence, d) block.
* ``token_flops(a, position)``, ``chunk_flops(a, start, length)`` and
  ``expert_gemm_work(a, rows, experts_hit)``: the operations (and bytes)
  the model needs, counted from its shapes.

What does not depend on the architecture is shared, not copied: the seeded
draw primitives, every routed expert drawn by its global id, the server
slot layout (:mod:`bench.weights`); the control's rounding, the SwiGLU FFN,
the held experts applied under a gate of the family's routing, and the
blockwise layer-by-layer loop with its padding and head
(:mod:`bench.reference`); the grouped GEMM's work (:mod:`bench.flops`).
Nothing here imports the program.
"""

from __future__ import annotations

import importlib
import json
from pathlib import Path
from types import ModuleType
from typing import Dict

CONFIGS = Path(__file__).resolve().parent.parent / "configs"

# every name a family module defines
CONTRACT = ("arch", "program_config", "served_init", "served_params",
            "forward", "reference_layer", "token_flops", "chunk_flops",
            "expert_gemm_work")


def load_config(name: str) -> Dict:
    path = CONFIGS / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no configuration file {path}")
    return json.loads(path.read_text())


def family(cfg: Dict) -> ModuleType:
    """The family module of a configuration, by its ``model_type``."""
    kind = cfg.get("model_type")
    if not isinstance(kind, str) or not kind.isidentifier():
        raise ValueError(f"{cfg.get('name')}: model_type {kind!r} names no "
                         "module")
    name = f"{__name__}.{kind}"
    try:
        mod = importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise ModuleNotFoundError(
            f"{cfg.get('name')}: model_type {kind!r} has no family module: "
            f"no file bench/models/{kind}.py", name=name) from None
    missing = [n for n in CONTRACT if not hasattr(mod, n)]
    if missing:
        raise AttributeError(f"family module {name} lacks {missing}")
    return mod
