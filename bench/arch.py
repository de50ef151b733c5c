"""A configuration file of the benchmark, read into the sizes the
benchmark's own code needs (weights, reference, operation counts).

The file holds the published ``config.json`` keys under their published
names (a key that counts the routed experts, for instance, is
``num_local_experts`` in a Mixtral config and ``n_routed_experts`` in a
DeepSeek one), with the cuts listed in its ``reduced`` block.  Nothing here
imports the program.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict

CONFIGS = Path(__file__).with_name("configs")


@dataclasses.dataclass(frozen=True)
class Arch:
    name: str
    d_model: int
    num_layers: int
    first_k_dense: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    d_expert: int
    num_experts: int
    top_k: int
    num_shared_experts: int
    vocab: int
    rope_theta: float
    rms_norm_eps: float
    score_fn: str
    normalize_topk: bool

    @property
    def moe_layers(self) -> int:
        return self.num_layers - self.first_k_dense


def load_config(name: str) -> Dict:
    path = CONFIGS / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no configuration file {path}")
    return json.loads(path.read_text())


def _first(cfg: Dict, *keys, default=None):
    """The value of the first of ``keys`` the file has."""
    for k in keys:
        if k in cfg and cfg[k] is not None:
            return cfg[k]
    if default is None:
        raise KeyError(f"{cfg['name']}: none of {keys}")
    return default


def arch_of(cfg: Dict) -> Arch:
    """The sizes a configuration file states."""
    if cfg["hidden_act"] != "silu":
        raise ValueError(f"{cfg['name']}: only SwiGLU (silu) FFNs")
    if cfg.get("sliding_window") is not None:
        raise ValueError(f"{cfg['name']}: no windowed attention")
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return Arch(
        name=cfg["name"],
        d_model=d,
        num_layers=cfg["num_hidden_layers"],
        first_k_dense=_first(cfg, "first_k_dense_replace", default=0),
        num_heads=heads,
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=_first(cfg, "head_dim", default=d // heads),
        d_ff=cfg["intermediate_size"],
        # Mixtral names its expert width intermediate_size
        d_expert=_first(cfg, "moe_intermediate_size", "intermediate_size"),
        num_experts=_first(cfg, "n_routed_experts", "num_local_experts",
                           "num_experts"),
        top_k=cfg["num_experts_per_tok"],
        num_shared_experts=_first(cfg, "n_shared_experts", default=0),
        vocab=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        score_fn=_first(cfg, "scoring_func", default="softmax"),
        # Mixtral renormalises its top-k scores without a key saying so
        normalize_topk=bool(_first(cfg, "norm_topk_prob", default=True)),
    )
