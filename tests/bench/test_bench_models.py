"""A configuration finds its model code by its ``model_type``
(``bench/models``): each configuration's family module keeps the contract,
an unknown type is refused by name, and a family that exists only in
``sys.modules`` runs the whole harness with its own reference, weights
and counts, so a new architecture needs no edit to the harness."""

import copy
import json
import sys
import types
from pathlib import Path

import numpy as np
import pytest

from bench import reference
from bench.models import CONTRACT, family, load_config, mixtral
from bench_tiny import tiny_config, tiny_run

ROOT = Path(__file__).resolve().parents[2]
BM = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("c", BM["configs"], ids=lambda c: c["name"])
def test_config_resolves_to_a_family(c):
    cfg = load_config(c["name"])
    fam = family(cfg)
    assert fam.__name__ == f"bench.models.{cfg['model_type']}"
    for name in CONTRACT:
        assert callable(getattr(fam, name)), name
    a = fam.arch(cfg)
    for field in ("name", "vocab", "d_model", "num_layers", "first_k_dense",
                  "moe_layers", "num_experts", "d_expert", "top_k"):
        assert getattr(a, field) is not None, field


def test_unknown_model_type_names_the_missing_file():
    cfg = dict(tiny_config(), model_type="no_such_family")
    with pytest.raises(ModuleNotFoundError,
                       match=r"bench/models/no_such_family\.py"):
        family(cfg)
    with pytest.raises(ValueError, match="names no module"):
        family(dict(cfg, model_type="../mixtral"))


@pytest.mark.parametrize("change, message", [
    ({"hidden_act": "gelu"}, "SwiGLU"),
    ({"sliding_window": 4096}, "windowed"),
    ({"num_attention_heads": 5}, "KV heads"),
], ids=["gelu", "sliding_window", "uneven_kv_heads"])
def test_mixtral_family_refuses_what_it_cannot_compute(change, message):
    with pytest.raises(ValueError, match=message):
        mixtral.arch(dict(tiny_config(), **change))


def _plant_family(monkeypatch, kind, **override):
    """A family module ``bench.models.<kind>`` that re-exports the Mixtral
    family with ``override`` in place, only in ``sys.modules``."""
    mod = types.ModuleType(f"bench.models.{kind}")
    mod.__dict__.update({k: v for k, v in vars(mixtral).items()
                         if not k.startswith("__")})
    mod.__dict__.update(override)
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    return mod


def _counted(fn, calls, name):
    def wrapped(*args, **kw):
        calls[name] = calls.get(name, 0) + 1
        return fn(*args, **kw)
    return wrapped


def test_a_new_family_runs_the_whole_harness(monkeypatch):
    calls = {}
    _plant_family(monkeypatch, "planted_moe", **{
        n: _counted(getattr(mixtral, n), calls, n)
        for n in ("forward", "served_params", "token_flops")})
    cfg = dict(tiny_config(), model_type="planted_moe")
    out = tiny_run("reason", seed=2 ** 31 + 77, trace=True, cfg=cfg,
                   metrics=[{"name": "mfu", "unit": "%"}])
    assert out["correct"] is True
    assert out["metrics"]["mfu"]["value"] > 0
    assert set(calls) == {"forward", "served_params", "token_flops"}


def _shared_left_out(a, seed, seqs, reads, **kw):
    draw, apply = mixtral.reference_layer(a)

    def no_shared(key, layer, moe, quant):
        w = draw(key, layer, moe, quant)
        w.pop("shared", None)
        return w
    return reference.forward(a, seed, seqs, reads, layer=(no_shared, apply),
                             eps=a.rms_norm_eps, **kw)


def _logits_scaled(a, seed, seqs, reads, **kw):
    return [1.25 * x for x in mixtral.forward(a, seed, seqs, reads, **kw)]


@pytest.mark.parametrize("forward, shared", [
    (_shared_left_out, True), (_logits_scaled, False)],
    ids=["shared_experts_left_out", "logits_scaled"])
def test_a_wrong_family_reference_is_not_correct(monkeypatch, forward,
                                                 shared):
    _plant_family(monkeypatch, "wrong_moe", forward=forward)
    cfg = copy.deepcopy(tiny_config())
    cfg["model_type"] = "wrong_moe"
    if shared:
        cfg.update(n_shared_experts=1, moe_intermediate_size=128)
    out = tiny_run("reason", seed=2 ** 31 + 78, cfg=cfg)
    assert out["correct"] is False
    err = out["checked"]["logit_err_p90"]
    assert err["value"] > err["limit"]
    assert np.isfinite(err["value"])
