"""BENCHMARK.json and the files it names: every cell finds its
configuration, traffic mix and per-layer readers by name, and the entries
keep to the benchmark's format."""

import json
import re
from pathlib import Path

import pytest

from bench.models import family, load_config

ROOT = Path(__file__).resolve().parents[2]
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
WIDTHS = {"hidden_size", "intermediate_size", "moe_intermediate_size",
          "num_experts_per_tok", "q_lora_rank", "kv_lora_rank", "head_dim",
          "num_key_value_heads", "num_attention_heads"}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_top_level_keys():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["command"] == ["python3", "bench/run.py"]
    for p in BM["paths"]:
        assert (ROOT / p).is_dir()
    assert 1 <= BM["run_seconds"] <= 51


@pytest.mark.parametrize("c", BM["configs"], ids=lambda c: c["name"])
def test_config_file(c):
    assert NAME.match(c["name"]) and c["file"].startswith("bench/configs/")
    cfg = load_config(c["name"])
    assert cfg["source"] == c["source"] and cfg["name"] == c["name"]
    assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    for key, cut in cfg["reduced"].items():
        assert cfg[key] == cut["here"] != cut["published"]
        assert not key.endswith(("_dim", "_rank")) and key not in WIDTHS
    # the family refuses what it cannot compute as the file states it
    a = family(cfg).arch(cfg)
    assert a.name == c["name"] and a.moe_layers > 0
    assert a.num_experts % cfg["serving"]["num_servers"] == 0
    assert cfg["check"] and all(v > 0 for v in cfg["check"].values())


@pytest.mark.parametrize("w", BM["workloads"], ids=lambda w: w["name"])
def test_cell_finds_its_files(w):
    assert NAME.match(w["name"]) and w["chips"] in (1, 4)
    assert w["config"] in {c["name"] for c in BM["configs"]}
    mix = json.loads((ROOT / "bench" / "traffic"
                      / f"{w['traffic']}.json").read_text())
    assert {"pattern_seed", "arrivals", "prompt_tokens", "output_tokens",
            "warm", "check"} <= set(mix)
    e2e = [m["name"] for m in BM["end_to_end"]
           if "workloads" not in m or w["name"] in m["workloads"]]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = [m for m in BM["per_layer"] if w["name"] in m["workloads"]]
    assert layer and all(m["moves"] in e2e for m in layer)


@pytest.mark.parametrize("m", BM["per_layer"], ids=lambda m: m["name"])
def test_per_layer_reader(m):
    assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
    assert m["moves"] in {e["name"] for e in BM["end_to_end"]}
    assert set(m["workloads"]) <= {w["name"] for w in BM["workloads"]}


def test_metric_entries():
    names = [m["name"] for m in BM["end_to_end"] + BM["per_layer"]]
    assert len(names) == len(set(names))
    for m in BM["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert {m["name"] for m in BM["end_to_end"]} == {"tok_per_s", "setup_s"}


def test_refuses_to_run_without_a_chip():
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "bench" / "run.py"),
                        "--workload", BM["workloads"][0]["name"],
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
