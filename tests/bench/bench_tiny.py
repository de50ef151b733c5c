"""A configuration and traffic small enough to run the whole benchmark on
the CPU: the mixtral-8x7b.pp8 file with tiny widths and more experts than
it routes to (16, top-4, so routing, dispatch over the servers and
near-ties are exercised), and mixes of the same shape as the chip's with
short lengths."""

import copy

from bench.models import load_config

DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def tiny_config(limit: float = 0.03) -> dict:
    """The chip's configuration at tiny widths; its compared number is the
    chip's, held to ``limit``."""
    cfg = copy.deepcopy(load_config("mixtral-8x7b.pp8"))
    cfg.update(hidden_size=128, intermediate_size=128,
               num_attention_heads=4, num_key_value_heads=2,
               num_local_experts=16, num_experts_per_tok=4, vocab_size=512,
               num_hidden_layers=3)
    # dropless: 128 tokens at capacity factor 1.25 give each server 160
    # rows, above the 32 x 4 a chunk can send one server's 4 experts
    cfg["serving"] = dict(cfg["serving"], max_batch=4, max_seq=512,
                          prefill_chunk=32, pool_tokens_per_client=128)
    cfg["check"] = {name: limit for name in cfg["check"]}
    return cfg


def tiny_mix(kind: str = "reason") -> dict:
    if kind == "reason":
        return {"pattern_seed": 5, "arrivals": {"rate_per_s": 4.0},
                "prompt_tokens": {"law": "lognormal", "median": 48,
                                  "sigma": 0.4, "min": 32, "max": 96},
                "output_tokens": {"law": "uniform", "min": 48, "max": 96},
                "round_prompt_to_chunk": True,
                "warm": {"fill_slots": True, "decode_steps": 2,
                         "min_budget": 8},
                "drain_s": 0, "check": {"requests": 8}}
    return {"pattern_seed": 6, "arrivals": {"rate_per_s": 2.0},
            "prompt_tokens": {"law": "lognormal", "median": 48, "sigma": 0.7,
                              "min": 16, "max": 128},
            "output_tokens": {"law": "lognormal", "median": 8, "sigma": 0.5,
                              "min": 2, "max": 24},
            "round_prompt_to_chunk": True,
            "warm": {"requests": 4, "prompt": [32, 64], "output": [3]},
            "drain_s": 5, "check": {"requests": 3}}


def tiny_run(kind: str = "reason", seed: int = 11, seconds: float = 2.0,
             trace: bool = False, limit: float = 0.03, controls=(),
             metrics=None, cfg=None):
    """One whole run of the harness on the CPU at the tiny size (the
    look for a chip is the only step left out); ``cfg`` in place of
    :func:`tiny_config`."""
    import time

    from bench import run

    name = "mx.reason" if kind == "reason" else "mx.chat"
    bm = run.load_benchmark()
    return run.run_cell(
        {"name": name, "chips": 1}, cfg or tiny_config(limit), tiny_mix(kind),
        seed=seed, seconds=seconds, trace=trace,
        metric_names=metrics or run.metrics_of(
            bm, name, "per_layer" if trace else "end_to_end"),
        device=DEVICE, peaks=PEAKS, t_start=time.perf_counter(),
        controls=controls)
