"""The system under test, built as a configuration file states it.

This is the benchmark's one seam into the program: it turns the file's
published keys (through its family module, ``bench/models``) and its
``serving`` block into the program's ``ModelConfig`` and ``EngineConfig``,
builds a ``repro.serving.Cluster`` around a recording wall clock, and loads
the benchmark's own weights into it.  ``Cluster`` takes no parameters, so
the engine's weight initialiser is swapped for the benchmark's loader
while the cluster is built.
"""

from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Dict, List, Tuple

import jax

from bench import weights
from bench.models import family


def model_config(cfg: Dict, a):
    """The program's ``ModelConfig`` of a configuration file, by its
    family."""
    return family(cfg).program_config(cfg, a)


def engine_config(serving: Dict):
    from repro.serving import EngineConfig

    keys = ("mode", "num_servers", "n_redundant", "max_batch", "max_seq",
            "prefill_chunk", "policy", "pool_tokens_per_client",
            "decode_mode", "exec_mode", "kv_mode", "gemm_impl")
    return EngineConfig(**{k: serving[k] for k in keys})


def recording_clock():
    """A ``WallClock`` that also keeps every bracket it returns, as
    ``(kind, seconds)``."""
    from repro.serving.clock import WallClock

    class RecordingClock(WallClock):
        def __init__(self):
            super().__init__()
            self.brackets: List[Tuple[str, float]] = []

        def stop(self, kind, **kw):
            dt = super().stop(kind, **kw)
            self.brackets.append((kind, dt))
            return dt

    return RecordingClock()


@contextmanager
def _loader(served_params, a, seed: int, timings: Dict):
    """Swap the engine's weight initialiser for the benchmark's draw."""
    from repro.models.transformer import build_model
    from repro.serving import engine

    program_init = engine.init_params

    def load(cfg, num_servers, redundant_table, key):
        model = build_model(cfg, num_servers=num_servers,
                            redundant_table=redundant_table)
        want = jax.eval_shape(model.init_params, key)
        slots = weights.slot_experts(cfg.moe.num_experts, num_servers,
                                     redundant_table)
        t0 = time.perf_counter()
        params = served_params(a, seed, want, slots)
        jax.block_until_ready(params)
        timings["weights_s"] = time.perf_counter() - t0
        return params

    engine.init_params = load
    try:
        yield
    finally:
        engine.init_params = program_init


def build(cfg: Dict, a, seed: int):
    """(cluster, the client engine, its recording clock, timings)."""
    from repro.serving import Cluster, ClusterConfig

    s = cfg["serving"]
    if s["clients"] != 1:
        raise ValueError("the harness drives one attention client")
    timings: Dict = {}
    fam = family(cfg)
    with _loader(fam.served_params, a, seed, timings):
        cluster = Cluster(fam.program_config(cfg, a), ClusterConfig(
            clients=1, engine=engine_config(s), max_clients=1),
            seed=seed, clock_factory=recording_clock)
    eng = cluster.clients[0]
    return cluster, eng, eng.clk, timings
