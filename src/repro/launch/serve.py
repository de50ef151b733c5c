"""Serving launcher: the EAAS cluster front-end on a selectable architecture.

Usage:
    PYTHONPATH=src python -m repro.launch.serve --arch kimi-k2-1t-a32b \
        --reduced --requests 12 [--mode eaas|monolithic_ep|tp] \
        [--clients 4 --frontend-policy least_loaded] \
        [--fail-at 12:1] [--servers 4]

The size is chosen explicitly: ``--arch`` names a registered config (the
default, ``deepseek-r1-chip``, is one v5e chip's share of deepseek-r1 at its
published widths) and ``--reduced`` shrinks it to the tiny CPU-test widths.

``--clients N`` runs the paper's M:N attention:expert shape through
:class:`repro.serving.Cluster`; ``--mode tp`` has no disaggregated expert
tier and therefore only supports a single client.

``--exec-mode async`` serves through the event-driven expert tier
(per-expert queue lanes, ``--async-depth`` pipelined decode waves) under
the deterministic :class:`~repro.serving.clock.VirtualClock` — token
streams are bitwise identical to lockstep, only the timing model changes.

``chip_smoke.py`` at the repository root drives the same :func:`build`,
:func:`submit` and :func:`run` at the chip cut.
"""

from __future__ import annotations

import argparse
from typing import Callable, List, Optional

import numpy as np

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.serving import (Cluster, ClusterConfig, EngineConfig, Request,
                           SamplingParams, ServingEngine, VirtualClock)
from repro.serving.frontend import FRONTEND_POLICIES


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-r1-chip")
    ap.add_argument("--reduced", action="store_true",
                    help="shrink --arch to the tiny CPU-test widths")
    ap.add_argument("--mode", default="eaas",
                    choices=["eaas", "monolithic_ep", "tp"])
    ap.add_argument("--clients", type=int, default=1,
                    help="attention clients sharing the expert tier")
    ap.add_argument("--frontend-policy", default="round_robin",
                    choices=list(FRONTEND_POLICIES))
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-lens", default="8",
                    help="comma-separated prompt lengths; each request "
                         "draws one with the seeded generator")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the weights and the request stream")
    ap.add_argument("--servers", type=int, default=4)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=96)
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="prompt tokens per prefill step (0 = whole "
                         "prompt; one compile per distinct length)")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--fail-at", default=None,
                    help="step:rank — inject an expert-server failure")
    ap.add_argument("--exec-mode", default="lockstep",
                    choices=["lockstep", "async"],
                    help="async = event-driven expert tier with per-expert "
                         "queue lanes (needs --mode eaas and an MoE arch; "
                         "runs under the deterministic VirtualClock)")
    ap.add_argument("--async-depth", type=int, default=2,
                    help="decode waves in flight under --exec-mode async "
                         "(1 = lockstep cadence, K = deeper speculative "
                         "wave pipelining)")
    ap.add_argument("--elastic", action="store_true",
                    help="attach the full-system autoscaler: expert-server "
                         "count (and, with --clients > 1, client count and "
                         "scale-to-zero expert paging) follows observed "
                         "traffic; token streams never change")
    return ap


def build(args: argparse.Namespace):
    """The config, serving system and (optional) autoscaler ``args`` name."""
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    ecfg = EngineConfig(mode=args.mode, num_servers=args.servers,
                        max_batch=args.max_batch, max_seq=args.max_seq,
                        n_redundant=2,
                        prefill_chunk=args.prefill_chunk,
                        # dispatch buffers sized for the larger of a decode
                        # batch and a prefill chunk, so neither drops tokens
                        pool_tokens_per_client=max(args.max_batch,
                                                   args.prefill_chunk),
                        exec_mode=args.exec_mode,
                        async_depth=args.async_depth,
                        tp_batch_cap=max(args.max_batch // 2, 1))
    if args.exec_mode == "async" and (args.mode != "eaas" or not cfg.moe):
        # surface the engine's own validation as a CLI error
        raise SystemExit("--exec-mode async models the EAAS expert tier: "
                         "it needs --mode eaas and an MoE arch")
    # the async event timeline is defined against the deterministic
    # virtual cost model; lockstep keeps the wall clock (the seed default)
    clock_factory = VirtualClock if args.exec_mode == "async" else None
    if args.mode == "tp" or not cfg.moe:
        if args.clients != 1:
            raise SystemExit("--clients > 1 needs a shared expert tier: "
                             "an MoE arch in eaas/monolithic_ep mode")
        system = ServingEngine(cfg, ecfg, seed=args.seed)
    else:
        system = Cluster(cfg, ClusterConfig(
            clients=args.clients, frontend_policy=args.frontend_policy,
            engine=ecfg, max_clients=args.clients), seed=args.seed,
            clock_factory=clock_factory)
    scaler = None
    if args.elastic:
        from repro.serving.autoscale import Autoscaler, AutoscalerConfig
        scaler = Autoscaler(AutoscalerConfig(
            rate_per_server=12.0, min_servers=1, max_servers=args.servers,
            window=0.1, cooldown=0.1,
            rate_per_client=24.0, min_clients=1, max_clients=args.clients,
            expert_idle_fraction=0.5))
    return cfg, system, scaler


def submit(system, cfg: ModelConfig, args: argparse.Namespace,
           scaler=None) -> List[Request]:
    """Submit ``args.requests`` seeded requests; returns them."""
    lens = [int(n) for n in args.prompt_lens.split(",")]
    rng = np.random.default_rng(args.seed)
    reqs = []
    for i in range(args.requests):
        if scaler is not None:
            scaler.observe_arrival(system.clock)
        # a single length draws nothing, so the token stream of the
        # default request mix does not depend on --prompt-lens existing
        n = lens[int(rng.integers(len(lens)))] if len(lens) > 1 else lens[0]
        req = Request(i, rng.integers(0, cfg.vocab_size, size=n)
                      .astype(np.int32),
                      SamplingParams(max_new_tokens=args.max_new))
        system.submit(req)
        reqs.append(req)
    return reqs


def run(system, args: argparse.Namespace, scaler=None,
        on_step: Optional[Callable] = None):
    """Serve until every request is done; ``--fail-at`` kills a server
    before the named step.  ``on_step(system)`` runs before every step."""
    fail = None
    if args.fail_at:
        step_s, rank_s = args.fail_at.split(":")
        fail = (int(step_s), int(rank_s))

    def step_hook(s):
        if scaler is not None:
            scaler.step(s, s.clock)
        if fail and s.step_idx == fail[0]:
            print(f"[t={s.clock:.2f}s] injecting failure of server {fail[1]}")
            s.inject_server_failure(fail[1])
        if on_step is not None:
            on_step(s)

    return system.run(max_steps=5000, on_step=step_hook)


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    cfg, system, scaler = build(args)
    submit(system, cfg, args, scaler)
    m = run(system, args, scaler)
    print("\n=== summary ===")
    for k, v in m.summary().items():
        print(f"  {k}: {v}")


if __name__ == "__main__":
    main()
