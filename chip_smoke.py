#!/usr/bin/env python3
"""Bring-up smoke of the EaaS serving path on TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the expert tier across four chips

One chip, in one process:

1. device report;
2. the ``repro.launch.serve`` path (its own ``build``/``submit``/``run``)
   at the ``deepseek-r1-chip`` cut: ``Cluster``, 1 client, ``mode="eaas"``,
   4 expert servers with 2 redundant slots each, lockstep, dense KV cache,
   wall clock.  It serves 8 seeded requests (prompts of 64-256 tokens, 32
   new tokens each) and kills expert server 1 mid-decode.  Checked: every
   request got its tokens, every logit is finite, the failure recompiled
   nothing and stalled no step;
3. one MoE layer at the same widths against the float32 reference
   (:func:`repro.core.reference.moe_layer_ref`).

``--chips 4`` runs only the expert tier across devices: ``_moe_apply`` on a
(1, 4) ``("data", "model")`` mesh, one expert server per chip, in ``a2a``
and ``replicated`` mode against the single-device layer on the same
inputs, and a server failure with every expert replicated.

Lines before the last are smoke output, not metrics.  The last line is one
JSON object, ``{"ok": true, "device": {...}}``.  Without a TPU, or when any
phase fails, the script exits non-zero and prints no such line.
"""

from __future__ import annotations

import argparse
import gc
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import tracing  # noqa: E402

ARCH = "deepseek-r1-chip"
GiB = 2 ** 30

# The served path rounds to bf16 (unit roundoff 2^-9, about 2e-3) at the
# gate/up outputs, their product, the down projection and the layer output;
# independent roundings add to a few 1e-3 of relative L2 error against the
# float32 reference.  1e-2 keeps that headroom, while a token sent to a
# wrong expert, or dropped, moves the error to order 1.
MOE_REL_TOL = 1e-2
# Across the mesh the same bf16 partial products are summed in another
# order and rounded once more: a bf16 ulp or two per element.
MESH_REL_TOL = 5e-3
# A replica is the same weights on another server: failover may reorder
# nothing but which server computes a row, so the output must not move.
FAILOVER_REL_TOL = 1e-6


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")
    log(f"ok: {what}")


def rel_err(y, y_ref) -> float:
    y = np.asarray(y, np.float64)
    y_ref = np.asarray(y_ref, np.float64)
    return float(np.linalg.norm(y - y_ref) / np.linalg.norm(y_ref))


def require_tpu() -> dict:
    """The device report; refuses anything but a TPU backend."""
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{d.platform!r} ({d.device_kind})")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def compiles_line() -> str:
    """The process's compiles so far, dropped rows and grouped GEMM row
    tiles, from :mod:`repro.tracing`'s counters."""
    c = tracing.counters()
    return (f"backend compiles {c.get('jax.compiles', 0):.0f}, "
            f"{c.get('jax.compile_s', 0.0):.1f} s; persistent-cache hits "
            f"{c.get('jax.cache_hits', 0):.0f}; inside engine steps "
            f"{c.get('engine.step_compiles', 0):.0f}; dropped rows "
            f"{c.get('moe.dropped_rows', 0):.0f}; grouped GEMM row tiles "
            f"live {c.get('gemm.tiles_live', 0):.0f} of "
            f"{c.get('gemm.tiles', 0):.0f}")


def _watch_logits(executor) -> list:
    """Count non-finite logits of every prefill and decode step."""
    counts = []
    for name in ("prefill", "prefill_chunk", "decode"):
        fn = getattr(executor, name)

        def watched(*a, _fn=fn, **kw):
            out = _fn(*a, **kw)
            logits = out[0] if isinstance(out, tuple) else out
            counts.append(int(jnp.sum(~jnp.isfinite(logits))))
            return out
        setattr(executor, name, watched)
    return counts


def serve_phase(reduced: bool = False, seed: int = 0, max_seq: int = 2048,
                max_batch: int = 32) -> None:
    """Phase 2: serve seeded requests through ``repro.launch.serve``."""
    from repro.launch import serve

    args = serve.build_parser().parse_args([
        "--arch", ARCH, "--clients", "1", "--mode", "eaas",
        "--servers", "4", "--requests", "8",
        "--prompt-lens", "64,128,192,256", "--max-new", "32",
        "--max-batch", str(max_batch), "--max-seq", str(max_seq),
        "--prefill-chunk", "64", "--seed", str(seed)]
        + (["--reduced"] if reduced else []))
    t0 = time.perf_counter()
    cfg, system, scaler = serve.build(args)
    eng = system.clients[0]
    jax.block_until_ready(eng.executor.params)
    log(f"serve: built {cfg.arch_id} (d_model {cfg.d_model}, "
        f"{cfg.num_heads}x{cfg.head_dim} heads, kv {cfg.num_kv_heads}, "
        f"{cfg.moe.num_experts} experts top-{cfg.moe.top_k}, d_expert "
        f"{cfg.moe.d_expert}, vocab {cfg.vocab_size}) in "
        f"{time.perf_counter() - t0:.1f} s")
    reqs = serve.submit(system, cfg, args, scaler)
    prefill_steps = sum(-(-len(r.prompt) // args.prefill_chunk)
                        for r in reqs)
    fail_step = prefill_steps + 8             # eight decode steps in
    args.fail_at = f"{fail_step}:1"
    nonfinite = _watch_logits(eng.executor)
    starts, decode_cache = [], {}

    def on_step(s):
        starts.append(time.perf_counter())
        if s.step_idx == fail_step:
            decode_cache["before"] = eng.executor._jit_decode._cache_size()

    t0 = time.perf_counter()
    m = serve.run(system, args, scaler, on_step=on_step)
    starts.append(time.perf_counter())
    log(f"serve: {len(starts) - 1} steps ({prefill_steps} prefill) in "
        f"{starts[-1] - t0:.1f} s, {m.summary()['output_tokens']} tokens")

    check(all(len(r.output_tokens) == args.max_new
              and r.finish_time is not None for r in reqs),
          f"all {len(reqs)} requests completed {args.max_new} tokens")
    check(len(nonfinite) > 0 and sum(nonfinite) == 0,
          f"every logit finite over {len(nonfinite)} prefill/decode steps")
    fails = [e for e in m.events if e.get("event") == "server_fail"]
    check(len(fails) == 1 and not bool(eng.pool.smap.alive[1]),
          f"expert server 1 failed at step {fail_step}")
    after = eng.executor._jit_decode._cache_size()
    check(decode_cache.get("before") == after == 1,
          f"failover recompiled nothing (decode executables {after})")
    dur = np.diff(starts)
    before = dur[prefill_steps + 1:fail_step]   # decode steps, compile out
    post = dur[fail_step:fail_step + 5]
    log(f"serve: decode step s before failure median "
        f"{statistics.median(before):.4f} max {before.max():.4f}; "
        f"the 5 after {np.round(post, 4).tolist()}")
    check(post.max() <= 3 * before.max(),
          "no decode step after the failure took over 3x the slowest "
          "before it")
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log(f"serve: device memory peak "
            f"{stats['peak_bytes_in_use'] / GiB:.2f} GiB of "
            f"{stats.get('bytes_limit', 0) / GiB:.2f} GiB")


def moe_layer_phase(reduced: bool = False, seed: int = 1,
                    tokens: int = 256) -> None:
    """Phase 3: one EaaS MoE layer against the float32 reference."""
    from repro.configs import get_config
    from repro.core.elastic import ServerPool
    from repro.core.moe_layer import eaas_moe_apply, init_eaas_moe
    from repro.core.reference import moe_layer_ref

    cfg = get_config(ARCH)
    if reduced:
        cfg = cfg.reduced()
    m = cfg.moe
    pool = ServerPool(cfg, 4, tokens_per_client=tokens, n_redundant=2)
    rt = pool.runtime()._replace(capacity=tokens * m.top_k)   # no drops
    kp, kx = jax.random.split(jax.random.PRNGKey(seed))
    params = jax.jit(lambda k: init_eaas_moe(
        k, cfg, 4, redundant_table=pool.redundant_table))(kp)
    # the MoE input is RMS-normalised: unit scale per token
    x = jax.random.normal(kx, (tokens, cfg.d_model)).astype(jnp.bfloat16)
    y, st = jax.jit(lambda p, v: eaas_moe_apply(
        p, v, m, rt, activation=cfg.activation))(params, x)
    with jax.default_matmul_precision("highest"):
        y_ref = jax.jit(lambda p, v: moe_layer_ref(
            p, v, m, cfg.activation))(params, x)
    check(int(st.dropped) == 0 and int(st.miss) == 0,
          "MoE layer dropped and missed no token")
    err = rel_err(y, y_ref)
    check(bool(jnp.all(jnp.isfinite(y))) and err <= MOE_REL_TOL,
          f"MoE layer ({tokens} tokens, d {cfg.d_model}, d_expert "
          f"{m.d_expert}, {m.num_experts} experts on 4 servers) vs float32 "
          f"reference: rel L2 err {err:.2e} <= {MOE_REL_TOL:g}")


def four_chip_phase(reduced: bool = False, seed: int = 2,
                    tokens: int = 128) -> None:
    """The expert tier across four devices, one server per device."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.core import expert_server, load_balance
    from repro.core.moe_layer import default_runtime, init_eaas_moe
    from repro.models.transformer import ParallelCtx, _moe_apply

    devs = jax.devices()
    check(len(devs) == 4, f"four devices ({len(devs)})")
    cfg = get_config(ARCH)
    if reduced:
        cfg = cfg.reduced()
    E, S, k = cfg.moe.num_experts, 4, cfg.moe.top_k
    # every expert has a replica on another server, so a failure is covered
    mapping, red = load_balance.eplb_plan(np.ones(E), S, n_redundant=E // S,
                                          max_replicas=2)
    rt = default_runtime(cfg, S, tokens, redundant_table=red)._replace(
        mapping=jnp.asarray(mapping),
        local_table=jnp.asarray(expert_server.make_local_table(E, S, red)),
        capacity=tokens * k)
    kp, kx = jax.random.split(jax.random.PRNGKey(seed))
    params = jax.jit(lambda key: init_eaas_moe(
        key, cfg, S, redundant_table=red))(kp)
    x = jax.random.normal(kx, (tokens, cfg.d_model)).astype(jnp.bfloat16)

    def layer(ctx):
        return jax.jit(lambda p, v: _moe_apply(p, v, cfg, ctx))

    y_local, _ = layer(ParallelCtx(moe_runtime=rt, remat=False))(params, x)

    mesh = jax.make_mesh((1, S), ("data", "model"), devices=devs,
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    servers = {name: jax.device_put(w, NamedSharding(mesh, P("model")))
               for name, w in params["servers"].items()}
    placed = {shard.device.id: shard.index[0].start
              for shard in servers["w_gate"].addressable_shards}
    log(f"4 chips: device id -> expert server {placed}")
    check(sorted(placed.values()) == list(range(S))
          and len(placed) == S and all(
              len({sh.device.id for sh in w.addressable_shards}) == S
              for w in servers.values()),
          "each expert server's weights sit on their own device")
    sharded = dict(params, servers=servers)

    outs = {}
    for mode in ("a2a", "replicated"):
        ctx = ParallelCtx(mesh=mesh, axis_data=("data",), moe_runtime=rt,
                          moe_mode=mode, remat=False)
        y, st = layer(ctx)(sharded, x)
        outs[mode] = y
        err = rel_err(y, y_local)
        check(int(st.dropped) == 0 and int(st.miss) == 0
              and err <= MESH_REL_TOL,
              f"{mode} on the mesh vs single-device layer: rel L2 err "
              f"{err:.2e} <= {MESH_REL_TOL:g}")
    dead = rt._replace(alive=rt.alive.at[1].set(False))
    ctx = ParallelCtx(mesh=mesh, axis_data=("data",), moe_runtime=dead,
                      moe_mode="a2a", remat=False)
    y_dead, st = layer(ctx)(sharded, x)
    err = rel_err(y_dead, outs["a2a"])
    check(int(st.miss) == 0 and err <= FAILOVER_REL_TOL,
          f"server 1 dead, replicas serve its experts: rel L2 change "
          f"{err:.2e} <= {FAILOVER_REL_TOL:g}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the expert tier across four chips")
    args = ap.parse_args(argv)
    device = require_tpu()
    log(f"device: {device['platform']} {device['kind']} x{device['count']}"
        f", jax {jax.__version__}")
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    tracing.watch_compiles()
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chip_phase()
    else:
        serve_phase()
        log(f"after serve: {compiles_line()}")
        gc.collect()                  # one engine's params at a time
        moe_layer_phase()
    log(f"{compiles_line()}; total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
