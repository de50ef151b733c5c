import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable (e)): prove the distribution config is
coherent for every (architecture × input shape × mesh) cell.

For each cell this lowers + compiles the real step function — ``train_step``
for train shapes, ``prefill_step`` / ``serve_step`` for inference shapes —
against abstract inputs (ShapeDtypeStruct, zero allocation) on the
production meshes (16×16 single-pod; 2×16×16 multi-pod), then records

* ``memory_analysis()``   — bytes per device (does it fit 16 GB HBM?)
* ``cost_analysis()``     — per-device HLO FLOPs / bytes (roofline terms)
* collective bytes        — parsed from the post-SPMD HLO text

Results land in ``experiments/dryrun/*.json``; ``benchmarks/roofline.py``
turns them into EXPERIMENTS.md §Roofline.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch kimi-k2-1t-a32b \
        --shape train_4k [--multi-pod]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""

import argparse
import json
import re
import time
import traceback
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import (ALL_SHAPES, ASSIGNED_ARCHS, InputShape, applicable,
                           get_config, shape_by_name)
from repro.configs.base import ModelConfig
from repro.distributed.sharding_rules import (batch_shardings,
                                              param_shardings, to_named)
from repro.kernels import ops as kops
from repro.launch.mesh import data_axes, make_production_mesh
from repro.models.transformer import ParallelCtx, build_model
from repro.core.moe_layer import MoERuntime, default_capacity
from repro.core import mapping as emap
from repro.training.optimizer import adafactor
from repro.training.train_loop import TrainState, make_train_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "experiments", "dryrun")

# op *applications* (name followed by '('), not references (%name)
OP_RE = re.compile(
    r"(?<![%\w-])(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(-start|-done)?(?:\.\d+)?\s*\(")
SHAPE_RE = re.compile(r"(f32|bf16|f16|s32|u32|s8|u8|pred|s64|f64)\[([\d,]*)\]")

DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
               "u8": 1, "pred": 1, "s64": 8, "f64": 8}


def parse_collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum output bytes of every collective op in the partitioned HLO
    (per-device bytes, matching cost_analysis conventions).  Handles
    tuple-shaped results (all-to-all) and async -start/-done pairs."""
    totals: Dict[str, float] = {}
    counts: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = OP_RE.search(line)
        if not m:
            continue
        if m.group(2) == "-done":      # counted at the -start
            continue
        kind = m.group(1)
        lhs = line[:m.start()]
        if "=" not in lhs:
            continue
        nbytes = 0
        for dm in SHAPE_RE.finditer(lhs):
            dt, dims = dm.group(1), dm.group(2)
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * DTYPE_BYTES[dt]
        totals[kind] = totals.get(kind, 0) + nbytes
        counts[kind] = counts.get(kind, 0) + 1
    totals["_counts"] = counts
    return totals


# ---------------------------------------------------------------------------
# Abstract inputs per (arch, shape)
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: InputShape) -> Dict:
    """ShapeDtypeStruct stand-ins for every model input of this shape."""
    sds = jax.ShapeDtypeStruct
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        specs = {"tokens": sds((B, S), jnp.int32),
                 "labels": sds((B, S), jnp.int32)}
    elif shape.kind == "prefill":
        specs = {"tokens": sds((B, S), jnp.int32)}
    else:  # decode: ONE new token against a cache of S tokens
        specs = {"tokens": sds((B, 1), jnp.int32)}
    if cfg.is_encoder_decoder and shape.kind == "train":
        specs["frames"] = sds((B, cfg.encoder_seq_len, cfg.d_model),
                              jnp.bfloat16)
    if cfg.mrope_sections is not None and shape.kind == "train":
        specs["mrope_positions"] = sds((3, B, S), jnp.int32)
    return specs


def _cache_sharding_specs(cache_abs, batch: int, dp: Tuple[str, ...],
                          seq_axes: Tuple[str, ...], seq_len: int):
    """Shard cache slots (dim == seq_len) over ``seq_axes`` and the batch
    dim over the data axes (when batch > 1 and data isn't used for slots).
    Leaves without either dim (ring windows, SSM states, cross-attn K/V)
    stay batch-sharded or replicated."""
    from jax.sharding import PartitionSpec as P

    batch_ok = batch > 1 and not set(dp) & set(seq_axes or ())

    def one(leaf):
        shape = leaf.shape
        spec = [None] * len(shape)
        start = 1 if len(shape) >= 3 else 0     # batch is never dim0 there
        if seq_axes:
            for i, d in enumerate(shape):
                if d == seq_len:
                    spec[i] = seq_axes
                    if batch_ok:
                        for j in range(start, len(shape)):
                            if j != i and shape[j] == batch:
                                spec[j] = dp
                                break
                    return P(*spec)
        if batch > 1:
            for i in range(start, len(shape)):
                if shape[i] == batch:
                    spec[i] = dp
                    return P(*spec)
        return P(*spec)

    return jax.tree.map(one, cache_abs)


# ---------------------------------------------------------------------------
# Cell runner
# ---------------------------------------------------------------------------

def moe_runtime_for(cfg: ModelConfig, mesh, shape: InputShape,
                    mode: str) -> Optional[MoERuntime]:
    if cfg.moe is None:
        return None
    S = mesh.shape["model"]
    dp_total = int(np.prod([mesh.shape[a] for a in data_axes(mesh)]))
    if mode == "a2a":
        tokens_per_client = shape.global_batch * shape.seq_len // (
            dp_total * S)
    else:
        tokens_per_client = max(shape.global_batch // dp_total, 1)
    from repro.core import expert_server
    table = emap.default_mapping(cfg.moe.num_experts, S, max_replicas=2)
    local = expert_server.make_local_table(
        cfg.moe.num_experts, S, np.zeros((S, 0), np.int32))
    return MoERuntime(
        mapping=jnp.asarray(table),
        alive=jnp.ones((S,), bool),
        local_table=jnp.asarray(local),
        num_servers=S,
        capacity=default_capacity(tokens_per_client, cfg.moe.top_k, S,
                                  cfg.moe.capacity_factor),
        gemm_impl="xla_dense",
    )


def build_cell(arch: str, shape: InputShape, mesh, cfg=None,
               unroll: bool = False):
    """Returns (jitted_fn, abstract_args) for one dry-run cell."""
    cfg = cfg or get_config(arch)
    dp = data_axes(mesh)
    S_servers = mesh.shape["model"]
    model = build_model(cfg, num_servers=S_servers if cfg.moe else 1)
    kops.set_default_impl("xla_dense")

    from repro.distributed.sharding_rules import train_phase_for
    mode = "a2a" if shape.kind in ("train", "prefill") else "replicated"
    rt = moe_runtime_for(cfg, mesh, shape, mode)
    # SP residual only where training is capacity-blocked (ZeRO-3 class):
    # small models fit without it and the per-layer reshards slow compile
    zero3 = train_phase_for(cfg.num_params(), mesh.shape["model"]) == "train"
    # decode: slot-shard the KV cache — over (data+model) for batch-1 long
    # context, over model otherwise (attention weights replicated; see
    # sharding_rules phase "decode" and EXPERIMENTS.md §Perf iter 1).
    # Latent attention has no slot-sharded decode: it decodes on the plain
    # path, which the compiler partitions from the cache's sharding.
    seq_shard = shape.kind == "decode" and cfg.mla is None
    seq_axes = ()
    if seq_shard:
        seq_axes = (*dp, "model") if shape.global_batch == 1 else ("model",)
    ctx = ParallelCtx(mesh=mesh, axis_data=dp, moe_runtime=rt,
                      moe_mode=mode, gemm_impl="xla_dense",
                      seq_shard_cache=seq_shard, seq_shard_axes=seq_axes,
                      sp_residual=(shape.kind == "train" and zero3),
                      remat=True, ce_chunk=512, unroll_scans=unroll)

    params_abs = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    phase = {"train": "train" if zero3 else "train_tp",
             "prefill": "serve", "decode": "decode"}[shape.kind]
    pspecs = param_shardings(params_abs, mesh, phase, dp=dp, mp="model")
    pshard = to_named(pspecs, mesh)

    specs = input_specs(cfg, shape)
    bshard = batch_shardings(mesh, dp)
    from jax.sharding import NamedSharding, PartitionSpec as P
    repl = NamedSharding(mesh, P())

    def batch_shd(name, spec):
        if name == "mrope_positions":
            return NamedSharding(mesh, P(None, dp, None))
        if name == "frames":
            return NamedSharding(mesh, P(dp, None, None))
        if spec.shape[0] == 1:           # batch 1 (long_500k): replicate
            return repl
        return bshard

    if shape.kind == "train":
        from repro.distributed.sharding_rules import adafactor_state_shardings
        opt = adafactor(lr=1e-3)
        state_abs = jax.eval_shape(
            lambda p: TrainState(params=p, opt_state=opt.init(p),
                                 step=jnp.zeros((), jnp.int32)),
            params_abs)
        opt_shard = to_named(
            adafactor_state_shardings(params_abs, pspecs), mesh)
        state_shard = TrainState(params=pshard, opt_state=opt_shard,
                                 step=repl, ef_residual=None)
        step = make_train_step(model, opt, ctx)
        in_shardings = (state_shard,
                        {k: batch_shd(k, v) for k, v in specs.items()})
        fn = jax.jit(step, in_shardings=in_shardings,
                     out_shardings=(state_shard, None),
                     donate_argnums=(0,))
        args = (state_abs, specs)
        return fn, args

    if shape.kind == "prefill":
        def prefill_step(params, tokens):
            logits, cache = model.prefill(params, tokens, ctx,
                                          max_slots=shape.seq_len)
            return logits, cache
        cache_abs = jax.eval_shape(
            lambda: model.init_cache(shape.global_batch, shape.seq_len,
                                     abstract=True))
        cache_spec = _cache_sharding_specs(
            cache_abs, shape.global_batch, dp, (), shape.seq_len)
        cache_shard = jax.tree.map(
            lambda s: NamedSharding(mesh, s), cache_spec,
            is_leaf=lambda x: isinstance(x, P))
        fn = jax.jit(prefill_step,
                     in_shardings=(pshard, batch_shd("tokens",
                                                     specs["tokens"])),
                     out_shardings=(None, cache_shard))
        return fn, (params_abs, specs["tokens"])

    # decode: serve_step — one token against a seq_len cache
    def serve_step(params, token, cache):
        logits, cache, _ = model.decode_step(params, token, cache, ctx)
        next_tok = jnp.argmax(logits, axis=-1, keepdims=True).astype(
            jnp.int32)
        return next_tok, cache

    cache_abs = model.init_cache(shape.global_batch, shape.seq_len,
                                 abstract=True)
    cache_spec = _cache_sharding_specs(
        cache_abs, shape.global_batch, dp, seq_axes, shape.seq_len)
    cache_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), cache_spec,
                               is_leaf=lambda x: isinstance(x, P))
    tok_shard = (repl if shape.global_batch == 1
                 else batch_shd("tokens", specs["tokens"]))
    fn = jax.jit(serve_step,
                 in_shardings=(pshard, tok_shard, cache_shard),
                 out_shardings=(tok_shard, cache_shard),
                 donate_argnums=(2,))
    return fn, (params_abs, specs["tokens"], cache_abs)


# ---------------------------------------------------------------------------
# Cost probes: XLA cost_analysis counts while-loop bodies ONCE, so layer
# scans hide depth.  We therefore compile 1-unit and 2-unit *unrolled*
# variants of each cell and extrapolate: total = C1 + (C2 - C1)·(units - 1).
# Every roofline number still comes from a compiled HLO artifact.
# ---------------------------------------------------------------------------

def probe_plan(cfg: ModelConfig):
    """Returns (probe_cfgs, combine(costs) -> cost_dict)."""

    def rep(**kw):
        return cfg.replace(**kw)

    if cfg.family == "audio":
        units = cfg.num_layers          # enc and dec both scale 1:1
        probes = [rep(num_layers=1, num_encoder_layers=1),
                  rep(num_layers=2, num_encoder_layers=2)]
        comb = lambda c: _lin(c[0], c[1], units)
    elif cfg.family == "hybrid":
        per = cfg.shared_block_every
        units = cfg.num_layers // per
        probes = [rep(num_layers=per), rep(num_layers=2 * per)]
        comb = lambda c: _lin(c[0], c[1], units)
    elif cfg.local_global_pattern:
        g = cfg.local_global_pattern + 1
        n_groups = cfg.num_layers // g
        remn = cfg.num_layers - n_groups * g
        probes = [rep(num_layers=g), rep(num_layers=2 * g)]
        if remn:
            probes.append(rep(num_layers=g + remn))
            comb = lambda c: _add(_lin(c[0], c[1], n_groups),
                                  _sub(c[2], c[0]))
        else:
            comb = lambda c: _lin(c[0], c[1], n_groups)
    else:
        k0 = cfg.moe.first_k_dense if cfg.moe else 0
        units = cfg.num_layers - k0
        probes = [rep(num_layers=k0 + 1), rep(num_layers=k0 + 2)]
        comb = lambda c: _lin(c[0], c[1], units)
    return probes, comb


def _lin(c1, c2, units):
    return {k: c1.get(k, 0) + (c2.get(k, 0) - c1.get(k, 0)) * (units - 1)
            for k in set(c1) | set(c2)}


def _add(a, b):
    return {k: a.get(k, 0) + b.get(k, 0) for k in set(a) | set(b)}


def _sub(a, b):
    return {k: a.get(k, 0) - b.get(k, 0) for k in set(a) | set(b)}


def _cost_dict(compiled) -> Dict[str, float]:
    cost = compiled.cost_analysis()
    coll = parse_collective_bytes(compiled.as_text())
    out = {"flops": float(cost.get("flops", 0.0)),
           "bytes": float(cost.get("bytes accessed", 0.0))}
    for k, v in coll.items():
        if k == "_counts":
            for kk, vv in v.items():
                out[f"n_{kk}"] = vv
        else:
            out[f"coll_{k}"] = v
    out["coll_total"] = sum(v for k, v in out.items()
                            if k.startswith("coll_"))
    return out


def run_probes(arch: str, shape: InputShape, mesh) -> Dict[str, float]:
    cfg = get_config(arch)
    probes, comb = probe_plan(cfg)
    costs = []
    for pc in probes:
        fn, args = build_cell(arch, shape, mesh, cfg=pc, unroll=True)
        compiled = fn.lower(*args).compile()
        costs.append(_cost_dict(compiled))
    return comb(costs)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             save: bool = True) -> Dict:
    shape = shape_by_name(shape_name)
    cfg = get_config(arch)
    ok, reason = applicable(cfg, shape)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    result = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
              "status": "skip", "reason": reason}
    if not ok:
        _save(result, save)
        return result

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    try:
        fn, args = build_cell(arch, shape, mesh)
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        coll = parse_collective_bytes(hlo)
        n_dev = int(np.prod(list(mesh.shape.values())))

        t0 = time.time()
        try:
            corrected = run_probes(arch, shape, mesh)
        except Exception as e:
            corrected = {"error": f"{type(e).__name__}: {e}"}
        t_probe = time.time() - t0

        result.update({
            "status": "ok",
            "probe_s": round(t_probe, 2),
            "roofline_corrected": corrected,
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "num_devices": n_dev,
            "flops_per_device": float(cost.get("flops", 0.0)),
            "bytes_per_device": float(cost.get("bytes accessed", 0.0)),
            "collective_bytes_per_device": {
                k: v for k, v in coll.items() if k != "_counts"},
            "collective_counts": coll.get("_counts", {}),
            "memory": {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "generated_code_bytes": mem.generated_code_size_in_bytes,
                "peak_bytes_per_device": (
                    mem.argument_size_in_bytes + mem.temp_size_in_bytes
                ) // n_dev if hasattr(mem, "argument_size_in_bytes") else None,
            },
        })
        print(f"[dryrun] {arch} × {shape_name} × {mesh_name}: OK "
              f"(lower {t_lower:.1f}s, compile {t_compile:.1f}s, "
              f"{result['flops_per_device']:.3e} flops/dev)")
    except Exception as e:  # a failing cell is a bug — record it loudly
        result.update({"status": "error", "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-4000:]})
        print(f"[dryrun] {arch} × {shape_name} × {mesh_name}: "
              f"FAILED — {type(e).__name__}: {e}")
    _save(result, save)
    return result


def _save(result: Dict, save: bool) -> None:
    if not save:
        return
    os.makedirs(RESULTS_DIR, exist_ok=True)
    name = f"{result['arch']}_{result['shape']}_{result['mesh']}.json"
    with open(os.path.join(RESULTS_DIR, name), "w") as f:
        json.dump(result, f, indent=2)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    args = ap.parse_args()

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    archs = ASSIGNED_ARCHS if (args.all or not args.arch) else [args.arch]
    shapes = ([s.name for s in ALL_SHAPES] if (args.all or not args.shape)
              else [args.shape])

    failures = 0
    for mp in meshes:
        for arch in archs:
            for shape in shapes:
                r = run_cell(arch, shape, mp)
                failures += r["status"] == "error"
    if failures:
        raise SystemExit(f"{failures} dry-run cells FAILED")
    print("dry-run: all requested cells passed")


if __name__ == "__main__":
    main()
