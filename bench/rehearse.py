#!/usr/bin/env python3
"""Compile a configuration's programs for a described TPU v5e chip, with
no chip: the weight draw, a 128-token prefill chunk, the decode step at
the full slot batch, and the reference's heaviest layer.  Prints each
program's memory analysis; raises what the chip's compiler would raise.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py mixtral-8x7b.pp8

Nothing runs, so nothing here is a time or a result.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
if sys.path and Path(sys.path[0]).resolve() == BENCH:
    sys.path.pop(0)
sys.path[:0] = [str(BENCH.parent), str(BENCH.parent / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

GiB = 2 ** 30


def main(name: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import weights
    from bench.models import family, load_config
    from repro.core.elastic import ServerPool
    from repro.models.transformer import ParallelCtx, build_model

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    cfg = load_config(name)
    fam = family(cfg)
    a = fam.arch(cfg)
    s = cfg["serving"]
    mcfg = fam.program_config(cfg, a)
    pool = ServerPool(mcfg, s["num_servers"],
                      tokens_per_client=s["pool_tokens_per_client"],
                      n_redundant=s["n_redundant"])
    model = build_model(mcfg, num_servers=s["num_servers"],
                        redundant_table=pool.redundant_table)
    rt = pool.runtime("pallas")
    B, S, C = s["max_batch"], s["max_seq"], s["prefill_chunk"]

    def spec(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=chip), tree)

    def report(what, compiled):
        m = compiled.memory_analysis()
        peak = (m.argument_size_in_bytes + m.output_size_in_bytes
                + m.temp_size_in_bytes - m.alias_size_in_bytes)
        print(f"{what}: arguments {m.argument_size_in_bytes / GiB:.2f} GiB, "
              f"temporaries {m.temp_size_in_bytes / GiB:.2f} GiB, peak "
              f"{peak / GiB:.2f} GiB", flush=True)

    def ctx_of(rt_arrays):
        mapping, alive, local = rt_arrays
        return ParallelCtx(moe_runtime=rt._replace(
            mapping=mapping, alive=alive, local_table=local), remat=False)

    params = spec(jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    rts = spec((rt.mapping, rt.alive, rt.local_table))
    slots = weights.slot_experts(a.num_experts, s["num_servers"],
                                 pool.redundant_table)
    print(f"{name}: {sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params)) / GiB:.2f} GiB of parameters; "
          f"expert slots {slots.tolist()}", flush=True)

    report("weight draw", jax.jit(
        fam.served_init(a, params, slots), out_shardings=chip).lower(
            spec(jax.ShapeDtypeStruct((2,), jnp.uint32))).compile())
    report("decode", jax.jit(lambda p, t, c, r: model.decode_step(
        p, t, c, ctx_of(r))[:2]).lower(
        params, spec(jax.ShapeDtypeStruct((B, 1), jnp.int32)),
        spec(model.init_cache(B, S, abstract=True)), rts).compile())
    report("prefill chunk", jax.jit(lambda p, t, c, st, r: model.prefill_chunk(
        p, t, c, st, ctx_of(r))[:2]).lower(
        params, spec(jax.ShapeDtypeStruct((1, C), jnp.int32)),
        spec(model.init_cache(1, S, abstract=True)),
        spec(jax.ShapeDtypeStruct((), jnp.int32)), rts).compile())
    draw, apply = fam.reference_layer(a)
    w = jax.eval_shape(lambda k: draw(k, a.first_k_dense, True, None),
                       jax.random.PRNGKey(0))
    report("reference MoE layer (16 x 2048 tokens, float32)",
           jax.jit(lambda w, x: apply(w, x)).lower(
               spec(w), spec(jax.ShapeDtypeStruct((16, S, a.d_model),
                                                  jnp.float32))).compile())
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
