"""Compile rehearsals for one TPU v5e chip, without the chip.

The TPU compiler is installed with jax and compiles for a described
topology: each test lowers a Pallas kernel, or the served decode step, at
the ``deepseek-r1-chip`` widths (latent attention, 16 of 256 experts; the
grouped GEMM at Mixtral-8x7B's too, and the GQA decode kernels at its
grouped-query widths) and compiles it, which raises what the chip's
compiler would raise
(unaligned blocks, refused kernel bodies, blocks that overflow VMEM,
programs that do not fit); the compiled decode step must also keep the
program's stage tags.  Nothing runs, so nothing here says anything
about results or times.

Only one process at a time may load the TPU library, so the topology is
described inside a module fixture, never at import: every pytest worker
collects the same tests and only the one given this file loads it.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import tracing
from repro.configs import get_config
from repro.core.elastic import ServerPool
from repro.kernels import ops
from repro.models.transformer import ParallelCtx, build_model

HBM_BYTES = 16 * 2 ** 30          # one v5e chip
CFG = get_config("deepseek-r1-chip")
BATCH, SEQ, CHUNK = 32, 2048, 64  # the smoke's engine shape
MIXTRAL_D, MIXTRAL_F = 4096, 14336   # bench/configs/mixtral-8x7b.pp8.json
MIXTRAL_HEADS, MIXTRAL_KV, MIXTRAL_HD = 32, 8, 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the kernel is there
    return compiled


@pytest.mark.parametrize("rows,groups,bank,k,n", [
    # the chip cut: 4 primaries + 2 redundant slots on one server
    (256, 6, 6, CFG.d_model, CFG.moe.d_expert),
    (256, 6, 6, CFG.moe.d_expert, CFG.d_model),
    # Mixtral-8x7B's stage: 4 servers of 2 experts and 264 rows each in one
    # call, 8 groups read at an offset in the bank of all 4 layers
    (4 * 264, 8, 4 * 8, MIXTRAL_D, MIXTRAL_F),
    (4 * 264, 8, 4 * 8, MIXTRAL_F, MIXTRAL_D),
], ids=["gate_up", "down", "mixtral_gate_up", "mixtral_down"])
def test_grouped_gemm_compiles(one_chip, rows, groups, bank, k, n):
    """The blocks chosen from these shapes compile and fit the chip's
    VMEM."""
    _compile(lambda x, w, g, at: ops.grouped_gemm(
                 x, w, g, impl="pallas", group_offset=at),
             _spec(one_chip, (rows, k), jnp.bfloat16),
             _spec(one_chip, (bank, k, n), jnp.bfloat16),
             _spec(one_chip, (groups,), jnp.int32),
             _spec(one_chip, (), jnp.int32))


def test_flash_decode_compiles(one_chip):
    kv = (BATCH, SEQ, MIXTRAL_KV, MIXTRAL_HD)
    _compile(lambda q, k, v, n: ops.flash_decode(q, k, v, n, impl="pallas"),
             _spec(one_chip, (BATCH, MIXTRAL_HEADS, MIXTRAL_HD),
                   jnp.bfloat16),
             _spec(one_chip, kv, jnp.bfloat16),
             _spec(one_chip, kv, jnp.bfloat16),
             _spec(one_chip, (BATCH,), jnp.int32))


def test_paged_flash_decode_compiles(one_chip):
    bs = 16                       # EngineConfig.kv_block_size
    per_seq = SEQ // bs
    pool = (BATCH * per_seq + 1, bs, MIXTRAL_KV, MIXTRAL_HD)
    _compile(lambda q, k, v, t, n: ops.paged_flash_decode(
        q, k, v, t, n, impl="pallas"),
        _spec(one_chip, (BATCH, MIXTRAL_HEADS, MIXTRAL_HD), jnp.bfloat16),
        _spec(one_chip, pool, jnp.bfloat16),
        _spec(one_chip, pool, jnp.bfloat16),
        _spec(one_chip, (BATCH, per_seq), jnp.int32),
        _spec(one_chip, (BATCH,), jnp.int32))


def test_combine_weighted_compiles(one_chip):
    T, k = 256, CFG.moe.top_k
    _compile(lambda x, w: ops.combine_weighted(x, w, impl="pallas"),
             _spec(one_chip, (T, k, CFG.d_model), jnp.bfloat16),
             _spec(one_chip, (T, k), jnp.float32))


@pytest.fixture(scope="module")
def decode_step(one_chip):
    """The served decode step of the chip cut, as the executor jits it
    (params, tokens, cache and the runtime placement arrays are arguments),
    with the expert GEMM ``auto`` picks on TPU, compiled for the chip."""
    pool = ServerPool(CFG, 4, tokens_per_client=max(BATCH, CHUNK),
                      n_redundant=2)
    model = build_model(CFG, num_servers=4,
                        redundant_table=pool.redundant_table)
    rt = pool.runtime("pallas")

    def spec(tree):
        return jax.tree.map(
            lambda a: _spec(one_chip, a.shape, a.dtype), tree)

    def decode(params, tokens, cache, rt_arrays):
        mapping, alive, local, bias = rt_arrays
        ctx = ParallelCtx(moe_runtime=rt._replace(
            mapping=mapping, alive=alive, local_table=local,
            route_bias=bias), remat=False)
        logits, cache, st = model.decode_step(params, tokens, cache, ctx)
        return logits, cache, st.expert_load

    return _compile(
        decode,
        spec(jax.eval_shape(model.init_params, jax.random.PRNGKey(0))),
        _spec(one_chip, (BATCH, 1), jnp.int32),
        spec(model.init_cache(BATCH, SEQ, abstract=True)),
        spec((rt.mapping, rt.alive, rt.local_table, rt.route_bias)))


def test_decode_step_fits_one_chip(decode_step):
    mem = decode_step.memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"decode step peak {peak / 2 ** 30:.2f} GiB")
    assert peak < HBM_BYTES, peak / 2 ** 30


def test_decode_step_carries_stage_tags(decode_step):
    """Every stage tag reaches the chip's program: the XLA ops' frontend
    attribute, and the grouped GEMM kernel's own ``kernel_metadata``."""
    text = decode_step.as_text()
    for stage in ("mla", "router", "dispatch", "server", "combine",
                  "head"):
        assert f'{tracing.STAGE_KEY}="{stage}"' in text, stage
    assert f'"{tracing.STAGE_KEY}":"gemm"' in text
    # latent attention is tagged in the place of GQA's
    assert f'{tracing.STAGE_KEY}="attention"' not in text


def test_decode_step_reads_expert_weights_in_place(decode_step):
    """No op of the compiled decode step makes a buffer shaped like an
    expert weight: the grouped GEMM reads the stacked bank of every layer
    and server in place, so neither the layer scan nor the servers slice
    or copy one.  Parameters, the layer loop's tuple elements and bitcasts
    are views, and the ops inside a fusion make no buffer of their own."""
    d, f = CFG.d_model, CFG.moe.d_expert
    # the fixture's 4 MoE layers of 4 servers, 4 primaries + 2 redundant
    groups = (CFG.num_layers - CFG.moe.first_k_dense) * 4 * (4 + 2)
    op = re.compile(r"\s*(?:ROOT )?%(\S+) = bf16\[([\d,]*)\]\S* ([\w-]+)\(")
    views = ("parameter", "get-tuple-element", "bitcast")
    copies, banks = [], 0
    for comp in decode_step.as_text().split("\n\n"):
        if "fus" in comp.strip().split("(", 1)[0]:
            continue                      # the body of a fusion
        for line in comp.splitlines():
            m = op.match(line)
            if not m:
                continue
            dims = tuple(int(v) for v in m.group(2).split(",") if v)
            if dims[-2:] not in ((d, f), (f, d)):
                continue
            if m.group(3) not in views:
                copies.append(m.group(1))
            elif m.group(3) == "bitcast" and dims[0] == groups:
                banks += 1                # every layer's servers, as groups
    assert not copies, copies
    assert banks == 3                     # the kernel's gate, up and down


def test_latent_attention_products_keep_the_tag(decode_step):
    """Each fusion that computes one of latent attention's products carries
    the ``mla`` tag on itself, where the profiler's op text shows it: a
    fusion that ends in an untagged op (a layout bitcast, the residual add
    of W_o) would leave that product's time out of ``mla_ms``."""
    products = []
    for comp in decode_step.as_text().split("\n\n"):
        if "fus" in comp.strip().split("(", 1)[0]:
            continue                      # the body of a fusion
        products += [line for line in comp.splitlines()
                     if "kind=kOutput" in line and "/mla/" in line]
    assert products
    untagged = [line.split(" = ")[0].strip() for line in products
                if f'{tracing.STAGE_KEY}="mla"' not in line]
    assert not untagged, untagged
