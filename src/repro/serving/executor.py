"""Jitted step execution — the executor half of the engine split.

The :class:`Executor` owns the model params, the batched KV cache, the
per-slot prefill staging caches, and the jitted step variants:

* ``prefill``       — whole-prompt prefill into a fresh batch-1 cache
  (the pre-split path; one compile per bucketed prompt length);
* ``prefill_chunk`` — chunked-prefill continuation against a staging cache
  (decoder family only; one compile per distinct chunk length);
* ``decode``        — one token for the whole slot batch, in one of three
  modes: ``lockstep`` (single full-batch step, the pre-split behaviour),
  ``pipelined`` (two half-batch microbatches as *independent* subgraphs —
  :func:`repro.core.overlap.split_batch_decode` — so the expert round-trip
  of microbatch A overlaps the attention of microbatch B, paper §4.2), or
  ``serialized`` (same split with an artificial dependency: the ablation
  baseline, bit-identical outputs, collectives exposed).

``kv_mode="paged"`` swaps the dense per-slot cache for the block-pool
cache: one shared pool per layer plus per-slot block tables owned by the
host-side scheduler.  Block tables and lengths are jit *arguments* (data,
not structure) — admission, prefix-cache sharing and preemption rewrite
them between steps without recompiling.  All paged prefill goes through the
chunk path (prefix-cache hits start chunks mid-prompt; there is no staging
cache — pool blocks are the real storage), and paged decode is
lockstep-only (the pool is shared across the batch, so a microbatch split
has no batch axis to cut).

The expert→server mapping, liveness mask and local placement table remain
jit *arguments*: failover and rebalancing never recompile.  A pool resize
(:meth:`resize`) re-shards the expert weights and rebuilds the jits for the
new static server count — the AOT-per-server-count story.

Each step method is a host span of its own name (``executor.decode``,
``executor.prefill_chunk``, ``executor.prefill``; :mod:`repro.tracing`);
building the placement arrays is ``executor.runtime``.  The MoE step
programs also return, summed over layers and servers, the rows their
dispatch buffers dropped and the grouped GEMM's row tiles, live and laid
out; :meth:`Executor.pull_load` brings them to the host with the step's
expert load, in one transfer, and adds them to ``moe.dropped_rows``,
``gemm.tiles_live`` and ``gemm.tiles``, and the load's sum (the (token,
k) routes that landed on the experts held) to ``moe.routes_held``.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core import expert_server
from repro.core.overlap import split_batch_decode
from repro.models import kv_cache as kvc
from repro.models.transformer import Model, ParallelCtx


# the counters of :meth:`Executor.pull_load`, in the order of _counts
COUNTERS = ("moe.dropped_rows", "gemm.tiles_live", "gemm.tiles")


def _counts(st):
    """What a MoE step adds to the :data:`COUNTERS`."""
    return st.dropped, st.gemm_tiles_live, st.gemm_tiles


class Executor:
    """Owns params + caches + jitted step variants for one engine."""

    def __init__(self, model: Model, params, pool, *, max_batch: int,
                 max_seq: int, gemm_impl: str = "auto",
                 decode_mode: str = "lockstep", kv_mode: str = "dense",
                 kv_block_size: int = 16, kv_num_blocks: int = 0):
        assert decode_mode in ("lockstep", "pipelined", "serialized"), \
            decode_mode
        assert kv_mode in ("dense", "paged"), kv_mode
        if decode_mode != "lockstep":
            if model.cache_batch_axis is None:
                raise ValueError(
                    f"decode_mode={decode_mode!r} needs a model family with "
                    "a uniform cache batch axis (decoder-family only)")
            if max_batch % 2:
                raise ValueError(
                    f"decode_mode={decode_mode!r} needs an even max_batch "
                    f"(got {max_batch}) to form two microbatches")
            if kv_mode == "paged":
                raise ValueError(
                    "kv_mode='paged' shares one block pool across the "
                    "batch — microbatch-split decode modes need the dense "
                    "per-slot cache (use decode_mode='lockstep')")
        if kv_mode == "paged":
            if model.init_paged_cache is None or model.prefill_chunk is None:
                raise ValueError(
                    "kv_mode='paged' needs a model family with paged-cache "
                    "and chunked-prefill support (uniform decoder family)")
            if max_seq % kv_block_size:
                raise ValueError(
                    f"max_seq={max_seq} must be a multiple of "
                    f"kv_block_size={kv_block_size}")
        self.model = model
        self.params = params
        self.pool = pool
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.gemm_impl = gemm_impl
        self.decode_mode = decode_mode
        self.kv_mode = kv_mode
        self.kv_block_size = kv_block_size
        self.kv_num_blocks = kv_num_blocks
        if kv_mode == "paged":
            self.cache = model.init_paged_cache(
                kv_num_blocks, kv_block_size, max_batch, max_seq)
        else:
            self.cache = model.init_cache(max_batch, max_seq)
        self._staging: Dict[int, object] = {}     # slot -> batch-1 cache
        self._counts = None           # the last MoE step's _counts
        self._rt0 = pool.runtime(gemm_impl) if pool else None
        self._build_jits()

    @property
    def supports_chunked_prefill(self) -> bool:
        return self.model.prefill_chunk is not None

    # -------------------------------------------------------------- jits
    def _build_jits(self) -> None:
        """(Re)build the jitted step functions around the current ``_rt0``.

        Static runtime fields (num_servers, capacity) are baked into the
        closures, so a pool resize needs fresh variants; liveness/mapping
        stay jit arguments and never recompile.
        """
        model, rt0 = self.model, self._rt0
        gemm_impl, max_seq = self.gemm_impl, self.max_seq

        def ctx_of(rt_arrays):
            rt = None
            if rt0 is not None:
                mapping, alive, local, route_bias, rweights = rt_arrays
                rt = rt0._replace(mapping=mapping, alive=alive,
                                  local_table=local, route_bias=route_bias,
                                  replica_weights=rweights)
            return ParallelCtx(moe_runtime=rt, gemm_impl=gemm_impl,
                               remat=False)

        def prefill_fn(params, tokens, rt_arrays):
            return model.prefill(params, tokens, ctx_of(rt_arrays),
                                 max_slots=max_seq)

        def decode_step(params, tokens, cache, rt_arrays):
            return model.decode_step(params, tokens, cache,
                                     ctx_of(rt_arrays))

        def decode_fn(params, tokens, cache, rt_arrays):
            if self.decode_mode == "lockstep":
                logits, cache, st = decode_step(params, tokens, cache,
                                                rt_arrays)
            else:
                logits, cache, st = split_batch_decode(
                    lambda t, c: decode_step(params, t, c, rt_arrays),
                    tokens, cache, axis=model.cache_batch_axis,
                    enabled=(self.decode_mode == "pipelined"))
            # per-expert token counts feed the pool's traffic EMA — this is
            # what rebalance() and traffic-aware scale_to re-plan from
            return logits, cache, st.expert_load, _counts(st)

        self._jit_prefill = jax.jit(prefill_fn)
        self._jit_decode = jax.jit(decode_fn)
        self._jit_masked = None
        if model.cache_batch_axis is not None:
            # async exec mode decodes a *subset* of slots while others wait
            # on their wave's completion event: the dense decode kernel
            # still runs the full batch (one compile, one shape), but
            # inactive rows' cache writes are masked back to their old
            # values so a later wave resumes them bit-exactly.
            def masked_decode_fn(params, tokens, cache, mask, rt_arrays):
                logits, new_cache, st = decode_step(params, tokens, cache,
                                                    rt_arrays)
                axis = model.cache_batch_axis

                def keep(new, old):
                    if not hasattr(new, "shape") or new.ndim <= axis \
                            or new.shape[axis] != mask.shape[0]:
                        return new
                    shape = [1] * new.ndim
                    shape[axis] = mask.shape[0]
                    return jnp.where(mask.reshape(shape), new, old)

                return (logits, jax.tree.map(keep, new_cache, cache),
                        st.expert_load, _counts(st))
            self._jit_masked = jax.jit(masked_decode_fn)
        self._jit_chunk = None
        if model.prefill_chunk is not None:
            def chunk_fn(params, tokens, cache, start, rt_arrays):
                logits, cache, st = model.prefill_chunk(
                    params, tokens, cache, start, ctx_of(rt_arrays))
                # chunked prefill feeds the traffic EMA like decode does —
                # the prompt-heavy-workload rebalance signal
                return logits, cache, st.expert_load, _counts(st)
            self._jit_chunk = jax.jit(chunk_fn)

        if self.kv_mode == "paged":
            # block tables / lengths enter as data each call — host-side
            # admission, sharing and preemption never recompile
            def paged_decode_fn(params, tokens, cache, tables, lengths,
                                rt_arrays):
                cache = _with_tables(cache, tables, lengths)
                logits, cache, st = decode_step(params, tokens, cache,
                                                rt_arrays)
                return logits, cache, st.expert_load, _counts(st)

            def paged_chunk_fn(params, tokens, cache, row, start, rt_arrays):
                view = _with_tables(cache, row[None],
                                    jnp.broadcast_to(start, (1,)))
                logits, view, st = model.prefill_chunk(
                    params, tokens, view, start, ctx_of(rt_arrays))
                return logits, view, st.expert_load, _counts(st)

            def copy_fn(cache, src, dst):
                return {k: kvc.copy_blocks(st, src, dst, stacked=True)
                        for k, st in cache.items()}

            self._jit_paged_decode = jax.jit(paged_decode_fn)
            self._jit_paged_chunk = jax.jit(paged_chunk_fn)
            self._jit_copy = jax.jit(copy_fn)

    def _rt_arrays(self):
        if self.pool is None:
            return ()
        with tracing.span("executor.runtime"):
            rt = self.pool.runtime(self.gemm_impl)
            return (rt.mapping, rt.alive, rt.local_table, rt.route_bias,
                    rt.replica_weights)

    def pull_load(self, expert_load) -> Optional[np.ndarray]:
        """The step's expert load on the host, fetched in one transfer with
        its :func:`_counts`, which add to the :data:`COUNTERS`; None
        without an expert tier or a load.  This waits for the step
        (``executor.wait``)."""
        if expert_load is None or self.pool is None:
            return None
        with tracing.span("executor.wait"):
            load, counts = jax.device_get((expert_load, self._counts))
        if counts is not None:
            for name, n in zip(COUNTERS, counts):
                tracing.count(name, int(n))
        load = np.asarray(load)
        tracing.count("moe.routes_held", int(load.sum()))
        return load

    # ------------------------------------------------------------ prefill
    def prefill(self, slot: int, prompt: np.ndarray) -> jax.Array:
        """Whole-prompt prefill straight into ``slot`` of the batch cache."""
        with tracing.span("executor.prefill"):
            tokens = jnp.asarray(prompt, jnp.int32)[None]
            logits, cache_one = self._jit_prefill(self.params, tokens,
                                                  self._rt_arrays())
            self.cache = jax.tree.map(
                lambda big, one: _slot_write(big, one, slot),
                self.cache, cache_one)
            return logits

    def prefill_chunk(self, slot: int, chunk: np.ndarray, start: int,
                      *, is_first: bool, is_last: bool
                      ) -> Tuple[jax.Array, np.ndarray]:
        """One chunked-prefill continuation step for ``slot``; returns
        ``(logits, expert_load)`` — the chunk's router traffic feeds the
        same EMA decode steps do.

        Chunks accumulate in a batch-1 staging cache; the final chunk
        commits the staging cache into the batch cache slot.
        """
        assert self._jit_chunk is not None, "model has no prefill_chunk"
        with tracing.span("executor.prefill_chunk"):
            if is_first:
                self._staging[slot] = self.model.init_cache(1, self.max_seq)
            tokens = jnp.asarray(chunk, jnp.int32)[None]
            logits, staging, expert_load, self._counts = self._jit_chunk(
                self.params, tokens, self._staging[slot],
                jnp.asarray(start, jnp.int32), self._rt_arrays())
            self._staging[slot] = staging
            if is_last:
                self.cache = jax.tree.map(
                    lambda big, one: _slot_write(big, one, slot),
                    self.cache, self._staging.pop(slot))
            return logits, expert_load

    # ------------------------------------------------------------- decode
    def decode(self, tokens: np.ndarray) -> Tuple[jax.Array, np.ndarray]:
        """One decode step over the whole slot batch -> (logits, load)."""
        with tracing.span("executor.decode"):
            logits, self.cache, expert_load, self._counts = \
                self._jit_decode(self.params, jnp.asarray(tokens),
                                 self.cache, self._rt_arrays())
            return logits, expert_load

    def decode_masked(self, tokens: np.ndarray, mask: np.ndarray
                      ) -> Tuple[jax.Array, np.ndarray]:
        """One decode step where only ``mask``-true slots advance their
        cache row; masked rows are restored bit-exactly (the dense
        ``append_decode`` advances length for every row, so the restore is
        what keeps inactive slots resumable).  Active rows' logits are
        bitwise identical to a full-batch :meth:`decode` — decode outputs
        are batch-composition independent, which is what lets the async
        engine reuse lockstep's values with different timing."""
        assert self._jit_masked is not None, \
            "decode_masked needs a uniform cache batch axis"
        with tracing.span("executor.decode"):
            logits, self.cache, expert_load, self._counts = \
                self._jit_masked(self.params, jnp.asarray(tokens),
                                 self.cache, jnp.asarray(mask, bool),
                                 self._rt_arrays())
            return logits, expert_load

    # -------------------------------------------------------------- paged
    def prefill_chunk_paged(self, chunk: np.ndarray, start: int,
                            table_row: np.ndarray
                            ) -> Tuple[jax.Array, np.ndarray]:
        """One (chunked or whole-suffix) prefill step through the block
        table; returns ``(logits, expert_load)``.  The pool blocks are the
        real storage — no staging cache — so a prefix-cache hit simply
        starts ``start`` past the cached prefix and the chunk attends over
        blocks an earlier request wrote.
        """
        with tracing.span("executor.prefill_chunk"):
            tokens = jnp.asarray(chunk, jnp.int32)[None]
            logits, view, expert_load, self._counts = self._jit_paged_chunk(
                self.params, tokens, self.cache,
                jnp.asarray(table_row, jnp.int32),
                jnp.asarray(start, jnp.int32), self._rt_arrays())
            self.cache = _adopt_pools(self.cache, view)
            return logits, expert_load

    def decode_paged(self, tokens: np.ndarray, tables: np.ndarray,
                     lengths: np.ndarray) -> Tuple[jax.Array, np.ndarray]:
        """One decode step with host-authoritative block tables/lengths."""
        with tracing.span("executor.decode"):
            logits, self.cache, expert_load, self._counts = \
                self._jit_paged_decode(
                    self.params, jnp.asarray(tokens), self.cache,
                    jnp.asarray(tables, jnp.int32),
                    jnp.asarray(lengths, jnp.int32), self._rt_arrays())
            return logits, expert_load

    def copy_blocks(self, pairs) -> None:
        """Apply copy-on-write forks: pool blocks src -> dst, every layer."""
        if not pairs:
            return
        src = jnp.asarray([s for s, _ in pairs], jnp.int32)
        dst = jnp.asarray([d for _, d in pairs], jnp.int32)
        self.cache = self._jit_copy(self.cache, src, dst)

    # ----------------------------------------------------------- rebalance
    def migrate_slots(self, updates) -> None:
        """Apply one incremental expert-weight migration chunk: copy the
        listed experts into their new redundant slots across every MoE
        layer (``updates: [(server, local_slot, expert_id)]``).  Weights
        are jit *arguments*, so the swap never recompiles; the pool drops
        the old replica from the mapping before this copy and commits the
        new mapping/local-table only after it lands (break-before-make)."""
        E = self.model.cfg.moe.num_experts
        self.params = _map_server_weights(
            self.params,
            lambda sw: expert_server.migrate_slots(sw, E, updates))

    # ------------------------------------------------------------- elastic
    def resize(self, pool) -> None:
        """Adopt a resized expert-server pool: re-shard the expert weights
        from the recovered global bank and rebuild the jitted variants for
        the new static server count.  The batch KV cache and any staging
        caches are untouched — scaling never drops in-flight work."""
        self.pool = pool
        E = self.model.cfg.moe.num_experts
        n = pool.num_servers
        red = pool.redundant_table
        self.params = _map_server_weights(
            self.params,
            lambda sw: expert_server.reshard_server_weights(sw, E, n, red))
        self._rt0 = pool.runtime(self.gemm_impl)
        self._build_jits()


# ------------------------------------------------------------------ helpers

def _with_tables(cache, tables, lengths):
    """Rebind block tables / lengths into every stacked PagedKVCache leaf
    (broadcast over the leading layer dim the layer scan expects)."""
    def one(stack):
        n = stack.k.shape[0]
        return dataclasses.replace(
            stack,
            block_tables=jnp.broadcast_to(tables[None],
                                          (n,) + tables.shape),
            length=jnp.broadcast_to(lengths[None], (n,) + lengths.shape))
    return {k: one(v) for k, v in cache.items()}


def _adopt_pools(cache, view):
    """Take the (shared) pool arrays back from a batch-1 prefill view;
    tables/lengths stay host-authoritative."""
    return {k: dataclasses.replace(cache[k], k=view[k].k, v=view[k].v)
            for k in cache}


def _map_server_weights(params, fn):
    """Apply ``fn`` to every MoE layer's per-server weight dict in a params
    tree (the ``{"moe": {"servers": ...}}`` sub-dicts), leaving everything
    else untouched."""
    if isinstance(params, dict):
        out = {}
        for k, v in params.items():
            if k == "moe" and isinstance(v, dict) and "servers" in v:
                out[k] = dict(v, servers=fn(v["servers"]))
            else:
                out[k] = _map_server_weights(v, fn)
        return out
    return params


def _slot_write(big, one, b: int):
    """Write a batch-1 cache pytree leaf into slot b of the engine cache.

    The batch dim is the first one where `big` and `one` differ with
    ``one == 1``.
    """
    if not hasattr(big, "shape"):
        return big
    if big.shape == getattr(one, "shape", None):
        return one.astype(big.dtype)      # max_batch == 1: replace wholesale
    for axis, (db, do) in enumerate(zip(big.shape, one.shape)):
        if db != do and do == 1:
            idx = [slice(None)] * big.ndim
            idx[axis] = slice(b, b + 1)
            return big.at[tuple(idx)].set(one.astype(big.dtype))
    return big
