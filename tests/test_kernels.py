"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels import group_shrink as gs
from repro.kernels import grouped_gemm as gg


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n,g,tm,blocks", [
    (64, 32, 32, 4, 8, 16),
    (128, 64, 48, 6, 16, 16),
    (96, 128, 128, 3, 32, 16),
    (256, 64, 128, 16, 8, 16),
    # blocks chosen from the shapes (above 128), with empty groups and a
    # row count no multiple of tm, so dead tiles follow the live ones
    (200, 512, 768, 5, 32, None),
    (72, 768, 256, 7, 16, None),
], ids=["64-32-32-4-8", "128-64-48-6-16", "96-128-128-3-32", "256-64-128-16-8",
        "200-512-768-5-32-chosen", "72-768-256-7-16-chosen"])
def test_grouped_gemm_pallas_vs_ref(m, k, n, g, tm, blocks, dtype, rng):
    p = np.ones(g) / g
    if blocks is None:
        p[1::2] = 0                                      # empty groups
        p /= p.sum()
    sizes = rng.multinomial(m - 8, p).astype(np.int32)  # pad 8
    x = jnp.asarray(rng.normal(size=(m, k)), dtype)
    w = jnp.asarray(rng.normal(size=(g, k, n)) * 0.1, dtype)
    gsz = jnp.asarray(sizes)
    out = ops.grouped_gemm(x, w, gsz, impl="pallas_interpret",
                           tm=tm, tn=blocks, tk=blocks)
    exp = ref.grouped_gemm_ref(x, w, gsz)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("impl", ["xla_ragged", "xla_dense"])
def test_grouped_gemm_xla_impls(impl, rng):
    m, k, n, g = 96, 32, 24, 5
    sizes = np.array([10, 0, 40, 30, 16], np.int32)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(g, k, n)) * 0.1, jnp.float32)
    out = ops.grouped_gemm(x, w, jnp.asarray(sizes), impl=impl,
                           expert_capacity=48)
    exp = ref.grouped_gemm_ref(x, w, jnp.asarray(sizes))
    np.testing.assert_allclose(out, exp, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl", ["pallas_interpret", "xla_ragged",
                                  "xla_dense", "ref"])
def test_group_offset_reads_the_bank_in_place(impl, rng):
    """Groups read at an offset into a bank of three layers equal the same
    call on that layer's slice, bit for bit; under the exact XLA lowering a
    row's result also stays put when its group moves within the bank."""
    m, k, n, g = 96, 32, 24, 5
    sizes = jnp.asarray(np.array([10, 0, 40, 30, 16], np.int32))
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    bank = jnp.asarray(rng.normal(size=(3 * g, k, n)) * 0.1, jnp.float32)
    at = jax.jit(lambda x, w, s, o: ops.grouped_gemm(
        x, w, s, impl=impl, expert_capacity=48, group_offset=o))
    out = at(x, bank, sizes, jnp.int32(g))
    own = ops.grouped_gemm(x, bank[g:2 * g], sizes, impl=impl,
                           expert_capacity=48)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(own))
    assert not np.array_equal(np.asarray(out),
                              np.asarray(at(x, bank, sizes, jnp.int32(0))))
    if impl == "xla_ragged":      # the same rows, their groups moved last
        moved = ops.grouped_gemm(
            x, jnp.concatenate([bank[:g], bank[g:2 * g]]),
            jnp.concatenate([jnp.zeros_like(sizes), sizes]), impl=impl)
        np.testing.assert_array_equal(np.asarray(moved), np.asarray(own))


def test_grouped_gemm_empty_groups(rng):
    """Group-shrink guarantee: all-empty groups produce zeros + no NaN."""
    m, k, n, g = 32, 16, 16, 4
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(g, k, n)), jnp.float32)
    gsz = jnp.zeros((g,), jnp.int32)
    out = ops.grouped_gemm(x, w, gsz, impl="pallas_interpret",
                           tm=8, tn=8, tk=8)
    assert np.allclose(out, 0)


@pytest.mark.parametrize("k,n", [(4096, 14336), (14336, 4096),
                                 (7168, 2048), (2048, 7168), (128, 128)])
def test_blocks_chosen_from_shapes(k, n):
    tk, tn = gg.choose_blocks(k, n)
    assert k % tk == 0 and n % tn == 0
    assert tk % 128 == 0 and tn % 128 == 0
    assert gg.working_set(128, tk, tn) <= gg.VMEM_BUDGET
    if k * n >= 2048 * 1024:           # published widths: MiB-sized blocks
        assert tk * tn * 2 >= 2 * 2 ** 20, (tk, tn)
    # no block as large that fits reads a row tile's x rows fewer times
    for bk in gg._block_sizes(k):
        bn = tk * tn // bk
        if (n % bn == 0 and bn % 128 == 0
                and gg.working_set(128, bk, bn) <= gg.VMEM_BUDGET):
            assert gg.x_reads(k, n, tk, tn) <= gg.x_reads(k, n, bk, bn)


def test_dead_tiles_fetch_the_last_live_block():
    """Dead tail tiles carry the last live tile's group, so their weight
    block index is the one the grid step before them holds."""
    table = gs.build_tile_table(jnp.array([9, 0, 20, 0], jnp.int32),
                                m=40, tm=8)
    assert int(table.num_tiles) == 2 + 3
    assert list(np.asarray(table.tile_gid)) == [0, 0, 2, 2, 2, 2, 2, 2, 2]
    empty = gs.build_tile_table(jnp.zeros((3,), jnp.int32), m=16, tm=8)
    assert int(empty.num_tiles) == 0
    assert not np.asarray(empty.tile_gid).any()


def test_row_tiles_are_counted(rng):
    sizes = jnp.array([10, 0, 40, 30, 16], jnp.int32)
    x = jnp.asarray(rng.normal(size=(128, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(5, 16, 16)), jnp.float32)
    _, tiles = ops.grouped_gemm_counted(x, w, sizes, impl="pallas_interpret")
    # tm = 128: one tile for each non-empty group, of 128 // 128 + 5
    assert (int(tiles.live), int(tiles.total)) == (4, 6)
    _, none = ops.grouped_gemm_counted(x, w, sizes, impl="xla_ragged")
    assert (int(none.live), int(none.total)) == (0, 0)


def test_tile_table_shrinks_inactive_groups():
    sizes = jnp.array([16, 0, 0, 8, 0, 24], jnp.int32)
    table = gs.build_tile_table(sizes, m=64, tm=8)
    # active groups: 0 (2 tiles), 3 (1), 5 (3) -> 6 live tiles
    assert int(table.num_tiles) == 6
    live = np.asarray(table.tile_gid)[:6]
    assert list(live) == [0, 0, 3, 5, 5, 5]
    assert int(np.asarray(table.tile_valid).sum()) == 6


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,kv,hd,s,ts", [
    (2, 8, 2, 32, 64, 16),
    (3, 4, 4, 64, 128, 32),
    (1, 16, 8, 16, 32, 8),
    (2, 128, 8, 64, 64, 32),      # deepseek-r1 head layout
])
def test_flash_decode_vs_ref(b, h, kv, hd, s, ts, dtype, rng):
    q = jnp.asarray(rng.normal(size=(b, h, hd)), dtype)
    kc = jnp.asarray(rng.normal(size=(b, s, kv, hd)), dtype)
    vc = jnp.asarray(rng.normal(size=(b, s, kv, hd)), dtype)
    lengths = jnp.asarray(rng.integers(1, s + 1, size=b), jnp.int32)
    out = ops.flash_decode(q, kc, vc, lengths, impl="pallas_interpret",
                           ts=ts)
    exp = ref.flash_decode_ref(q, kc, vc, lengths)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("t,k,d,tt,td", [(64, 2, 128, 16, 32),
                                         (128, 8, 256, 32, 64),
                                         (16, 8, 7168, 8, 512)])  # r1 top-8
def test_combine_vs_ref(t, k, d, tt, td, rng):
    x = jnp.asarray(rng.normal(size=(t, k, d)), jnp.float32)
    w = jnp.asarray(rng.random(size=(t, k)), jnp.float32)
    out = ops.combine_weighted(x, w, impl="pallas_interpret", tt=tt, td=td)
    exp = ref.combine_weighted_ref(x, w)
    np.testing.assert_allclose(out, exp, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("op,impl", [
    ("flash_decode", "xla_ragged"), ("flash_decode", "xla_dense"),
    ("paged_flash_decode", "xla_ragged"), ("combine_weighted", "xla_dense"),
    ("grouped_gemm", "mosaic"),
])
def test_ops_refuse_impl_they_do_not_have(op, impl, rng):
    """An impl name an op does not implement raises; it never falls back
    to the reference."""
    q = jnp.zeros((1, 4, 16))
    kv = jnp.zeros((1, 16, 2, 16))
    calls = {
        "flash_decode": lambda: ops.flash_decode(
            q, kv, kv, jnp.ones((1,), jnp.int32), impl=impl),
        "paged_flash_decode": lambda: ops.paged_flash_decode(
            q, kv, kv, jnp.zeros((1, 1), jnp.int32),
            jnp.ones((1,), jnp.int32), impl=impl),
        "combine_weighted": lambda: ops.combine_weighted(
            jnp.zeros((8, 2, 16)), jnp.zeros((8, 2)), impl=impl),
        "grouped_gemm": lambda: ops.grouped_gemm(
            jnp.zeros((8, 16)), jnp.zeros((2, 16, 16)),
            jnp.asarray([4, 4], jnp.int32), impl=impl),
    }
    with pytest.raises(ValueError, match="no implementation"):
        calls[op]()


def test_ops_auto_picks_from_platform():
    """On the CPU "auto" is xla_ragged for the grouped GEMM and the plain
    XLA lowering for the others; Pallas is what it picks on TPU."""
    assert ops._resolve("grouped_gemm", "auto", "xla_ragged",
                        ops._GEMM_IMPLS) == "xla_ragged"
    assert ops._resolve("flash_decode", "auto", "ref",
                        ops._KERNEL_IMPLS) == "ref"
