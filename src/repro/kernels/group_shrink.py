"""Group-shrink: active-group compaction for the grouped GEMM (paper §4.1).

The paper's CUDA problem: DeepGEMM's scheduler iterates *all* expert groups,
paying a low-throughput global-memory read per group, even though most groups
are empty under fine-grained MoE.  Their fix is a GPU prefix scan that
compacts active-group metadata so the scheduler early-stops.

TPU translation: the Pallas grid must be static, so "early stop" becomes
"inactive groups contribute zero row-tiles".  We prefix-scan the group sizes
into a *tile table* — for each of the (statically bounded) row tiles, the
group it belongs to and whether it is live.  Empty groups simply never
appear in the table; the only residual cost is the per-group tile-alignment
padding (< TM rows per active group), and dead tail tiles are skipped with
``pl.when`` and, holding the last live tile's group, fetch no weights.  The
tile table is consumed by the kernel through scalar prefetch (SMEM), i.e.
loaded once — the analogue of the paper's "compacted tensor loaded into
shared memory once".
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp


class TileTable(NamedTuple):
    """Static-size, dynamically-valid tile metadata (scalar-prefetch input)."""

    tile_gid: jax.Array       # (T,) int32 group per row tile; dead tiles
    #                           repeat the last live tile's (0 if none)
    tile_valid: jax.Array     # (T,) int32 1 = live tile
    padded_offset: jax.Array  # (G,) int32 first padded row of each group
    num_tiles: jax.Array      # scalar int32 — live tile count (diagnostics)


def max_tiles(m: int, g: int, tm: int) -> int:
    """Static bound on live row tiles: every group wastes < 1 tile."""
    return m // tm + g


def build_tile_table(group_sizes: jax.Array, m: int, tm: int) -> TileTable:
    """group_sizes: (G,) int32, sum <= m (static).  O(G + T) prefix scans."""
    G = group_sizes.shape[0]
    T = max_tiles(m, G, tm)
    tiles_per = (group_sizes + tm - 1) // tm                  # 0 for empty
    num_tiles = jnp.sum(tiles_per)
    # first tile of each group (exclusive prefix scan)
    first_tile = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(tiles_per)[:-1].astype(jnp.int32)])
    # tile -> group: scatter group starts, then max-scan
    tile_gid = jnp.zeros((T,), jnp.int32)
    # mark group boundaries: at first_tile[g] the gid becomes g (only for
    # non-empty groups; empty groups share a start with their successor and
    # the later scatter wins because we scatter in increasing g with max)
    has_tiles = tiles_per > 0
    tile_gid = tile_gid.at[jnp.where(has_tiles, first_tile, T)].max(
        jnp.arange(G, dtype=jnp.int32), mode="drop")
    tile_gid = jax.lax.associative_scan(jnp.maximum, tile_gid)
    # the max-scan carries the last live group into the dead tail
    tile_valid = (jnp.arange(T) < num_tiles).astype(jnp.int32)
    padded_offset = (first_tile * tm).astype(jnp.int32)
    return TileTable(tile_gid=tile_gid, tile_valid=tile_valid,
                     padded_offset=padded_offset,
                     num_tiles=num_tiles.astype(jnp.int32))


def pad_rows_to_tiles(x: jax.Array, group_sizes: jax.Array,
                      table: TileTable, tm: int
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Scatter group-sorted rows into the tile-aligned padded layout.

    Returns (x_padded (T*tm, K), padded_idx (M,), row_live (M,)) where
    padded_idx maps each sorted row to its padded position (for the inverse
    gather) and row_live masks rows beyond sum(group_sizes).
    """
    M = x.shape[0]
    G = group_sizes.shape[0]
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(group_sizes).astype(jnp.int32)])
    rows = jnp.arange(M, dtype=jnp.int32)
    gid = jnp.searchsorted(offsets[1:], rows, side="right").astype(jnp.int32)
    row_live = rows < offsets[-1]
    gid_c = jnp.minimum(gid, G - 1)
    pos = rows - offsets[gid_c]
    padded_idx = jnp.where(
        row_live, table.padded_offset[gid_c] + pos, table.tile_gid.shape[0] * tm)
    T = table.tile_gid.shape[0]
    x_padded = jnp.zeros((T * tm, x.shape[1]), x.dtype).at[padded_idx].set(
        x, mode="drop")
    return x_padded, padded_idx, row_live


def unpad_rows(y_padded: jax.Array, padded_idx: jax.Array,
               row_live: jax.Array) -> jax.Array:
    """Inverse of :func:`pad_rows_to_tiles` for the kernel output."""
    safe = jnp.minimum(padded_idx, y_padded.shape[0] - 1)
    y = y_padded[safe]
    return jnp.where(row_live[:, None], y, 0)
