"""One cluster-wide KV pool tensor: the paper's distributed KVCache.

``GlobalKVPool`` folds the per-instance ``pool_k/pool_v`` tensors into
ONE pair of arrays ``k/v: [ranks, L, NB, bs, K, hd]`` whose leading rank
axis is (optionally) sharded over a device mesh (``("data",)`` or
``("data", "model")`` per ``ServeLayout.pool_axes``). Rank ``i``'s slice
``k[i]`` plays exactly the role engine ``i``'s private pool used to play
— same block ids, same tables — but every cross-rank KV access is now a
slice of one tensor:

  * a creditor READ during decode/prefill is a per-shard MicroAttention
    partial under ``shard_map`` (``sharded_step.decode_step_global``) —
    the KV never moves, only the LSE-merge scalars do (paper Eq. 3);
  * a ``StripedMove`` leg, a ``PrefixSink`` streaming write, an
    ``AsyncStager``-staged prefetch — all become slice assignments
    ``k.at[dst_rank, ...].set(...)``, which GSPMD lowers to remote DMA
    between the owning shards when a mesh is attached;
  * allocator state stays HOST metadata: ``ranks[i]`` is the same
    ``RankKVPool`` (block allocator + per-request chains) each engine's
    ``RManager`` would otherwise own privately — engines in global-pool
    mode alias these, so the cluster and the sharded step literally
    share one layout and allocator view.

Zero-copy discipline (PR 4) carries over: every updater donates the
global tensor and callers must continue with the returned handle —
``GlobalKVPool`` threads exactly one live ``self.k``/``self.v``
reference, and ``CommStats.pool_copy_steps`` still gates in-place reuse.

Tail-append convention: same as everywhere else (see the kvpool module
docstring) — block index ``NB`` + ``mode="drop"`` is the universal
"write nothing" sentinel; the rank axis needs no extra masking either,
because an out-of-range shard-local rank index drops the same way.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.serving.kvpool import RankKVPool


# Every updater DONATES the global pool: on donating backends the write
# is an in-place row update of the [R, L, NB, bs, K, hd] tensor. The
# rank indices are STATIC (there are only n_ranks of them, so compiles
# stay bounded). NB on the index mix: in pool.at[rank, :, idx] the int
# rank and the array idx are both ADVANCED indices separated by the
# layer slice, so their broadcast dims land at the FRONT — values are
# [n, L, ...], hence the swapaxes from the [L, n, ...] caller layout.
@functools.partial(jax.jit, static_argnames=("rank",),
                   donate_argnames=("pool",))
def _gp_write_blocks(pool, idx, rows, *, rank):
    val = jnp.swapaxes(rows.astype(pool.dtype), 0, 1)
    return pool.at[rank, :, idx].set(val)


@functools.partial(jax.jit, static_argnames=("rank",),
                   donate_argnames=("pool",))
def _gp_scatter_rows(pool, blk, off, rows, *, rank):
    val = jnp.swapaxes(rows.astype(pool.dtype), 0, 1)
    return pool.at[rank, :, blk, off].set(val)


@functools.partial(jax.jit, static_argnames=("rank",))
def _gp_read_blocks(pool, idx, *, rank):
    return pool[rank][:, idx]


@functools.partial(jax.jit, static_argnames=("src", "dst"),
                   donate_argnames=("pool",))
def _gp_copy_blocks(pool, src_idx, dst_idx, *, src, dst):
    # One StripedMove leg: whole blocks slide from src rank to dst rank
    # inside the tensor. Under a mesh GSPMD lowers this to a remote DMA
    # between the owning shards; no host round-trip, no dense KV array.
    rows = pool[src][:, src_idx]
    return pool.at[dst, :, dst_idx].set(jnp.swapaxes(rows, 0, 1))


class GlobalKVPool:
    """The cluster-wide pool tensor + the per-rank allocator views."""

    def __init__(self, n_ranks: int, num_blocks: int, block_size: int,
                 cfg: ModelConfig, *, mesh=None,
                 pool_axes: Tuple[str, ...] = ("data",)):
        assert cfg.family in ("dense", "moe"), \
            "only attention archs pool KV"
        self.n_ranks = n_ranks
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.mesh = mesh
        self.pool_axes = tuple(pool_axes)
        L, K, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
        shape = (n_ranks, L, num_blocks, block_size, K, hd)
        zeros = functools.partial(jnp.zeros, shape, jnp.dtype(cfg.dtype))
        if mesh is None:
            self.k, self.v = zeros(), zeros()
        else:
            n_shards = 1
            for ax in self.pool_axes:
                n_shards *= mesh.shape[ax]
            assert n_ranks % n_shards == 0, \
                f"{n_ranks} ranks not divisible over {n_shards} shards"
            # Created in place, shard by shard: no device ever holds the
            # whole cluster's pool (at four chips' worth it cannot fit).
            make = jax.jit(zeros, out_shardings=NamedSharding(
                mesh, P(self.pool_axes)))
            self.k, self.v = make(), make()
        # THE shared allocator view: engine i's RManager aliases
        # ranks[i], so host-side placement metadata is identical whether
        # the step runs in-process or under shard_map.
        self.ranks: List[RankKVPool] = [RankKVPool(num_blocks, block_size)
                                        for _ in range(n_ranks)]

    # --- functional updaters (donated; continue with self.k/self.v) --- #
    def _prep_rows(self, rows, nb: int):
        rows = jnp.asarray(rows)
        pad = nb * self.block_size - rows.shape[1]
        if pad:
            widths = [(0, 0), (0, pad)] + [(0, 0)] * (rows.ndim - 2)
            rows = jnp.pad(rows, widths)
        return rows.reshape((rows.shape[0], nb, self.block_size)
                            + rows.shape[2:])

    def write_blocks(self, rank: int, block_ids: Sequence[int],
                     k_rows, v_rows) -> None:
        """Fill whole blocks of one rank from [L, n, K, hd] token rows
        (n <= len(block_ids) * bs; a partial final block zero-pads)."""
        nb = len(block_ids)
        idx = jnp.asarray(list(block_ids), jnp.int32)
        self.k = _gp_write_blocks(self.k, idx, self._prep_rows(k_rows, nb),
                                  rank=rank)
        self.v = _gp_write_blocks(self.v, idx, self._prep_rows(v_rows, nb),
                                  rank=rank)

    def scatter_rows(self, rank: int, block_ids, offsets, k, v) -> None:
        """Row-addressed scatter into one rank's blocks (may land
        mid-block — the streaming-prefill creditor write)."""
        blk = jnp.asarray(block_ids, jnp.int32)
        off = jnp.asarray(offsets, jnp.int32)
        self.k = _gp_scatter_rows(self.k, blk, off, jnp.asarray(k),
                                  rank=rank)
        self.v = _gp_scatter_rows(self.v, blk, off, jnp.asarray(v),
                                  rank=rank)

    def read_blocks(self, rank: int, block_ids: Sequence[int]):
        """Whole blocks of one rank as ([L, nb*bs, K, hd], same) — a
        gather, safe to hold after the frames are freed."""
        idx = jnp.asarray(list(block_ids), jnp.int32)
        k = _gp_read_blocks(self.k, idx, rank=rank)
        v = _gp_read_blocks(self.v, idx, rank=rank)
        n = len(block_ids) * self.block_size
        return (k.reshape((k.shape[0], n) + k.shape[3:]),
                v.reshape((v.shape[0], n) + v.shape[3:]))

    def copy_blocks(self, src_rank: int, src_blocks: Sequence[int],
                    dst_rank: int, dst_blocks: Sequence[int]) -> None:
        """One StripedMove leg: block i of ``src_blocks`` lands in block
        i of ``dst_blocks`` — a slice assignment inside the tensor
        (remote DMA under GSPMD), never a host materialization."""
        si = jnp.asarray(list(src_blocks), jnp.int32)
        di = jnp.asarray(list(dst_blocks), jnp.int32)
        self.k = _gp_copy_blocks(self.k, si, di, src=src_rank,
                                 dst=dst_rank)
        self.v = _gp_copy_blocks(self.v, si, di, src=src_rank,
                                 dst=dst_rank)


__all__ = ["GlobalKVPool"]
