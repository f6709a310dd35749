"""Embedding-bag gather/segment-sum kernel (Pallas TPU, scalar prefetch).

Recsys models (MT-WND / DIEN) and LM token embeddings are gather-bound: rows
scattered across a huge HBM-resident table.  TPU-native design: the bag
indices are *scalar-prefetched* so they are available to the BlockSpec
index_map BEFORE the DMA engine issues the row fetch — each (bag, slot) grid
step DMAs exactly the one row it needs HBM→VMEM, and the bag's running sum
accumulates in the output block (revisited across the inner grid axis).

Layout: the table is viewed as (V, 1, D) and the output as (n_bags, 1, D),
and both are blocked as (None, 1, D).  A plain (1, D) block of a (V, D)
array breaks the TPU's (8, 128) tiling rule on its second-to-last axis; in
the 3-D view that axis is the whole (size-1) dimension, which Mosaic
accepts, and the row index moves to a squeezed leading axis.

This is the TPU analogue of FBGEMM's TBE gather-reduce: no atomics, one
row-granular DMA per lookup, MXU untouched (pure VPU adds).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bag_kernel(idx_ref, w_ref, table_ref, o_ref, *, bag_size: int,
                weighted: bool):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    row = table_ref[...]                        # (1, D)
    if weighted:
        i = pl.program_id(0)
        row = row.astype(jnp.float32) * w_ref[i, j]
    o_ref[...] += row.astype(o_ref.dtype)


def embedding_bag(indices, table, weights=None, *, interpret: bool = False):
    """indices (n_bags, bag_size) int32 → (n_bags, D) sums of table rows.

    weights (n_bags, bag_size) optional per-lookup multipliers (e.g. recsys
    multi-hot frequencies).  Rows are fetched via scalar-prefetch-driven
    index maps; the weights sit in SMEM as float32 scalars, because a
    dynamically indexed scalar load from VMEM does not lower.
    """
    n_bags, bag_size = indices.shape
    v, d = table.shape
    weighted = weights is not None
    if weights is None:
        weights = jnp.ones((n_bags, bag_size), jnp.float32)

    kernel = functools.partial(_bag_kernel, bag_size=bag_size,
                               weighted=weighted)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_bags, bag_size),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, 1, d), lambda i, j, idx: (idx[i, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, d), lambda i, j, idx: (i, 0, 0)),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_bags, 1, d), table.dtype),
        interpret=interpret,
    )(indices, weights.astype(jnp.float32), table.reshape(v, 1, d))
    return out.reshape(n_bags, d)
