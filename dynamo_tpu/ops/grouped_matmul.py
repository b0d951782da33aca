"""Grouped expert matmul: row tiles, each multiplied by ITS expert's weights.

The rows of a step's (token, expert) pairs arrive sorted by expert, each
expert's group padded to whole tiles of ``TILE_ROWS`` rows (models/moe.py
``expert_dispatch``), so a tile has one expert.  ``moe_grouped_matmul`` walks
the tiles: ``tile_expert`` and the count of live tiles are scalar-prefetch
operands, the weight ``BlockSpec``'s index map reads the tile's expert (and
the layer, so that the STACKED leaf ``[L, E, K, N]`` is the operand and no
layer is sliced out of it first), and a tile past the last live one repeats
the block index before it and skips its body.  A weight block is therefore
copied from HBM only for an expert that has a row (and once, the first block
of the first tile's expert, by a call that has no live tile at all: the
pipeline's prologue).

The arithmetic is ops/quant_matmul.py's W8A8 contract, row for row: int8 x
int8 with int32 accumulation over the WHOLE of K in one block (exact, so no
tiling of K can show), then ``acc * row_scale * channel_scale`` in float32.
Float leaves take the same walk with a float32 accumulator.  Off the chip
the call runs under the Pallas interpreter where the caller asks for it
(``interpret``; models/moe.py passes ``pallas_interpret()``) and not
otherwise; under a device mesh the caller wraps it in ``shard_map``.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_ROWS = 32  # int8's native sublane tile: the least a row tile can be
BLOCK_BYTES = 4 << 20  # of one weight block [K, tn]; two are in flight a leaf


def _block_cols(K: int, N: int, itemsize: int) -> int:
    """Columns of a weight block: the widest whole-lane divisor of N whose
    block stays under ``BLOCK_BYTES`` (the narrowest if none does); all of N
    where N is not whole lanes (the tests' widths)."""
    cols = [d for d in range(128, N + 1, 128) if N % d == 0]
    if not cols:
        return N
    fit = [d for d in cols if K * d * itemsize <= BLOCK_BYTES]
    return max(fit) if fit else cols[0]


def _kernel(te_ref, meta_ref, x_ref, *refs, n_w: int, quantized: bool):
    """Grid (tile, column block).  ``refs``: the rows' scales [tm, 1] (only
    ``quantized``), then per leaf its weight block [K, tn], then (only
    ``quantized``) per leaf its channel scales [1, tn], then the outputs."""
    del te_ref  # read by the index maps
    xs_ref, refs = (refs[0], refs[1:]) if quantized else (None, refs)
    w_refs, refs = refs[:n_w], refs[n_w:]
    ws_refs, o_refs = (refs[:n_w], refs[n_w:]) if quantized else ((None,) * n_w, refs)
    live = pl.program_id(0) < meta_ref[0]

    @pl.when(live)
    def _():
        x = x_ref[...]
        for w_ref, ws_ref, o_ref in zip(w_refs, ws_refs, o_refs):
            acc = jax.lax.dot_general(
                x, w_ref[...], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32 if quantized else jnp.float32)
            if quantized:
                acc = acc.astype(jnp.float32) * xs_ref[...] * ws_ref[...]
            o_ref[...] = acc.astype(o_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _():
        for o_ref in o_refs:
            o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("out_dtypes", "interpret"))
def moe_grouped_matmul(
    x: jnp.ndarray,  # [M, K] int8 (with ``x_scale``) or float; M = tiles * TILE_ROWS, or
    # TILE_ROWS: ONE tile of rows that every tile shares (a step of so few
    # rows that they are each expert's tile as they stand)
    x_scale: Optional[jnp.ndarray],  # [M, 1] f32 row scales, None for float leaves
    ws: Sequence[jnp.ndarray],  # each [L, E, K, N]: leaves that share the rows
    w_scales: Optional[Sequence[jnp.ndarray]],  # each [L, E, N] f32, None for float leaves
    tile_expert: jnp.ndarray,  # [tiles] int32; past ``n_tiles`` the last live tile's
    n_tiles: jnp.ndarray,  # [] int32 live tiles
    layer: jnp.ndarray,  # [] int32 index into L
    *,
    out_dtypes: tuple,
    interpret: bool = False,
):
    """One output [M, N] a leaf: rows of tile i times ``w[layer,
    tile_expert[i]]``; rows of a tile past ``n_tiles`` read zero.

    Jitted, so that a process traces the kernel once a shape (PR 31: an
    unwrapped Pallas call cost 1.1 s of tracing a call site)."""
    K = x.shape[1]
    tm = TILE_ROWS
    tiles = tile_expert.shape[0]
    shared = x.shape[0] == tm and tiles > 1
    M = tiles * tm
    N = ws[0].shape[-1]
    tn = _block_cols(K, N, ws[0].dtype.itemsize)
    nb = N // tn
    quantized = x_scale is not None
    n_w = len(ws)

    def last(i, meta):  # the last live tile (0 when none is)
        return jnp.minimum(i, jnp.maximum(meta[0] - 1, 0))

    def rows(i, j, te, meta):
        return (0 if shared else last(i, meta)), 0

    def weight(i, j, te, meta):
        # A tile past the last repeats the step before it: no copy.
        return meta[1], te[last(i, meta)], 0, jnp.where(i < meta[0], j, nb - 1)

    in_specs = [pl.BlockSpec((tm, K), rows)]
    operands = [x]
    if quantized:
        in_specs.append(pl.BlockSpec((tm, 1), rows))
        operands.append(x_scale)
    in_specs += [pl.BlockSpec((None, None, K, tn), weight)] * n_w
    operands += list(ws)
    if quantized:
        in_specs += [pl.BlockSpec((None, None, 1, tn), weight)] * n_w
        operands += [s.reshape(s.shape[:2] + (1, N)) for s in w_scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(tiles, nb),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((tm, tn), lambda i, j, te, meta: (i, j))] * n_w,
    )
    return pl.pallas_call(
        functools.partial(_kernel, n_w=n_w, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((M, N), dt) for dt in out_dtypes],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name="moe_grouped_matmul",
    )(tile_expert.astype(jnp.int32),
      jnp.stack([n_tiles, layer]).astype(jnp.int32), *operands)
