"""Mamba-1's prompt-side recurrence as ONE Pallas call a block of a row's tokens.

    S_t[n, d] = exp(dt_t[d] A[n, d]) S_{t-1}[n, d] + (dt_t[d] c_t[d]) B_t[n]
    y_t[d]    = sum_n C_t[n] S_t[n, d]

(models/mamba1.py has the mixer, docs/jamba.md the equations and this call's
layout; ``D c``, the gate and the projections stay with the caller).  The decay
differs a (channel, state index) pair, so there is no matmul form: the
recurrence is elementwise in time, and what it costs is how often the state and
a token's decay and input travel.  Here they do not: a program holds its ``[N,
tile]`` of the state in vector registers across a ``fori_loop`` over the
block's VALID tokens, forms a token's decay and input in registers and writes
neither anywhere.

Layout.  The state index lies along the sublanes and the channels along the
lanes, as the slots hold it: ``[N, 128]`` is two registers at ``N`` 16.  The
grid walks tiles of ``TILE`` channels.  ``dt`` and ``dt c`` stay where XLA left
them (``[tokens, channels]``, ``pl.ANY``): a program copies a window of ``block
+ 8`` token rows in while its neighbour is worked on (a row's first token is
wherever the step put it, so the window starts at the multiple of 8 below it,
or where it still ends inside the step), and walks it eight rows, one aligned
register, at a time; a token's row is broadcast along the sublanes.  ``B_t[n]``
and ``C_t[n]`` scale single SUBLANES, so the call takes them already broadcast
along the lanes (``bc`` ``[tokens, 2N, 128]``) and copies the window's once.
The sum over ``n`` is the state's two halves added, a token's eight partial
rows stored, and row s of eight tokens read back at once (a strided load):
seven more additions give eight tokens of ``y`` along the sublanes, no
cross-sublane shuffle.  ``y_t`` takes ``dt_t c_t``'s place: the buffer is
ALIASED to the output and its window goes back as it came but for the block's
valid tokens.  The state goes in and comes out as a VALUE: the slot pool is
``walk_rows``' business and is not aliased through this call.

Nothing is rounded: state, decay and every product are float32, and a token's
arithmetic does not depend on where its block starts, so any block size gives
the same sums and a chunk resumed from a snapshot is the cold chunk to the bit.

Compiles for the chip or raises; under the Pallas interpreter only where
``DYN_PALLAS_INTERPRET`` asks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ragged_attention import pallas_interpret

# Channels a program: [16, 512] float32 of the state is 8 vector registers, and
# as many of ``A``.
TILE = 512
# Rows of a register: a window's start is a multiple, so it holds that many
# rows beyond the block, and the loop takes that many tokens a pass.
SLACK, LOG2_SLACK = 8, 3
LOG2E = 1.4426950408889634


def _kernel(meta_ref, s_ref, a_ref, dt_hbm, dtc_hbm, bc_hbm, s_out, y_hbm,
            dt_buf, y_buf, bc_buf, part, isem, osem, bsem, *, interpret: bool):
    """Grid (channel tiles), walked in order.  ``meta_ref`` (SMEM): the block's
    first token and how many of its tokens are valid.  ``dt_buf`` / ``y_buf``
    [2, rows, TILE]: a tile's windows of ``dt`` and ``dt c`` coming in while
    its neighbour is worked on; ``y_buf`` goes back out with ``y`` where the
    block's tokens are.  ``dtc_hbm`` and ``y_hbm`` are one buffer."""
    at0, n = meta_ref[0], meta_ref[1]
    j, tiles = pl.program_id(0), pl.num_programs(0)
    N, W = s_ref.shape
    rows = dt_buf.shape[1]
    # (No ``//`` and no ``%`` on a traced integer: each costs the lowering
    # milliseconds, a prompt program, at every start.  SLACK is a power of 2.)
    start = pl.multiple_of(jnp.minimum(at0 & -SLACK, dt_hbm.shape[0] - rows), SLACK)
    off = at0 - start
    end = off + n  # the window's rows [off, end) are the block's valid tokens
    slot = j & 1

    def window(ref, j):
        return ref.at[pl.ds(start, rows), pl.ds(pl.multiple_of(j * W, W), W)]

    def fetch(j, slot):
        return [pltpu.make_async_copy(window(dt_hbm, j), dt_buf.at[slot], isem.at[0, slot]),
                pltpu.make_async_copy(window(dtc_hbm, j), y_buf.at[slot], isem.at[1, slot])]

    def put(j, slot):
        return pltpu.make_async_copy(y_buf.at[slot], window(y_hbm, j), osem.at[slot])

    both = pltpu.make_async_copy(bc_hbm.at[pl.ds(start, rows)], bc_buf, bsem.at[0])

    @pl.when(j == 0)
    def _():
        both.start()
        for c in fetch(0, 0):
            c.start()

    @pl.when(j >= 1)
    def _():
        put(j - 1, 1 - slot).wait()  # the other buffer's tile has left

    @pl.when(j + 1 < tiles)
    def _():
        for c in fetch(j + 1, 1 - slot):
            c.start()

    for c in fetch(j, slot):
        c.wait()

    @pl.when(j == 0)
    def _():
        both.wait()

    # The loop's body is ``lax`` on whole tiles: it is traced and lowered for
    # every prompt program at every start, and ``jnp`` costs four times as much.
    mul, add, cut = jax.lax.mul, jax.lax.add, jax.lax.slice_in_dim
    A = a_ref[...] * LOG2E  # exp(dt A) as exp2: one product a token less
    sub = jax.lax.broadcasted_iota(jnp.int32, (SLACK, W), 0)
    zeros = jnp.zeros((SLACK, W), jnp.float32)
    down = lambda row: jax.lax.broadcast_in_dim(row, (N, W), (0, 1))  # noqa: E731  (a token's row the sublanes down)
    along = lambda v: pltpu.repeat(v, W // 128, axis=v.ndim - 1)  # noqa: E731  (128 lanes the tile's width over)

    # A product is rounded before it is added: the chip's vector unit has no
    # fused multiply-add.  XLA's CPU backend has one, and contracts a product
    # into the sum behind it in one copy of a token's arithmetic and not in
    # another, so under the interpreter every product passes through an
    # integer no-op it cannot see through (``n`` is never negative).
    def rounded(v):
        if not interpret:
            return v
        as_bits = jax.lax.bitcast_convert_type
        return as_bits(as_bits(v, jnp.int32) ^ jnp.minimum(n, 0), jnp.float32)

    def group(g, S):
        """The window's rows [8g, 8g + 8): eight tokens, a row apiece.  Those
        that are not the block's (before its first in the first group, past
        its last in the last) read ``dt`` and ``dt c`` as 0, so the state
        stands (times 1, plus 0), and leave their rows as they were."""
        base = pl.multiple_of(g * SLACK, SLACK)
        rows8 = pl.ds(base, SLACK)
        live = (base + sub >= off) & (base + sub < end)
        dt8 = jax.lax.select(live, dt_buf[slot, rows8, :], zeros)
        dc8 = jax.lax.select(live, y_buf[slot, rows8, :], zeros)
        states = []
        for i in range(SLACK):
            decay = jax.lax.exp2(mul(down(cut(dt8, i, i + 1)), A))
            S = add(rounded(mul(decay, S)),
                    rounded(mul(down(cut(dc8, i, i + 1)), along(bc_buf[base + i, :N, :]))))
            states.append(S)
        # y_t = sum_n C_t[n] S_t[n]: the eight tokens' products at once, the
        # state's halves added, then row s of every token's eight partial rows
        # at once (a strided load), the tokens along the sublanes as ``y`` has
        # them.
        c = along(bc_buf[rows8, N:, :])  # [8, N, W]
        S8 = jax.lax.concatenate([jax.lax.expand_dims(S_t, (0,)) for S_t in states], 0)
        halves = functools.reduce(add, [
            rounded(mul(cut(c, h, h + SLACK, axis=1), cut(S8, h, h + SLACK, axis=1)))
            for h in range(0, N, SLACK)])
        y = []
        for l in range(W // 128):
            part[l] = cut(halves, l * 128, (l + 1) * 128, axis=2).reshape(SLACK * SLACK, 128)
            y.append(functools.reduce(add, [
                part[pl.ds(l, 1), pl.ds(s, SLACK, stride=SLACK), :].reshape(SLACK, 128)
                for s in range(SLACK)]))
        y_buf[slot, rows8, :] = jax.lax.select(live, jax.lax.concatenate(y, 1), y_buf[slot, rows8, :])
        return S

    s_out[...] = jax.lax.fori_loop(off >> LOG2_SLACK, (end + SLACK - 1) >> LOG2_SLACK, group, s_ref[...])

    put(j, slot).start()

    @pl.when(j == tiles - 1)
    def _():
        put(j, slot).wait()


@functools.partial(jax.jit, static_argnames=("block", "tile", "interpret"))
def _call(state, A, dt, dtc, bc, at0, n, *, block: int, tile: int, interpret: bool):
    N, di = state.shape
    tokens = dt.shape[0]
    if di % 128 or N % SLACK or tokens % SLACK:
        raise ValueError(f"mamba1_scan: a state [{N}, {di}] over {tokens} tokens does not fill "
                         "whole vector registers")
    W = tile if di % tile == 0 else 128
    rows = min(block + SLACK, tokens)  # a window: the block and the slack before it, or the whole step
    per_tile = pl.BlockSpec((N, W), lambda j, *_: (0, j))
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    window = pltpu.VMEM((2, rows, W), jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(di // W,),
        in_specs=[per_tile, per_tile, anywhere, anywhere, anywhere],
        out_specs=[per_tile, anywhere],
        scratch_shapes=[window, window, pltpu.VMEM((rows, 2 * N, 128), jnp.float32),
                        pltpu.VMEM((W // 128, SLACK * SLACK, 128), jnp.float32),
                        pltpu.SemaphoreType.DMA((2, 2)), pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((1,))],
    )
    state, y = pl.pallas_call(
        functools.partial(_kernel, interpret=interpret),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, jnp.float32),
                   jax.ShapeDtypeStruct(dtc.shape, jnp.float32)],
        # Operands counted with the prefetched scalars: ``dt c`` is the fifth.
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=32 << 20),
        interpret=interpret,
        name="mamba1_scan",
    )(jnp.stack([at0, n]).astype(jnp.int32), state, A, dt, dtc, bc)
    return y, state


def mamba1_scan(state, A, dt, y, bc, at0, n, *, block: int):
    """Tokens ``[at0, at0 + n)`` of one row, ``n`` at most ``block``: ``state``
    [N, di] float32 as the row left it, ``A`` [N, di]; of the whole step (a
    multiple of 8 tokens) ``dt`` [tokens, di] float32, ``bc`` [tokens, 2N, 128]
    its ``B`` and ``C`` along the sublanes (``lane_broadcast``) and ``y``
    [tokens, di] float32, which holds ``dt_t c_t`` where a token's recurrence
    has not been and ``y_t`` where it has.  Returns (``y`` with those tokens'
    rows written and every other as it was, the state after the last of them).
    ``y`` should be donated: the call writes it in place."""
    return _call(state, A, dt, y, bc, at0, n, block=block, tile=TILE, interpret=pallas_interpret())


def lane_broadcast(B, C):
    """``bc`` of ``mamba1_scan``: [T, 2N, 128] from ``B``, ``C`` [T, N]."""
    both = jnp.concatenate([B, C], axis=-1)
    return jnp.broadcast_to(both[:, :, None], both.shape + (128,))
