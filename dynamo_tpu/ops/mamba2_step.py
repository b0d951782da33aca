"""Mamba-2's one-step form as ONE Pallas call a layer: a decoding row's scan
state is read from its slot once and written back once.

    S_t = a S + x B^T,  y = S_t C        (a = exp(dt A) a head, x = dt u)

(models/mamba2.py has the recurrence; ``D u`` stays with the caller).  ``y``
reads the NEW state, so XLA makes two ops of it, an update in place and a
reduction that reads the pool again.  Here a program holds the tile of
``block_heads`` heads of one row, ``[block_heads * P, N]`` float32 rows of the
pool as they lie, in VMEM: the update and the read-out happen on that copy and
the tile goes back where it came from.

The pool ``[layers, slots, Hm * P, N]`` is ALIASED to the output and stays
where it lies (``pl.ANY``): a program copies its tile into one of two VMEM
buffers while its neighbour is worked on, and out of one of two while the next
is (``pltpu.make_async_copy``, as ``ops/kda_step.py`` walks its tiles; no
BlockSpec names a block of the pool: docs/granite_hybrid.md says why).  A slot
past the step's rows, and every other layer, is never named by a copy and is
not touched; a row whose ``ok`` is False has its tile copied through.  The
layer is a prefetched scalar (it is traced where the layers run as one jitted
function).  The grid is walked in order: a tile is fetched one program ahead.

Layout.  A head's rows lie along a tile's sublanes, the state's ``N`` along its
lanes.  ``B`` and ``C`` are lane vectors shared by every row of the tile.
``a`` is one scalar a head: the call takes it as prefetched scalars and a
program splats it.  Only ``x`` scales single ROWS, so it is needed as a
column: the call takes it as lanes (``[.., rows a strip]``, the way XLA has
it), and a program turns a strip's row over with one transposition of its
sublane broadcast, a strip being the whole heads that fill 128 rows.  The sum
over ``N`` runs on the matrix unit (``C . S_t^T`` at ``Precision.HIGHEST``),
so ``y`` leaves along the lanes too.  A tile is walked ``STRIPS_A_PASS`` strips
at a time in a ``fori_loop``: the kernel's text does not grow with the heads.
Nothing is rounded: the state, the decay and every product are float32, as in
``mamba2.scan``.

Compiles for the chip or raises (a shape Mosaic cannot tile raises there);
under the Pallas interpreter only where ``DYN_PALLAS_INTERPRET`` asks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ragged_attention import pallas_interpret

# Rows of a strip, the part of a tile whose ``x`` is turned over at once: the
# whole heads that fit a [128, 128] transposition.
STRIP_ROWS = 128
# Of one state tile [block_heads * P, N] float32: two in flight in, two out
# (ops/kda_step.py found 1 MB the size to ride inside the decode program).
TILE_BYTES = 1 << 20
# Strips a pass of the loop over a tile, so that one strip's transposition and
# read-out wait behind another's arithmetic: alone the call takes 0.72 ms with
# one, 0.45 with two, 0.42 with four (PERF.md section 6, PR 55).
STRIPS_A_PASS = 8


def block_heads(Hm: int, P: int, N: int) -> tuple[int, int]:
    """(heads a program, heads a strip): a strip is the most heads that divide
    ``Hm`` and lie in ``STRIP_ROWS`` rows (one where a head is longer), a
    program the most whole strips that divide ``Hm`` and whose tile stays
    under ``TILE_BYTES`` (one strip where none does)."""
    strip = max([n for n in range(1, Hm + 1) if Hm % n == 0 and n * P <= STRIP_ROWS] or [1])
    fit = [n for n in range(strip, Hm + 1, strip)
           if Hm % n == 0 and n * P * N * 4 <= TILE_BYTES]
    return (max(fit) if fit else strip), strip


def _tile_step(a_ref, first, x_ref, b_ref, c_ref, s_ref, y_ref, out_ref, *, strip: int, P: int):
    """One token on for a tile's heads.  ``a_ref`` (SMEM) holds the decay of
    the tile's head j at ``first + j``; ``x_ref`` / ``y_ref`` [strips, strip *
    P]: a strip's ``dt u`` and its read-out (along the lanes); ``b_ref`` /
    ``c_ref`` [1, N]; ``s_ref`` / ``out_ref`` [strips * strip * P, N]: the tile
    as it was and as it will be."""
    rows, N = strip * P, s_ref.shape[1]
    b = b_ref[...]
    c8 = jnp.broadcast_to(c_ref[...], (8, N))

    def one_strip(n):
        # x as a column, broadcast along the lanes: [rows, N]
        xc = jnp.broadcast_to(x_ref[pl.ds(n, 1), :], (N, rows)).T
        new = []
        for j in range(strip):
            old = s_ref[pl.ds(pl.multiple_of(n * rows + j * P, P), P), :]
            new.append(a_ref[first + n * strip + j] * old + xc[j * P:(j + 1) * P] * b)
        new = jnp.concatenate(new, axis=0) if strip > 1 else new[0]
        out_ref[pl.ds(pl.multiple_of(n * rows, rows), rows), :] = new
        y = jax.lax.dot_general(c8, new, (((1,), (1,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)  # [8, rows]
        y_ref[pl.ds(n, 1), :] = y[0:1]

    strips = x_ref.shape[0]
    together = max(n for n in range(1, STRIPS_A_PASS + 1) if strips % n == 0)

    def some_strips(k, carry):
        for j in range(together):
            one_strip(k * together + j)
        return carry

    jax.lax.fori_loop(0, strips // together, some_strips, 0)


def _kernel(meta_ref, ok_ref, a_ref, x_ref, b_ref, c_ref, pool_ref, y_ref, new_ref,
            inbuf, outbuf, isem, osem, *, strip: int, P: int, Hm: int):
    """Grid (row, block of heads), walked in order.  ``pool_ref`` / ``new_ref``
    [layers, slots, Hm * P, N]: the pool where it lies, read and written (one
    buffer); ``inbuf`` / ``outbuf`` [2, tile rows, N]: a tile coming in while
    its neighbour is worked on, one going out while the next is."""
    m = meta_ref[0]
    r, h, nh = pl.program_id(0), pl.program_id(1), pl.num_programs(1)
    i, n = r * nh + h, pl.num_programs(0) * nh
    rows = inbuf.shape[1]
    slot = i % 2

    def tile(ref, j):
        return ref.at[m, j // nh, pl.ds((j % nh) * rows, rows)]

    def fetch(j, slot):
        return pltpu.make_async_copy(tile(pool_ref, j), inbuf.at[slot], isem.at[slot])

    def put(j, slot):
        return pltpu.make_async_copy(outbuf.at[slot], tile(new_ref, j), osem.at[slot])

    @pl.when(i == 0)
    def _():
        fetch(0, 0).start()

    @pl.when(i + 1 < n)
    def _():
        fetch(i + 1, 1 - slot).start()

    fetch(i, slot).wait()

    @pl.when(i >= 2)
    def _():
        put(i - 2, slot).wait()  # this slot's last tile has left

    live = ok_ref[r] != 0

    @pl.when(live)
    def _():
        _tile_step(a_ref, r * Hm + h * (rows // P), x_ref, b_ref, c_ref, inbuf.at[slot], y_ref,
                   outbuf.at[slot], strip=strip, P=P)

    @pl.when(jnp.logical_not(live))
    def _():
        outbuf[slot] = inbuf[slot]
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    put(i, slot).start()

    @pl.when(i == n - 1)
    def _():
        put(i, slot).wait()

        @pl.when(n >= 2)
        def _():
            put(i - 1, 1 - slot).wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(ssm, m, a, x, B, C, ok, *, interpret: bool):
    S, Hm, P = x.shape
    N = B.shape[-1]
    hb, strip = block_heads(Hm, P, N)
    strips = hb // strip
    lanes = (S, Hm // strip, strip * P)  # x and y: a strip's rows along the lanes
    tile = pltpu.VMEM((2, hb * P, N), jnp.float32)
    per_row = pl.BlockSpec((None, 1, N), lambda r, h, *_: (r, 0, 0))
    per_tile = pl.BlockSpec((None, strips, strip * P), lambda r, h, *_: (r, h, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, Hm // hb),
        in_specs=[per_tile, per_row, per_row, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[per_tile, pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[tile, tile, pltpu.SemaphoreType.DMA((2,)), pltpu.SemaphoreType.DMA((2,))],
    )
    y, ssm = pl.pallas_call(
        functools.partial(_kernel, strip=strip, P=P, Hm=Hm),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(lanes, jnp.float32),
                   jax.ShapeDtypeStruct(ssm.shape, ssm.dtype)],
        # Operands counted with the three prefetched scalars: the pool is the seventh.
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=32 << 20),
        interpret=interpret,
        name="mamba2_step",
    )(jnp.reshape(m, (1,)).astype(jnp.int32), ok.astype(jnp.int32), a.reshape(S * Hm),
      x.reshape(lanes), B.reshape(S, 1, N), C.reshape(S, 1, N), ssm)
    return y.reshape(S, Hm, P), ssm


def mamba2_step(ssm, m, a, x, B, C, ok):
    """``ssm`` [layers, slots, Hm * P, N] float32, the state pool, of which row
    i's is ``ssm[m, i]``; ``a`` = exp(dt A) [S, Hm], ``x`` = dt u [S, Hm, P],
    ``B``, ``C`` [S, N] float32; ``ok`` [S] False leaves a row's slot as it
    was.  Returns (y = S_t C [S, Hm, P] float32, the pool with rows 0..S-1 of
    layer ``m`` one token on).  The pool should be donated: the call writes it
    in place."""
    return _call(ssm, m, a, x, B, C, ok, interpret=pallas_interpret())
