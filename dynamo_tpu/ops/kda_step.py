"""Kimi Delta Attention's one-step form as ONE Pallas call a layer: a decoding
row's state is read from its slot once and written back once.

    S_t = diag(exp g) S + k u^T,  u = beta (v - S^T (e * k)),  o = S^T (e * q) + u (k . q)

(models/kda.py has the recurrence; both reads come off the OLD state with the
decay folded into the vectors, so the decayed state is never formed apart).
``u`` needs a sum over the whole key axis of a head before the first element
of ``S_t`` can be written, which is why XLA moves the state three and a half
times: out of the pool, through the reduction, through the update.  Here a
program holds the tile of ``block_heads`` heads of one row, ``[block_heads *
d, d]`` float32 rows of the pool as they lie, in VMEM: both reads, ``u``,
``o`` and the update happen on that copy and the tile goes back where it
came from.

The pool ``[layers, slots, H * d, d]`` is ALIASED to the output and stays
where it lies (``pl.ANY``): a program copies its tile into one of two VMEM
buffers while its neighbour is worked on, and out of one of two while the next
is (``pltpu.make_async_copy``, the way ``ops/dense_mla.py`` walks pages).  A
slot past the step's rows, and every other layer, is never named by a copy and
is not touched; a row whose ``ok`` is False has its tile copied through.  The
layer is a prefetched scalar (it is traced where the layers run as one jitted
function).  The grid is walked in order: a tile is fetched one program ahead.

Layout.  Keys lie along a tile's sublanes, values along its lanes.  ``e * k``,
``e * q``, ``exp g`` and ``k`` scale ROWS of a head's [d, d], so they are
needed as columns: the call takes them as lanes ([heads, d], the way XLA has
them), and a program turns a group of heads' four vectors over in one
transpose; each column is then broadcast along the lanes in registers.  The
sums over keys are float32 adds on the vector unit.  Nothing is rounded: the
state, the decay and every product are float32, as in ``kda.scan``.

Compiles for the chip or raises (a shape Mosaic cannot tile raises there);
under the Pallas interpreter only where ``DYN_PALLAS_INTERPRET`` asks.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ragged_attention import pallas_interpret

# Heads whose vectors share a transpose: at most a float32 sublane tile.
GROUP = 8
# Of one state tile [block_heads * d, d] float32: two in flight in, two out.
# At 32 heads of 128 that is 16 heads a program: alone the call takes the same
# time with 8, 16 or 32, inside the decode program 16 read 0.21 ms a call where
# 8 read 0.24 (PERF.md section 6, PR 54).
TILE_BYTES = 1 << 20
# The small operand's rows a head: exp g, k, q, v, beta.
_VECS = 5


def block_heads(H: int, d: int) -> tuple[int, int]:
    """(heads a program, heads a group): a group is ``gcd(H, GROUP)`` heads, a
    program the most whole groups that divide H and whose tile stays under
    ``TILE_BYTES`` (one group where none does)."""
    group = math.gcd(H, GROUP)
    fit = [n for n in range(group, H + 1, group) if H % n == 0 and n * d * d * 4 <= TILE_BYTES]
    return (max(fit) if fit else group), group


def _tile_step(vec_ref, s_ref, o_ref, out_ref, *, group: int, d: int):
    """One token on for a tile's heads.  ``vec_ref`` [groups, 5 * group, d]: a
    group's exp g, k, q, v and beta (along the lanes), ``group`` rows each;
    ``s_ref`` / ``out_ref`` [groups * group * d, d]: the tile as it was and as
    it will be; ``o_ref`` [groups * group, d]."""
    for n in range(vec_ref.shape[0]):
        vec = vec_ref[n]
        eg, k, q, v, beta = (vec[i * group:(i + 1) * group] for i in range(_VECS))
        kq = jnp.sum(k * q, axis=1, keepdims=True)  # [group, 1]
        # Columns of the four vectors that scale a head's ROWS: one
        # transpose a group, padded to whole lanes.
        pad = jnp.zeros((128 - 4 * group, d), jnp.float32)
        cols = jnp.concatenate([eg * k, eg * q, eg, k, pad], axis=0).T  # [d, 128]
        rows = []
        for j in range(group):
            at = pl.ds((n * group + j) * d, d)
            ek, eq, e, kc = (cols[:, i * group + j:i * group + j + 1] for i in range(4))  # [d, 1]
            s = s_ref[at, :]  # [d keys, d values]
            read_k = jnp.sum(s * ek, axis=0, keepdims=True)  # [1, d]
            read_q = jnp.sum(s * eq, axis=0, keepdims=True)
            u = beta[j:j + 1] * (v[j:j + 1] - read_k)
            rows.append(read_q + u * kq[j:j + 1])
            out_ref[at, :] = e * s + kc * u
        o_ref[n * group:(n + 1) * group, :] = jnp.concatenate(rows, axis=0)


def _kernel(meta_ref, ok_ref, vec_ref, pool_ref, o_ref, new_ref, inbuf, outbuf, isem, osem,
            *, group: int, d: int):
    """Grid (row, block of heads), walked in order.  ``pool_ref`` / ``new_ref``
    [layers, slots, H * d, d]: the pool where it lies, read and written (one
    buffer); ``inbuf`` / ``outbuf`` [2, tile rows, d]: a tile coming in while
    its neighbour is worked on, one going out while the next is."""
    m = meta_ref[0]
    r, nh = pl.program_id(0), pl.num_programs(1)
    i, n = r * nh + pl.program_id(1), pl.num_programs(0) * nh
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
        _tile_step(vec_ref, inbuf.at[slot], o_ref, outbuf.at[slot], group=group, d=d)

    @pl.when(jnp.logical_not(live))
    def _():
        outbuf[slot] = inbuf[slot]
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    put(i, slot).start()

    @pl.when(i == n - 1)
    def _():
        put(i, slot).wait()

        @pl.when(n >= 2)
        def _():
            put(i - 1, 1 - slot).wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(ssm, m, eg, k, q, v, beta, ok, *, interpret: bool):
    S, H, d = k.shape
    hb, group = block_heads(H, d)
    groups = hb // group
    b = jnp.broadcast_to(beta[..., None], (S, H, d))
    # [S, H / group, 5 * group, d]: what a program needs of a group, one block.
    vec = jnp.stack([a.reshape(S, H // group, group, d) for a in (eg, k, q, v, b)], axis=2)
    vec = vec.reshape(S, H // group, _VECS * group, d)
    tile = pltpu.VMEM((2, hb * d, d), jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, H // hb),
        in_specs=[
            pl.BlockSpec((None, groups, _VECS * group, d), lambda r, h, meta, ok: (r, h, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((None, hb, d), lambda r, h, meta, ok: (r, h, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[tile, tile, pltpu.SemaphoreType.DMA((2,)), pltpu.SemaphoreType.DMA((2,))],
    )
    return pl.pallas_call(
        functools.partial(_kernel, group=group, d=d),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S, H, d), jnp.float32),
                   jax.ShapeDtypeStruct(ssm.shape, ssm.dtype)],
        # Operands counted with the two prefetched scalars: the pool is the fourth.
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=32 << 20),
        interpret=interpret,
        name="kda_step",
    )(jnp.reshape(m, (1,)).astype(jnp.int32), ok.astype(jnp.int32), vec, ssm)


def kda_step(ssm, m, eg, k, q, v, beta, ok):
    """``ssm`` [layers, slots, H * d, d] float32, the state pool, of which row
    i's is ``ssm[m, i]``; ``eg`` = exp(g), ``k``, ``q``, ``v`` [S, H, d] and
    ``beta`` [S, H] float32; ``ok`` [S] False leaves a row's slot as it was.
    Returns (o [S, H, d] float32, the pool with rows 0..S-1 of layer ``m``
    one token on).  The pool should be donated: the call writes it in place."""
    return _call(ssm, m, eg, k, q, v, beta, ok, interpret=pallas_interpret())
