"""The Mamba-2 mixer of the hybrid family (``granitemoehybrid``;
docs/granite_hybrid.md has the equations, models/reference/granitemoehybrid.py
the recurrence one token at a time).

A layer's state for one sequence is ``S`` [heads, d_head, d_state] float32 (a
running sum over the whole context) and the convolution's tail, the last
``d_conv - 1`` inputs of the taps.  Both live in SLOTS, not pages
(``lfm2.HybridCache.ssm`` / ``.tail``): a running row reads and writes its
live slot in place, and a snapshot is a copy of it in another slot.

Two forms of ONE recurrence from ONE set of leaves:

``scan``  the chunked (SSD) form for the rows of a ragged step.  Rows are
          walked one after another and a row's tokens in chunks of
          ``SSD_CHUNK`` counted from the ROW's first token, so what a row
          computes depends neither on where it lies in the step nor on what
          shares the step: a chunk resumed from a snapshot is, to the bit, the
          chunk of the cold run.  The loops run as many times as the step has
          rows and chunks (padding rows cost nothing).
``step``  one token a row, every row at once (the fused decode program): row ``i``'s state
          is slot ``i``, updated in place by ONE call a layer (ops/mamba2_step.py).
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from ..ops.mamba2_step import mamba2_step
from .config import ModelConfig
from .llama import linear

Params = Dict[str, Any]

# Tokens a pass of ``scan``'s inner loop: any size gives the same sums; 128
# fills the MXU and keeps the [heads, chunk, chunk] decay matrix at 8 MB
# (the release trains with ``mamba_chunk_size`` 256).
SSD_CHUNK = 128

QUANT_AXES = {"in_proj": 1, "out_proj": 1}
ONES = ("norm_w",)


def dims(c: ModelConfig) -> Tuple[int, int, int, int, int]:
    """(inner width, heads, head size, state size, taps)."""
    return (c.mamba_n_heads * c.mamba_d_head, c.mamba_n_heads, c.mamba_d_head,
            c.mamba_d_state, c.mamba_d_conv)


def conv_width(c: ModelConfig) -> int:
    """Channels through the taps: u, B and C (one group)."""
    return c.mamba_n_heads * c.mamba_d_head + 2 * c.mamba_d_state


def leaf_shapes(c: ModelConfig, Lm: int) -> Dict[str, tuple]:
    di, Hm, _, N, K = dims(c)
    D = c.hidden_size
    # in_proj's columns: z (di), xBC (di + 2N: u, B, C), dt (a head).
    # conv_w[k] multiplies xBC_{t-K+1+k}.
    return {"in_proj": (Lm, D, 2 * di + 2 * N + Hm), "conv_w": (Lm, K, di + 2 * N),
            "conv_b": (Lm, di + 2 * N), "A_log": (Lm, Hm), "D": (Lm, Hm), "dt_bias": (Lm, Hm),
            "norm_w": (Lm, di), "out_proj": (Lm, di, D)}


def _draw_dt_bias(k, shape):
    # softplus(dt_bias) log-uniform in [1e-3, 1e-1], as the release initialises it.
    dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


# Leaves whose seeded draw is not N(0, 0.02): taps of a size that lets the
# state matter beside D u, A in [-16, -1], D = 1; A_log, D, dt_bias float32.
DRAWS = {
    "conv_w": lambda k, shape, dt: (jax.random.normal(k, shape, jnp.float32) * 0.5).astype(dt),
    "A_log": lambda k, shape, dt: jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0)),
    "D": lambda k, shape, dt: jnp.ones(shape, jnp.float32),
    "dt_bias": lambda k, shape, dt: _draw_dt_bias(k, shape),
}


class Rows(NamedTuple):
    """A ragged step's rows as the mixer reads them (shared by its layers)."""

    first: jnp.ndarray  # [S] a row's first token in the step
    count: jnp.ndarray  # [S] its tokens (0: no row)
    num: jnp.ndarray  # [] rows
    row_of: jnp.ndarray  # [T] a token's row (S for padding)
    read: jnp.ndarray  # [S] slot a row's state starts from (-1: zeros)
    write: jnp.ndarray  # [S] its live slot
    snap: jnp.ndarray  # [S] slot that gets a copy of its state after the step (-1: none)


def _rounded(v: jnp.ndarray, dtype) -> jnp.ndarray:
    fi = jnp.finfo(dtype)
    return jax.lax.reduce_precision(v, fi.nexp, fi.nmant).astype(dtype)


def _project(x, lp: Params, c: ModelConfig):
    di, Hm, _, N, _ = dims(c)
    zxd = linear(x, lp, "in_proj")
    z, xbc, dt = jnp.split(zxd, [di, 2 * di + 2 * N], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"].astype(jnp.float32))
    return z, xbc, dt


def _taps(prev, xbc, lp: Params, dtype):
    """silu(conv + b): ``prev[k - 1]`` is xBC_{t-k}."""
    w = lp["conv_w"].astype(jnp.float32)  # [K, C]
    K = w.shape[0]
    v = w[K - 1] * xbc.astype(jnp.float32) + lp["conv_b"].astype(jnp.float32)
    for k in range(1, K):
        v = v + w[K - 1 - k] * prev[k - 1].astype(jnp.float32)
    return _rounded(jax.nn.silu(v), dtype)


def _gated_out(y, z, lp: Params, c: ModelConfig, dtype):
    """W_out RMSNorm(y * silu(z)): the gate BEFORE the norm, one group."""
    g = y * jax.nn.silu(z.astype(jnp.float32))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + c.rms_norm_eps)
    return linear((g * lp["norm_w"].astype(jnp.float32)).astype(dtype), lp, "out_proj")


def row_taps(xbc, tail, m, rows: Rows, K: int):
    """The taps' inputs over the rows of a ragged step: (``prev``, with
    ``prev[k - 1]`` [T, C] the input k tokens before each token, from its run
    or, at the run's start, from the tail its row starts from; that tail ``t0``
    [S, K-1, C], zeros for a row that starts from nothing)."""
    (T,) = rows.row_of.shape
    S = rows.first.shape[0]
    row = jnp.minimum(rows.row_of, S - 1)
    idx = jnp.arange(T) - rows.first[row]  # a token's place in its row
    t0 = jnp.take(tail[m], jnp.maximum(rows.read, 0), axis=1).transpose(1, 0, 2)
    t0 = jnp.where((rows.read >= 0)[:, None, None], t0, 0)  # [S, K-1, C]
    prev = []
    for k in range(1, K):
        run = jnp.concatenate([jnp.zeros((k, xbc.shape[1]), xbc.dtype), xbc[:-k]], axis=0)
        old = t0[row, jnp.clip(K - 1 - k + idx, 0, K - 2)]
        prev.append(jnp.where((idx >= k)[:, None], run, old))
    return prev, t0


def leave_tails(xbc, t0, tail, m, rows: Rows, K: int):
    """What a row leaves in its ``write`` slot and its ``snap`` slot: its last
    K-1 inputs (older ones from its old tail ``t0``)."""
    (T,) = rows.row_of.shape
    S = rows.first.shape[0]
    j = jnp.arange(K - 1)[None, :]
    at = rows.count[:, None] - (K - 1) + j  # [S, K-1] place in the row
    from_run = xbc[jnp.clip(rows.first[:, None] + at, 0, T - 1)]
    from_old = jnp.take_along_axis(
        t0, jnp.clip(rows.count[:, None] + j, 0, K - 2)[:, :, None], axis=1)
    new_tail = jnp.where((at >= 0)[:, :, None], from_run, from_old).astype(tail.dtype)
    live = (jnp.arange(S) < rows.num) & (rows.count > 0) & (rows.write >= 0)
    size = tail.shape[2]
    for to in (jnp.where(live, rows.write, size),
               jnp.where(live & (rows.snap >= 0), rows.snap, size)):
        for k in range(K - 1):
            tail = tail.at[m, k, to].set(new_tail[:, k], mode="drop")
    return tail


def walk_rows(chunk, ssm, m, rows: Rows, Q: int, y, state_shape):
    """The recurrence a row at a time and ``Q`` of its tokens at a time:
    ``chunk(k, (y, state), first, count)`` -> (y, state) is one pass; a row's
    state ``state_shape`` starts from its ``read`` slot of ``ssm[m]`` (zeros
    without one) and ends in its ``write`` slot and, where ``snap`` names one,
    there too.  A slot is one block of its pool.  Returns (y, ssm)."""
    block = (1, 1) + ssm.shape[2:]

    def one_row(r, carry):
        y, ssm = carry
        first, count = rows.first[r], rows.count[r]
        read, write, snap = rows.read[r], rows.write[r], rows.snap[r]
        slot = lambda i: jax.lax.dynamic_slice(ssm, (m, i, 0, 0), block).reshape(state_shape)
        state = jnp.where(read >= 0, slot(jnp.maximum(read, 0)), 0.0)
        y, state = jax.lax.fori_loop(
            0, (count + Q - 1) // Q, lambda k, cr: chunk(k, cr, first, count), (y, state))
        # A row of no tokens, or without a slot (warm-up), leaves things as they were.
        to = jnp.maximum(write, 0)
        state = jnp.where((count > 0) & (write >= 0), state, slot(to))
        state = state.reshape(block)
        ssm = jax.lax.dynamic_update_slice(ssm, state, (m, to, 0, 0))
        # A row without a snapshot writes its live slot twice.
        ssm = jax.lax.dynamic_update_slice(
            ssm, state, (m, jnp.where(snap >= 0, snap, to), 0, 0))
        return y, ssm

    return jax.lax.fori_loop(0, rows.num, one_row, (y, ssm))


def step(x, lp: Params, c: ModelConfig, ssm, tail, m, ok):
    """One token a row: ``x`` [S, D]; ``ssm`` [Lm, S', Hm * P, N] / ``tail``
    [Lm, K-1, S', C] the slot pools (``lfm2.HybridCache`` on their shapes), of
    which this layer's are ``[m]`` and row i's is slot i; ``ok`` [S] False
    leaves a row's slot as it was.  Returns (y [S, D], ssm, tail)."""
    di, Hm, P, N, K = dims(c)
    S = x.shape[0]
    dtype = x.dtype
    z, xbc, dt = _project(x, lp, c)
    with jax.named_scope("mamba2_step"):
        old_tail = tail[m, :, :S]  # [K-1, S, C]
        act = _taps([old_tail[K - 1 - k] for k in range(1, K)], xbc, lp, dtype)
        u, B, C = jnp.split(act.astype(jnp.float32), [di, di + N], axis=-1)
        u = u.reshape(S, Hm, P)
        a = jnp.exp(dt * -jnp.exp(lp["A_log"].astype(jnp.float32)))  # [S, Hm]
        # S = a S + dt u B^T written back to the slot and y = S C off the tile
        # that was written: the pool is aliased through the call, which names
        # this layer's first S slots and nothing else of it, and leaves a row
        # whose ``ok`` is False as it was (ops/mamba2_step.py).
        y, ssm = mamba2_step(ssm, m, a, dt[:, :, None] * u, B, C, ok)
        y = y + lp["D"].astype(jnp.float32)[:, None] * u
        new_tail = jnp.concatenate([old_tail[1:], xbc[None].astype(tail.dtype)], axis=0)
        tail = tail.at[m, :, :S].set(jnp.where(ok[None, :, None], new_tail, old_tail))
    return _gated_out(y.reshape(S, di), z, lp, c, dtype), ssm, tail


def scan(x, lp: Params, c: ModelConfig, ssm, tail, m, rows: Rows):
    """The rows of a ragged step: ``x`` [T, D]; the pools and ``m`` as
    ``step``.  Returns (y [T, D], ssm, tail) with every row's state after its
    last token in its ``write`` slot and, where ``snap`` names one, in that
    slot too.  A slot is read and written as one block of its pool, never
    through a copy of the layer's slots."""
    di, Hm, P, N, K = dims(c)
    (T,) = rows.row_of.shape
    Q = min(SSD_CHUNK, T)
    dtype = x.dtype
    z, xbc, dt = _project(x, lp, c)
    with jax.named_scope("mamba2_scan"):
        prev, t0 = row_taps(xbc, tail, m, rows, K)
        act = _taps(prev, xbc, lp, dtype)
        tail = leave_tails(xbc, t0, tail, m, rows, K)

        # ---- the recurrence, a row at a time and a chunk of its tokens at a time.
        act = act.astype(jnp.float32)
        pad = ((0, Q), (0, 0))
        u = jnp.pad(act[:, :di], pad).reshape(T + Q, Hm, P)
        B = jnp.pad(act[:, di:di + N], pad)
        C = jnp.pad(act[:, di + N:], pad)
        dtp = jnp.pad(dt, pad)  # [T + Q, Hm]; tokens outside a row are masked chunk by chunk
        A = -jnp.exp(lp["A_log"].astype(jnp.float32))
        tri = jnp.tril(jnp.ones((Q, Q), bool))

        def chunk(k, carry, first, count):
            """Tokens [first + k Q, first + (k + 1) Q) of a row of ``count``."""
            y, state = carry
            at0 = first + k * Q
            valid = (k * Q + jnp.arange(Q)) < count  # [Q]
            cut = lambda v: jax.lax.dynamic_slice_in_dim(v, at0, Q, axis=0)
            dq = jnp.where(valid[:, None], cut(dtp), 0.0)  # [Q, Hm]
            xq = dq[:, :, None] * cut(u)  # [Q, Hm, P]: dt u (0 past the row's end)
            Bq, Cq = cut(B), cut(C)
            cs = jnp.cumsum(dq * A, axis=0)  # [Q, Hm] log decay from the chunk's start
            # y_i = sum_{j <= i} exp(cs_i - cs_j) (C_i . B_j) x_j + exp(cs_i) C_i . S_in
            decay = jnp.exp(jnp.where(tri[:, :, None], cs[:, None, :] - cs[None, :, :], -jnp.inf))
            m = (Cq @ Bq.T)[:, :, None] * decay  # [Q, Q, Hm]
            yq = jnp.einsum("ijh,jhp->ihp", m, xq)
            yq = yq + jnp.exp(cs)[:, :, None] * jnp.einsum("in,hpn->ihp", Cq, state)
            end = cs[Q - 1]  # [Hm]: past the row's end dq is 0, so this is its last token's
            state = (jnp.exp(end)[:, None, None] * state
                     + jnp.einsum("jh,jhp,jn->hpn", jnp.exp(end[None, :] - cs), xq, Bq))
            old = jax.lax.dynamic_slice_in_dim(y, at0, Q, axis=0)
            y = jax.lax.dynamic_update_slice_in_dim(
                y, jnp.where(valid[:, None, None], yq, old), at0, axis=0)
            return y, state

        y, ssm = walk_rows(chunk, ssm, m, rows, Q, jnp.zeros((T + Q, Hm, P), jnp.float32),
                           (Hm, P, N))
        y = y[:T] + lp["D"].astype(jnp.float32)[None, :, None] * u[:T]
    return _gated_out(y.reshape(T, di), z, lp, c, dtype), ssm, tail
