"""The Mamba-1 mixer of the hybrid family (``jamba``; docs/jamba.md has the
equations, models/reference/jamba.py the recurrence one token at a time).

It is not Mamba-2 with other numbers.  The decay differs a (channel, state
index) PAIR, ``exp(dt_t[d] A[n, d])``, so there are no heads, no [chunk, chunk]
decay matrix to multiply by and no matmul form of the recurrence: it is
elementwise in time,

    S_t[n, d] = exp(dt_t[d] A[n, d]) S_{t-1}[n, d] + dt_t[d] B_t[n] c_t[d];
    y_t[d] = sum_n C_t[n] S_t[n, d] + D[d] c_t[d],

with the step size ``dt_t`` a learned projection of the token through a
bottleneck of ``mamba_dt_rank`` and three RMSNorms INSIDE the mixer (Jamba's
own addition), and a gate ``silu(z)`` without a norm.

A layer's state for one sequence is ``S`` [d_state, inner] float32 (the state
index on sublanes, the channels on lanes: with the 16 minor the chip's (8, 128)
tiling would pad a slot eightfold) and the taps' tail, their last ``d_conv -
1`` inputs.  Both live in the SLOTS Mamba-2's and KDA's live in
(``lfm2.HybridCache.ssm`` / ``.tail``).

Two forms of ONE recurrence from ONE set of leaves:

``scan``  the rows of a ragged step, one after another and a row's tokens in
          token order from the ROW's first token: what a row computes depends
          neither on where it lies in the step nor on what shares it, and a
          chunk resumed from a snapshot is, to the bit, the chunk of the cold
          run.
``step``  one token a row, every row at once (the fused decode program): row
          ``i``'s state is slot ``i``, updated in place.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..ops import mamba1_scan
from . import mamba2
from .config import ModelConfig
from .llama import linear
from .mamba2 import Rows

Params = Dict[str, Any]

# No leaf of the mixer is served in int8: the release's card keeps the Mamba
# blocks out of quantization, and ``--weight-quant int8`` honours that.
QUANT_AXES: Dict[str, int] = {}
ONES = ("dt_norm", "b_norm", "c_norm")


def dims(c: ModelConfig) -> Tuple[int, int, int, int]:
    """(inner width, state size, taps, the step size's rank)."""
    return c.mamba_expand * c.hidden_size, c.mamba_d_state, c.mamba_d_conv, c.mamba_dt_rank


def leaf_shapes(c: ModelConfig, Lm: int) -> Dict[str, tuple]:
    di, N, K, R = dims(c)
    D = c.hidden_size
    # in_proj's columns: u (di), z (di); conv_w[k] multiplies u_{t-K+1+k};
    # x_proj's columns: dt's bottleneck (R), B (N), C (N); A_log is stored
    # [N, di], the state's own layout (the release: [di, N]).
    return {"in_proj": (Lm, D, 2 * di), "conv_w": (Lm, K, di), "conv_b": (Lm, di),
            "x_proj": (Lm, di, R + 2 * N), "dt_norm": (Lm, R), "b_norm": (Lm, N),
            "c_norm": (Lm, N), "dt_proj": (Lm, R, di), "dt_bias": (Lm, di),
            "A_log": (Lm, N, di), "D": (Lm, di), "out_proj": (Lm, di, D)}


# Seeded draws that are not N(0, 0.02), as the release initialises them: the
# taps, D and dt_bias as Mamba-2's leaves of those names; A = 1..16 along the
# state index of every channel.
DRAWS = {
    **{name: mamba2.DRAWS[name] for name in ("conv_w", "D", "dt_bias")},
    "A_log": lambda k, shape, dt: jnp.broadcast_to(
        jnp.log(jnp.arange(1, shape[-2] + 1, dtype=jnp.float32))[:, None], shape),
}


def _project(u, lp: Params, c: ModelConfig, prev, dtype):
    """Everything of the mixer before the recurrence, from the taps' inputs
    ``u`` on (``prev[k - 1]`` is u_{t-k}): (c_t [T, di] in ``dtype``, dt [T,
    di], B [T, N], C [T, N] float32)."""
    _, N, _, R = dims(c)
    with jax.named_scope("mamba1_taps"):
        act = mamba2._taps(prev, u, lp, dtype)
    f32 = lambda a, w: jnp.matmul(a, lp[w], preferred_element_type=jnp.float32)  # noqa: E731
    norm = lambda v, w: (v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True)  # noqa: E731
                                           + c.rms_norm_eps) * lp[w].astype(jnp.float32))
    dt, B, C = jnp.split(f32(act, "x_proj"), [R, R + N], axis=-1)
    dt = f32(norm(dt, "dt_norm").astype(dtype), "dt_proj") + lp["dt_bias"].astype(jnp.float32)
    return act, jax.nn.softplus(dt), norm(B, "b_norm"), norm(C, "c_norm")


# Tokens a call of the recurrence (``ops/mamba1_scan.py``) at most: a row's
# last block costs its tokens, a block past a row's end is not entered, and any
# size gives the same sums.
SCAN_CHUNK = 256


def _recurrence(ssm, m, A, dt, B, C, cf, rows: Rows):
    """(y [T, di] float32 without the ``D c`` term, ssm): the recurrence over
    the step's rows (``mamba2.walk_rows``), strictly in token order, a block of
    a row's tokens one Pallas call that carries the state in registers and
    leaves ``y_t`` where it found ``dt_t c_t``."""
    T, di = dt.shape
    N = A.shape[0]
    Q = min(SCAN_CHUNK, T)
    whole = lambda a: jnp.pad(a, ((0, -T % mamba1_scan.SLACK),) + ((0, 0),) * (a.ndim - 1))  # noqa: E731
    dt8, bc = whole(dt), whole(mamba1_scan.lane_broadcast(B, C))

    def chunk(k, carry, first, count):
        """Tokens [first + k Q, first + (k + 1) Q) of a row of ``count``."""
        y, state = carry
        return mamba1_scan.mamba1_scan(state, A, dt8, y, bc, first + k * Q,
                                       jnp.minimum(Q, count - k * Q), block=Q)

    y, ssm = mamba2.walk_rows(chunk, ssm, m, rows, Q, whole(dt * cf), (N, di))
    # A token of no row (the step's padding) has had no recurrence.
    return jnp.where((rows.row_of < rows.count.shape[0])[:, None], y[:T], 0.0), ssm


def _gated_out(y, z, lp: Params, dtype):
    """W_out (y * silu(z)): no norm between."""
    return linear((y * jax.nn.silu(z.astype(jnp.float32))).astype(dtype), lp, "out_proj")


def step(x, lp: Params, c: ModelConfig, ssm, tail, m, ok):
    """One token a row: ``x`` [S, D]; ``ssm`` [Ls, S', N, di] / ``tail`` [Ls,
    K-1, S', di] the slot pools (``lfm2.HybridCache`` on their shapes), of
    which this layer's are ``[m]`` and row i's is slot i; ``ok`` [S] False
    leaves a row's slot as it was.  Returns (y [S, D], ssm, tail)."""
    di, _, K, _ = dims(c)
    S = x.shape[0]
    dtype = x.dtype
    u, z = jnp.split(linear(x, lp, "in_proj"), [di], axis=-1)
    with jax.named_scope("mamba1_taps"):
        old_tail = tail[m, :, :S]  # [K-1, S, di]
        new_tail = jnp.concatenate([old_tail[1:], u[None].astype(tail.dtype)], axis=0)
        tail = tail.at[m, :, :S].set(jnp.where(ok[None, :, None], new_tail, old_tail))
    act, dt, B, C = _project(u, lp, c, [old_tail[K - 1 - k] for k in range(1, K)], dtype)
    with jax.named_scope("mamba1_step"):
        cf = act.astype(jnp.float32)
        A = -jnp.exp(lp["A_log"].astype(jnp.float32))  # [N, di]
        old = ssm[m, :S]  # [S, N, di]
        new = jnp.exp(dt[:, None, :] * A[None]) * old + (dt * cf)[:, None, :] * B[:, :, None]
        y = jnp.sum(C[:, :, None] * new, axis=1) + lp["D"].astype(jnp.float32) * cf
        ssm = ssm.at[m, :S].set(jnp.where(ok[:, None, None], new, old))
    return _gated_out(y, z, lp, dtype), ssm, tail


def scan(x, lp: Params, c: ModelConfig, ssm, tail, m, rows: Rows):
    """The rows of a ragged step: ``x`` [T, D]; the pools and ``m`` as
    ``step``.  Returns (y [T, D], ssm, tail) with every row's state after its
    last token in its ``write`` slot and, where ``snap`` names one, in that
    slot too.  A slot is read and written as one block of its pool, never
    through a copy of the layer's slots."""
    di, _, K, _ = dims(c)
    dtype = x.dtype
    u, z = jnp.split(linear(x, lp, "in_proj"), [di], axis=-1)
    with jax.named_scope("mamba1_taps"):
        prev, t0 = mamba2.row_taps(u, tail, m, rows, K)
        tail = mamba2.leave_tails(u, t0, tail, m, rows, K)
    act, dt, B, C = _project(u, lp, c, prev, dtype)
    with jax.named_scope("mamba1_scan"):
        cf = act.astype(jnp.float32)
        A = -jnp.exp(lp["A_log"].astype(jnp.float32))
        y, ssm = _recurrence(ssm, m, A, dt, B, C, cf, rows)
        y = y + lp["D"].astype(jnp.float32) * cf
    return _gated_out(y, z, lp, dtype), ssm, tail
