"""Kimi Delta Attention (KDA), the linear-attention mixer of ``kimi_linear`` in
the hybrid family (docs/kimi_linear.md has the equations,
models/reference/kimi_linear.py the recurrence one token at a time).

A layer's state for one sequence is ``S`` [heads, d_key, d_value] float32 and
the convolutions' tail, the last ``taps - 1`` inputs of q's, k's and v's taps.
Both live in SLOTS beside the pages, the pools models/mamba2.py's state lives
in (``lfm2.HybridCache.ssm`` / ``.tail``): a running row reads and writes its
live slot in place, a snapshot is a copy of it in another slot.

It is not Mamba-2 with other numbers.  The decay is a vector a head (a factor
a key CHANNEL, ``exp(g_t)`` with ``g_t <= 0``), and the update is a delta rule:
the decayed state is first READ with the key and then corrected by what the
value lacks,

    S' = diag(exp(g_t)) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T;
    o_t = S_t^T q_t.

Two forms of that ONE recurrence from ONE set of leaves:

``scan``  the chunked form for the rows of a ragged step, rows one after
          another and a row's tokens in chunks of ``KDA_CHUNK`` counted from
          the ROW's first token (any size gives the same sums; a chunk resumed
          from a snapshot is the chunk of the cold run).  With ``G`` the
          running sum of ``g`` inside a chunk, the corrections ``u_i = beta_i
          (v_i - S'_i^T k_i)`` of a chunk solve a unit lower triangular system
          ``(I + diag(beta) A) U = diag(beta) (V - (K * exp(G)) S_0)``,
          ``A_ij = sum_c k_ic k_jc exp(G_ic - G_jc)`` for j < i, where
          Mamba-2's chunk needs a cumulative product only.
          Only ``exp(G_i - G_j)`` with i >= j is ever taken (the PAIRWISE
          form): the textbook factoring ``(k_i exp(G_i)) . (k_j exp(-G_j))``
          overflows float32 inside one chunk (A 16, dt 0.1: G passes -100 in
          64 tokens).
``step``  one token a row, every row at once (the fused decode program): row
          ``i``'s state is slot ``i``, updated in place.

``g``, its running sums, their ``exp`` and the state are float32, and every
product with the state is taken at the highest matmul precision: the chip's
default rounds float32 operands to bfloat16, which a running sum that feeds
back into its own correction does not forgive.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

from ..ops.kda_step import kda_step
from . import mamba2
from .config import ModelConfig
from .llama import linear
from .mamba2 import Rows, _rounded

Params = Dict[str, Any]

# Tokens a pass of ``scan``'s inner loop: the pairwise decay is
# [chunk, chunk, heads, d_key] float32, 16 MB at 32 (67 MB at 64).
KDA_CHUNK = 32

QUANT_AXES = {"wqkv": 1, "wo": 1}
ONES = ("norm_w",)
# Seeded draws that are not N(0, 0.02), as the release initialises them and as
# the Mamba-2 leaves of the same names are drawn: taps N(0, 0.5), A =
# exp(A_log) uniform in [1, 16], softplus(dt_bias) log-uniform in [1e-3, 1e-1].
DRAWS = {name: mamba2.DRAWS[name] for name in ("conv_w", "A_log", "dt_bias")}
_HIGHEST = jax.lax.Precision.HIGHEST


def dims(c: ModelConfig) -> Tuple[int, int, int]:
    """(heads, head size of keys and of values, taps)."""
    return c.kda_n_heads, c.kda_head_dim, c.kda_conv


def conv_width(c: ModelConfig) -> int:
    """Channels through the taps: q, k and v, each with its own."""
    return 3 * c.kda_n_heads * c.kda_head_dim


def leaf_shapes(c: ModelConfig, Lk: int) -> Dict[str, tuple]:
    H, d, K = dims(c)
    D, r = c.hidden_size, c.kda_head_dim  # the two low-rank pairs' rank: a head's size
    # wqkv's columns: q, k, v (H heads of d each); conv_w[k] multiplies
    # qkv_{t-K+1+k}; w_low's columns: W_f1 (r), W_g1 (r), W_b (a head).
    return {"wqkv": (Lk, D, 3 * H * d), "conv_w": (Lk, K, 3 * H * d),
            "w_low": (Lk, D, 2 * r + H), "wf_b": (Lk, r, H * d), "dt_bias": (Lk, H * d),
            "A_log": (Lk, H), "wg_b": (Lk, r, H * d), "wg_bias": (Lk, H * d),
            "norm_w": (Lk, d), "wo": (Lk, H * d, D)}


def _project(x, lp: Params, c: ModelConfig):
    """(qkv [T, 3 H d] before the taps, g [T, H, d] float32 <= 0, beta [T, H]
    float32, the output gate's logits [T, H d] float32)."""
    H, d, _ = dims(c)
    T = x.shape[0]
    qkv = linear(x, lp, "wqkv")
    f, og, b = jnp.split(linear(x, lp, "w_low"), [d, 2 * d], axis=-1)
    f32 = lambda a, w: jnp.matmul(a, lp[w], preferred_element_type=jnp.float32)  # noqa: E731
    dt = jax.nn.softplus(f32(f, "wf_b") + lp["dt_bias"].astype(jnp.float32)).reshape(T, H, d)
    g = -jnp.exp(lp["A_log"].astype(jnp.float32))[None, :, None] * dt
    gate = f32(og, "wg_b") + lp["wg_bias"].astype(jnp.float32)
    return qkv, g, jax.nn.sigmoid(b.astype(jnp.float32)), gate


def _taps(prev, qkv, lp: Params, dtype):
    """silu(conv), no bias: ``prev[k - 1]`` is qkv_{t-k}."""
    w = lp["conv_w"].astype(jnp.float32)  # [K, C]
    K = w.shape[0]
    v = w[K - 1] * qkv.astype(jnp.float32)
    for k in range(1, K):
        v = v + w[K - 1 - k] * prev[k - 1].astype(jnp.float32)
    return _rounded(jax.nn.silu(v), dtype)


def _heads(act, c: ModelConfig):
    """(q, k, v) [T, H, d] float32 of the taps' output: q and k of length 1 a
    head, q times d ** -0.5."""
    H, d, _ = dims(c)
    q, k, v = (a.reshape(-1, H, d) for a in jnp.split(act.astype(jnp.float32), 3, axis=-1))
    unit = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    return unit(q) * d**-0.5, unit(k), v


def _gated_out(o, gate, lp: Params, c: ModelConfig, dtype):
    """W_o (RMSNorm_d(o) * sigmoid(gate)): the norm a head, one weight of d."""
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + c.rms_norm_eps)
    o = o * lp["norm_w"].astype(jnp.float32)
    T = o.shape[0]
    return linear((o.reshape(T, -1) * jax.nn.sigmoid(gate)).astype(dtype), lp, "wo")


def step(x, lp: Params, c: ModelConfig, ssm, tail, m, ok):
    """One token a row: ``x`` [S, D]; ``ssm`` [Lk, S', H * d, d] / ``tail``
    [Lk, K-1, S', 3 H d] the slot pools (``lfm2.HybridCache`` on their
    shapes), of which this layer's are ``[m]`` and row i's is slot i; ``ok``
    [S] False leaves a row's slot as it was.  Returns (y [S, D], ssm, tail)."""
    _, _, K = dims(c)
    S = x.shape[0]
    dtype = x.dtype
    qkv, g, beta, gate = _project(x, lp, c)
    with jax.named_scope("kda_step"):
        old_tail = tail[m, :, :S]  # [K-1, S, C]
        q, k, v = _heads(_taps([old_tail[K - 1 - j] for j in range(1, K)], qkv, lp, dtype), c)
        # The state's part is ONE call that holds a row's tile in VMEM: both
        # reads off the OLD state with the decay folded into k and q, u, o and
        # S = diag(exp g) S + k u^T written back to the slot (ops/kda_step.py).
        o, ssm = kda_step(ssm, m, jnp.exp(g), k, q, v, beta, ok)
        new_tail = jnp.concatenate([old_tail[1:], qkv[None].astype(tail.dtype)], axis=0)
        tail = tail.at[m, :, :S].set(jnp.where(ok[None, :, None], new_tail, old_tail))
    return _gated_out(o, gate, lp, c, dtype), ssm, tail


def scan(x, lp: Params, c: ModelConfig, ssm, tail, m, rows: Rows):
    """The rows of a ragged step: ``x`` [T, D]; the pools and ``m`` as
    ``step``.  Returns (y [T, D], ssm, tail) with every row's state after its
    last token in its ``write`` slot and, where ``snap`` names one, in that
    slot too.  A slot is read and written as one block of its pool, never
    through a copy of the layer's slots."""
    H, d, K = dims(c)
    (T,) = rows.row_of.shape
    Q = min(KDA_CHUNK, T)
    dtype = x.dtype
    qkv, g, beta, gate = _project(x, lp, c)
    with jax.named_scope("kda_scan"):
        prev, t0 = mamba2.row_taps(qkv, tail, m, rows, K)
        q, k, v = _heads(_taps(prev, qkv, lp, dtype), c)
        tail = mamba2.leave_tails(qkv, t0, tail, m, rows, K)
        # Padded by a chunk, so that a chunk's slice never runs off the end;
        # tokens outside a row are masked chunk by chunk.
        pad = lambda a: jnp.pad(a, ((0, Q),) + ((0, 0),) * (a.ndim - 1))  # noqa: E731
        q, k, v, g, beta = pad(q), pad(k), pad(v), pad(g), pad(beta)
        lower = jnp.tril(jnp.ones((Q, Q), bool))
        strict = jnp.tril(jnp.ones((Q, Q), bool), -1)
        eye = jnp.eye(Q, dtype=jnp.float32)

        def chunk(n, carry, first, count):
            """Tokens [first + n Q, first + (n + 1) Q) of a row of ``count``."""
            o, state = carry  # state [H, key, value]
            at0 = first + n * Q
            valid = (n * Q + jnp.arange(Q)) < count  # [Q]
            cut = lambda a: jax.lax.dynamic_slice_in_dim(a, at0, Q, axis=0)  # noqa: E731
            qc, kc, vc = cut(q), cut(k), cut(v)
            # Past the row's end: no decay and no write, so the state stands.
            G = jnp.cumsum(jnp.where(valid[:, None, None], cut(g), 0.0), axis=0)  # [Q, H, d]
            bc = jnp.where(valid[:, None], cut(beta), 0.0)  # [Q, H]
            # E_ij = exp(G_i - G_j) a channel, for j <= i only: never exp(-G_j).
            E = jnp.exp(jnp.where(lower[:, :, None, None], G[:, None] - G[None, :], -jnp.inf))
            kE = kc[None, :] * E  # [i, j, H, d]: k_j exp(G_i - G_j)
            A = jnp.sum(kc[:, None] * kE, axis=-1)  # [i, j, H] float32 on the vector unit
            B = jnp.sum(qc[:, None] * kE, axis=-1)
            A = jnp.where(strict[:, :, None], A, 0.0).transpose(2, 0, 1)  # [H, i, j]
            B = B.transpose(2, 0, 1)  # j <= i by E
            eG = jnp.exp(G)
            from_state = jnp.einsum(  # (k exp(G)) S_0 and (q exp(G)) S_0
                "xihk,hkv->xhiv", jnp.stack([kc * eG, qc * eG]), state, precision=_HIGHEST)
            bh = bc.T[:, :, None]  # [H, Q, 1]
            rhs = bh * (vc.transpose(1, 0, 2) - from_state[0])
            U = solve_triangular(eye + bh * A, rhs, lower=True, unit_diagonal=True)  # [H, Q, v]
            oc = from_state[1] + jnp.einsum("hij,hjv->hiv", B, U, precision=_HIGHEST)
            end = G[Q - 1]  # [H, d]: the row's last token's
            state = jnp.exp(end)[:, :, None] * state + jnp.einsum(
                "jhk,hjv->hkv", kc * jnp.exp(end[None] - G), U, precision=_HIGHEST)
            old = jax.lax.dynamic_slice_in_dim(o, at0, Q, axis=0)
            o = jax.lax.dynamic_update_slice_in_dim(
                o, jnp.where(valid[:, None, None], oc.transpose(1, 0, 2), old), at0, axis=0)
            return o, state

        o, ssm = mamba2.walk_rows(chunk, ssm, m, rows, Q, jnp.zeros((T + Q, H, d), jnp.float32),
                                  (H, d, d))
    return _gated_out(o[:T], gate, lp, c, dtype), ssm, tail
