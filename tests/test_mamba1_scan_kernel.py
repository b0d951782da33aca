"""ops/mamba1_scan.py (Mamba-1's prompt-side recurrence as one Pallas call a
block of a row's tokens, the state carried in registers) under the Pallas
interpreter against the recurrence one token at a time: XLA's token loop, the
form ``models/mamba1.py`` had before the call (``lax.scan`` over a block's
decays and inputs taken at once).

Tolerance: 2e-5 of the largest reference value, tests/test_jamba.py's (both
sides float32; they differ in ``exp`` against ``exp2`` and in the order of the
sum over the state's 16).  What the call must NOT touch is held to the bit:
``y`` outside the block's valid tokens, the state where none is valid.  So are
the contracts of ``models/mamba1.py::scan``: any block size gives the same
sums, and a chunk resumed from a snapshot is the cold chunk.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import mamba1, mamba2
from dynamo_tpu.ops import mamba1_scan as ks

TOL = 2e-5
N, T, Q = 16, 40, 16


def close(a, b):
    return float(np.max(np.abs(np.asarray(a) - np.asarray(b))) / np.max(np.abs(np.asarray(b))))


def case(di, seed):
    """(state, A, dt, c, B, C) of a step of ``T`` tokens, as ``mamba1.scan`` hands them on."""
    rs = np.random.RandomState(seed)
    draw = lambda *shape: jnp.asarray(rs.randn(*shape), jnp.float32)  # noqa: E731
    A = -jnp.exp(jnp.asarray(rs.uniform(0.0, 2.7, (N, di)), jnp.float32))  # in [-16, -1]
    return draw(N, di), A, jax.nn.softplus(draw(T, di)), draw(T, di), draw(T, N), draw(T, N)


def token_loop(state, A, dt, dtc, B, C):
    """XLA's token loop: (y [tokens, di], the state after the last)."""
    decay = jnp.exp(dt[:, None, :] * A[None])
    inp = dtc[:, None, :] * B[:, :, None]

    def token(S, t):
        a, b, c_t = t
        S = a * S + b
        return S, jnp.sum(c_t[:, None] * S, axis=0)

    state, y = jax.lax.scan(token, state, (decay, inp, C))
    return np.asarray(y), np.asarray(state)


# (first token, valid tokens): a full block from an odd token, from a multiple
# of 8 and at the step's end; one token; all but one; none.
BLOCKS = [(3, Q), (8, Q), (T - Q, Q), (5, 1), (13, Q - 1), (7, 0)]


@pytest.mark.parametrize("at0,n", BLOCKS, ids=[f"from-{a}-{n}-valid" for a, n in BLOCKS])
@pytest.mark.parametrize("di,tile", [(128, 128), (256, 128)], ids=["one-tile", "two-tiles"])
def test_a_block_is_the_token_loop_from_a_stored_state_and_touches_nothing_else(monkeypatch, di, tile, at0, n):
    monkeypatch.setattr(ks, "TILE", tile)
    state, A, dt, cf, B, C = case(di, seed=at0 + n)
    dtc = dt * cf
    y, new = ks.mamba1_scan(state, A, dt, dtc, ks.lane_broadcast(B, C), jnp.int32(at0), jnp.int32(n), block=Q)
    assert y.shape == dtc.shape and new.shape == state.shape and new.dtype == jnp.float32
    mine = np.zeros(T, bool)
    mine[at0:at0 + n] = True
    assert np.array_equal(np.asarray(y)[~mine], np.asarray(dtc)[~mine])  # ``dt c`` where no recurrence has been
    if n == 0:
        assert np.array_equal(new, state)
        return
    want_y, want_s = token_loop(state, A, *(v[at0:at0 + n] for v in (dt, dtc, B, C)))
    assert close(np.asarray(y)[mine], want_y) < TOL and close(new, want_s) < TOL
    assert float(np.max(np.abs(want_s - np.asarray(state)))) > 0.01  # it moved


def test_a_state_that_does_not_fill_whole_registers_is_refused_by_name():
    state, A, dt, cf, B, C = case(64, seed=0)
    with pytest.raises(ValueError, match="whole vector registers"):
        ks.mamba1_scan(state, A, dt, dt * cf, ks.lane_broadcast(B, C), jnp.int32(0), jnp.int32(Q), block=Q)


# ------------------------------------------- through ``mamba1._recurrence``
def rows_of(spans):
    first, count, read, write, snap = (np.asarray(v, np.int32) for v in zip(*spans))
    row_of = np.full(T, len(spans), np.int32)
    for i, (f, c) in enumerate(zip(first, count)):
        row_of[f:f + c] = i
    return mamba2.Rows(*(jnp.asarray(v) for v in (first, count, np.int32(len(spans)), row_of, read, write, snap)))


def recurrence(block, pool, A, dt, cf, B, C, spans, monkeypatch):
    monkeypatch.setattr(mamba1, "SCAN_CHUNK", block)
    y, pool = mamba1._recurrence(pool, 1, A, dt, B, C, cf, rows_of(spans))
    return np.asarray(y), np.asarray(pool)


def test_two_block_sizes_give_the_same_sums(monkeypatch):
    """A row of 29 tokens from a stored state beside a row of 3, in blocks of
    8 (a last block of 5) and in one block: the same bits, and the token loop's
    sums."""
    state, A, dt, cf, B, C = case(128, seed=5)
    pool = jnp.zeros((2, 4, N, 128)).at[1, 2].set(state)
    spans = [(2, 29, 2, 0, 3), (33, 3, -1, 1, -1)]
    y8, pool8 = recurrence(8, pool, A, dt, cf, B, C, spans, monkeypatch)
    y40, pool40 = recurrence(40, pool, A, dt, cf, B, C, spans, monkeypatch)
    assert np.array_equal(y8, y40) and np.array_equal(pool8, pool40)
    want_y, want_s = token_loop(state, A, dt[2:31], (dt * cf)[2:31], B[2:31], C[2:31])
    assert close(y8[2:31], want_y) < TOL and close(pool8[1, 0], want_s) < TOL
    assert np.array_equal(pool8[1, 3], pool8[1, 0]) and np.array_equal(pool8[0], np.zeros_like(pool8[0]))
    assert np.array_equal(y8[:2], np.zeros_like(y8[:2]))  # no row's tokens: as ``scan`` hands them on


@pytest.mark.parametrize("block", [8, 40], ids=["blocks-of-8", "one-block"])
def test_a_chunk_resumed_from_its_snapshot_is_the_cold_chunk_to_the_bit(monkeypatch, block):
    """The cold run: 19 tokens into slot 0 with a snapshot in slot 3, then the
    next 14 from slot 0.  The resumed row reads the snapshot and lies elsewhere
    in its step, behind a stranger: ``y`` and the state it leaves are the cold
    chunk's."""
    state, A, dt, cf, B, C = case(128, seed=9)
    pool = jnp.zeros((2, 5, N, 128))
    _, pool = recurrence(block, pool, A, dt, cf, B, C, [(0, 19, -1, 0, 3)], monkeypatch)
    assert np.array_equal(pool[1, 3], pool[1, 0]) and float(np.abs(pool[1, 3]).max()) > 0
    cold_y, cold = recurrence(block, jnp.asarray(pool), A, dt, cf, B, C, [(19, 14, 0, 0, -1)], monkeypatch)
    move = lambda v: jnp.roll(v, 5, axis=0)  # noqa: E731  (tokens 19.. now lie at 24..)
    hit_y, hit = recurrence(block, jnp.asarray(pool), A, move(dt), move(cf), move(B), move(C),
                            [(0, 7, -1, 4, -1), (24, 14, 3, 2, -1)], monkeypatch)
    assert np.array_equal(hit_y[24:38], cold_y[19:33]) and np.array_equal(hit[1, 2], cold[1, 0])
