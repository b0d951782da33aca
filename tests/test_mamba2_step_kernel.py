"""ops/mamba2_step.py (Mamba-2's one-step form as one Pallas call a layer, the
pool aliased to the output and left where it lies) under the Pallas interpreter
against the reference's one-token recurrence from a STORED state
(models/reference/granitemoehybrid.py::mamba2, the body of its ``token``: the
reference starts a sequence from zeros, so its two lines are said here).

Tolerance: 2e-5 of the largest reference value, tests/test_granite_hybrid.py's
(both sides float32; they differ in the order of the sum over the state's
``N``).  What the call must NOT touch is held to the bit: a row whose ``ok`` is
False, every slot past the step's rows, every other layer of the pool.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import mamba2
from dynamo_tpu.ops import mamba2_step as ms

TOL = 2e-5
LAYERS, SLOTS, S = 2, 7, 5
# (heads, head size, state size, TILE_BYTES): one program a row, one strip of 4
# heads; four programs a row of one strip (8 heads) each; the rehearsal
# engine's pool [.., 128, 16]; 12 heads, strips of 6 (96 rows); a head longer
# than a strip, two programs a row; the cell's heads of 64 rows, a tile of 16
# strips walked in two passes of ``STRIPS_A_PASS``.
SHAPES = [(4, 16, 32, ms.TILE_BYTES), (32, 16, 32, 8 * 16 * 32 * 4), (8, 16, 16, ms.TILE_BYTES),
          (12, 16, 32, ms.TILE_BYTES), (2, 256, 8, 256 * 8 * 4), (32, 64, 8, ms.TILE_BYTES)]
IDS = ["4-heads-one-strip", "32-heads-four-programs", "rehearsal-pool-n16", "12-heads-strips-of-6",
       "a-head-of-256-rows", "16-strips-two-passes"]


def close(a, b):
    return float(np.max(np.abs(np.asarray(a) - np.asarray(b))) / np.max(np.abs(np.asarray(b))))


def case(Hm, P, N, seed):
    rs = np.random.RandomState(seed)
    draw = lambda *shape: jnp.asarray(rs.randn(*shape), jnp.float32)  # noqa: E731
    pool = draw(LAYERS, SLOTS, Hm * P, N)
    dt = jax.nn.softplus(draw(S, Hm))
    A = -jnp.exp(jnp.asarray(rs.uniform(0.0, 2.7, Hm), jnp.float32))  # in [-16, -1]
    return pool, draw(S, Hm, P), draw(S, N), draw(S, N), dt, A


def token(S_, u_t, B_t, C_t, dt_t, A):
    """The reference's ``token``: one row's state [Hm, P, N] one token on."""
    S_ = jnp.exp(dt_t * A)[:, None, None] * S_ + (dt_t[:, None] * u_t)[:, :, None] * B_t[None, None, :]
    return S_, jnp.einsum("hpn,n->hp", S_, C_t)


def token_loop(pool, m, u, B, C, dt, A):
    """(S_t, y_t) of every row from slot i of layer ``m``, a row apart from the others."""
    Hm, P = u.shape[1:]
    s, y = jax.vmap(token, in_axes=(0, 0, 0, 0, 0, None))(
        pool[m, :S].reshape(S, Hm, P, -1), u, B, C, dt, A)
    return np.asarray(s).reshape(S, Hm * P, -1), np.asarray(y)


@jax.jit
def call(pool, m, u, B, C, dt, A, ok):
    """Jitted once a shape: both layers of a case share the program (``m`` is traced)."""
    return ms.mamba2_step(pool, m, jnp.exp(dt * A), dt[:, :, None] * u, B, C, ok)


@pytest.mark.parametrize("Hm,P,N,tile", SHAPES, ids=IDS)
@pytest.mark.parametrize("m", [0, 1])
def test_the_call_is_the_references_token_step_and_touches_nothing_else(monkeypatch, Hm, P, N, tile, m):
    """Live and dead rows mixed, fewer rows than slots, two layers in the
    pool, the layer traced (as ``lfm2``'s layer loop has it)."""
    monkeypatch.setattr(ms, "TILE_BYTES", tile)
    pool, u, B, C, dt, A = case(Hm, P, N, seed=11 + m)
    ok = np.array([True, False, True, True, False])
    want_s, want_y = token_loop(pool, m, u, B, C, dt, A)
    y, new = call(pool, jnp.int32(m), u, B, C, dt, A, jnp.asarray(ok))
    assert y.shape == (S, Hm, P) and new.shape == pool.shape and new.dtype == jnp.float32
    assert close(y[ok], want_y[ok]) < TOL
    assert close(new[m, :S][ok], want_s[ok]) < TOL
    assert float(np.max(np.abs(want_s[ok] - np.asarray(pool[m, :S])[ok]))) > 0.01  # it moved
    assert np.array_equal(new[m, :S][~ok], pool[m, :S][~ok])  # a dead row's slot
    assert np.array_equal(new[m, S:], pool[m, S:])  # slots past the rows
    assert np.array_equal(new[1 - m], pool[1 - m])  # the other layer


def test_four_steps_in_a_scan_carry_the_pool_as_four_calls_do():
    """The fused decode program's shape: the call inside ``lax.scan`` with the
    pool as the carry, rows ending mid-chunk (``ok`` falls)."""
    pool, u, B, C, dt, A = case(4, 16, 32, seed=3)
    oks = jnp.asarray([[True] * 5, [True, True, False, True, True],
                       [True, False, False, True, True], [False] * 4 + [True]])
    one = lambda pool, ok: call(pool, 1, u, B, C, dt, A, ok)  # noqa: E731

    def body(pool, ok):
        y, pool = one(pool, ok)
        return pool, y

    fused, ys = jax.jit(lambda pool: jax.lax.scan(body, pool, oks))(pool)
    single = pool
    for t in range(4):
        y, single = one(single, oks[t])
        assert close(ys[t][np.asarray(oks[t])], y[oks[t]]) < 1e-6  # XLA:CPU contracts a loop's body its own way
    assert close(fused, single) < 1e-6
    assert np.array_equal(fused[0], pool[0]) and np.array_equal(fused[1, S:], pool[1, S:])


def test_the_mixers_step_reaches_the_state_through_the_call_alone(monkeypatch):
    """``mamba2.step`` hands the pool to ``mamba2_step`` and returns what it
    gave: no other line of the step reads or writes the state."""
    from dynamo_tpu.models.config import ModelConfig

    seen = []

    def spy(ssm, m, a, x, B, C, ok):
        seen.append((ssm, a.shape, x.shape, B.shape, C.shape))
        return jnp.zeros(x.shape, jnp.float32), ssm + 1.0

    monkeypatch.setattr(mamba2, "mamba2_step", spy)
    c = ModelConfig(name="t", vocab_size=64, num_layers=1, num_heads=2, num_kv_heads=2, head_dim=16,
                    intermediate_size=64, hidden_size=32, mamba_n_heads=4, mamba_d_head=16,
                    mamba_d_state=8, mamba_d_conv=4, dtype="float32")
    rs = np.random.RandomState(0)
    lp = {k: jnp.asarray(rs.randn(*shape[1:]) * 0.1, jnp.float32)
          for k, shape in mamba2.leaf_shapes(c, 1).items()}
    ssm = jnp.asarray(rs.randn(LAYERS, SLOTS, 64, 8), jnp.float32)
    tail = jnp.zeros((LAYERS, 3, SLOTS, mamba2.conv_width(c)), jnp.float32)
    _, got, _ = mamba2.step(jnp.asarray(rs.randn(S, 32), jnp.float32), lp, c, ssm, tail, 1,
                            jnp.ones(S, bool))
    assert len(seen) == 1 and seen[0][0] is ssm
    assert seen[0][1:] == ((S, 4), (S, 4, 16), (S, 8), (S, 8))
    assert np.array_equal(got, ssm + 1.0)


@pytest.mark.parametrize("Hm,P,N,want", [(128, 64, 128, (32, 2)), (8, 16, 16, (8, 8)),
                                         (48, 64, 128, (24, 2)), (12, 16, 32, (12, 6)),
                                         (24, 128, 128, (12, 1)), (4, 256, 128, (4, 1))])
def test_the_head_block_follows_from_the_heads_and_their_size(Hm, P, N, want):
    """Strips of the most heads that divide ``Hm`` and fill at most 128 rows;
    a program the most whole strips under ``TILE_BYTES`` that divide ``Hm``."""
    hb, strip = ms.block_heads(Hm, P, N)
    assert (hb, strip) == want
    assert Hm % hb == 0 and hb % strip == 0 and hb * P * N * 4 <= ms.TILE_BYTES
