"""ops/kda_step.py (KDA's one-step form as one Pallas call a layer, the pool
aliased to the output) under the Pallas interpreter against the reference's
one-token recurrence (models/reference/kimi_linear.py::kda_state_step) from a
STORED state.

Tolerance: 2e-5 of the largest reference value, tests/test_kimi_linear.py's
(both sides float32; they differ in the order of a sum over a head's keys and
in where the decay is multiplied in).  What the call must NOT touch is held to
the bit: a row whose ``ok`` is False, every slot past the step's rows, every
other layer of the pool.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models.reference import kimi_linear as ref
from dynamo_tpu.ops import kda_step as ks

TOL = 2e-5
LAYERS, SLOTS, S = 2, 7, 5
# (heads, head size, TILE_BYTES): one group of 4 heads; two programs a row of
# one group each; one program of two groups; three groups of 4 (gcd(12, 8)).
SHAPES = [(4, 16, ks.TILE_BYTES), (16, 16, 8 * 16 * 16 * 4), (16, 8, ks.TILE_BYTES),
          (12, 16, ks.TILE_BYTES)]
IDS = ["4-heads-one-group", "16-heads-two-programs", "16-heads-two-groups", "12-heads-groups-of-4"]


def close(a, b):
    return float(np.max(np.abs(np.asarray(a) - np.asarray(b))) / np.max(np.abs(np.asarray(b))))


def case(H, d, seed, g_value=None):
    rs = np.random.RandomState(seed)
    draw = lambda *shape: jnp.asarray(rs.randn(*shape), jnp.float32)  # noqa: E731
    unit = lambda a: a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    pool = draw(LAYERS, SLOTS, H * d, d)
    q, k, v = unit(draw(S, H, d)) * d**-0.5, unit(draw(S, H, d)), draw(S, H, d)
    g = -jnp.exp(draw(S, H, d)) if g_value is None else jnp.full((S, H, d), g_value, jnp.float32)
    beta = jax.nn.sigmoid(draw(S, H))
    return pool, q, k, v, g, beta


def token_loop(pool, m, q, k, v, g, beta):
    """(S_t, o_t) of every row from slot i of layer ``m``, one row at a time."""
    H, d = k.shape[1:]
    out = [ref.kda_state_step(pool[m, i].reshape(H, d, d), q[i], k[i], v[i], g[i], beta[i])
           for i in range(S)]
    return np.stack([np.asarray(s).reshape(H * d, d) for s, _ in out]), np.stack(
        [np.asarray(o) for _, o in out])


@pytest.mark.parametrize("H,d,tile", SHAPES, ids=IDS)
@pytest.mark.parametrize("m", [0, 1])
def test_the_call_is_the_references_token_step_and_touches_nothing_else(monkeypatch, H, d, tile, m):
    """Live and dead rows mixed, fewer rows than slots, two layers in the
    pool, the layer traced (as ``kda_layer(l, m, ...)`` has it)."""
    monkeypatch.setattr(ks, "TILE_BYTES", tile)
    pool, q, k, v, g, beta = case(H, d, seed=11 + m)
    ok = np.array([True, False, True, True, False])
    want_s, want_o = token_loop(pool, m, q, k, v, g, beta)
    o, new = jax.jit(lambda pool, m: ks.kda_step(
        pool, m, jnp.exp(g), k, q, v, beta, jnp.asarray(ok)))(pool, jnp.int32(m))
    assert o.shape == (S, H, d) and new.shape == pool.shape and new.dtype == jnp.float32
    assert close(o[ok], want_o[ok]) < TOL
    assert close(new[m, :S][ok], want_s[ok]) < TOL
    assert float(np.max(np.abs(want_s[ok] - np.asarray(pool[m, :S])[ok]))) > 0.01  # it moved
    assert np.array_equal(new[m, :S][~ok], pool[m, :S][~ok])  # a dead row's slot
    assert np.array_equal(new[m, S:], pool[m, S:])  # slots past the rows
    assert np.array_equal(new[1 - m], pool[1 - m])  # the other layer


@pytest.mark.parametrize("H,d,tile", SHAPES, ids=IDS)
def test_a_decay_that_underflows_is_finite_and_the_references(monkeypatch, H, d, tile):
    """g = -100 a channel: exp(g) is 3.8e-44, a float32 denormal (zero where
    the backend flushes them).  The old state is gone either way and the new
    one is k u^T with u = beta v."""
    monkeypatch.setattr(ks, "TILE_BYTES", tile)
    pool, q, k, v, g, beta = case(H, d, seed=5, g_value=-100.0)
    want_s, want_o = token_loop(pool, 1, q, k, v, g, beta)
    o, new = ks.kda_step(pool, 1, jnp.exp(g), k, q, v, beta, jnp.ones(S, bool))
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(new)).all()
    assert close(o, want_o) < TOL and close(new[1, :S], want_s) < TOL
    assert close(new[1, :S], np.einsum("shk,shv->shkv", k, beta[..., None] * v).reshape(
        S, H * d, d)) < TOL


def test_four_steps_in_a_scan_carry_the_pool_as_four_calls_do():
    """The fused decode program's shape: the call inside ``lax.scan`` with the
    pool as the carry, rows ending mid-chunk (``ok`` falls)."""
    H, d = 4, 16
    pool, q, k, v, g, beta = case(H, d, seed=3)
    oks = jnp.asarray([[True] * 5, [True, True, False, True, True],
                       [True, False, False, True, True], [False] * 4 + [True]])
    one = lambda pool, ok: ks.kda_step(pool, 1, jnp.exp(g), k, q, v, beta, ok)  # noqa: E731

    def body(pool, ok):
        o, pool = one(pool, ok)
        return pool, o

    fused, os_ = jax.jit(lambda pool: jax.lax.scan(body, pool, oks))(pool)
    single = pool
    for t in range(4):
        o, single = one(single, oks[t])
        assert close(os_[t], o) < 1e-6  # XLA:CPU contracts a loop's body its own way
    assert close(fused, single) < 1e-6
    assert np.array_equal(fused[0], pool[0]) and np.array_equal(fused[1, S:], pool[1, S:])


@pytest.mark.parametrize("H,d,want", [(32, 128, (16, 8)), (8, 128, (8, 8)), (4, 16, (4, 4)),
                                      (64, 128, (16, 8)), (24, 128, (8, 8)), (12, 64, (12, 4))])
def test_the_head_block_follows_from_the_heads_and_their_size(H, d, want):
    """Groups of gcd(H, 8) heads; a program the most whole groups under
    ``TILE_BYTES`` that divide H."""
    hb, group = ks.block_heads(H, d)
    assert (hb, group) == want
    assert H % hb == 0 and hb % group == 0 and 4 * group <= 128
