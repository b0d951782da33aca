"""The grouped expert matmul (ops/grouped_matmul.py) and the dispatch built on
it (models/moe.py ``expert_dispatch``) against the per-expert TABLES they
replaced: ``[E, C, D]`` rows gathered for every expert, ``qdot_batched`` over
all of them, a scatter-add back.  On the CPU, the kernel under the Pallas
interpreter (tests/conftest.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import deepseek_v32 as ds
from dynamo_tpu.models import moe
from dynamo_tpu.models.config import ModelConfig, get_config
from dynamo_tpu.models.quant import _quantize_jnp
from dynamo_tpu.ops.grouped_matmul import TILE_ROWS, moe_grouped_matmul
from dynamo_tpu.ops.quant_matmul import qdot_batched, quantize_rows

NAMES = ("moe_gate", "moe_up", "moe_down")


def leaves(E, D, F, kind, seed=0):
    """One layer's expert leaves [E, ...]: int8 with scales, or float."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    lp = {n: jax.random.normal(k, (E, D, F) if n != "moe_down" else (E, F, D)) * 0.1
          for n, k in zip(NAMES, ks)}
    if kind == "int8":
        out = {}
        for n, w in lp.items():
            out[n], out[n + "_scale"] = _quantize_jnp(w, 1)
        return out
    return {n: w.astype(kind) for n, w in lp.items()}


def linear_tables(xe, lp, name, out_dtype=None):
    """[E, C, K] x [E, K, N]: what every held expert computed before."""
    s = lp.get(name + "_scale")
    if s is not None:
        return qdot_batched(xe, lp[name], s, out_dtype=out_dtype)
    r = jnp.einsum("ecd,edf->ecf", xe, lp[name])
    return r.astype(out_dtype) if out_dtype is not None else r


def tables_dispatch(xt, chosen, weights, lp, E, valid=None):
    """The dispatch this PR replaced, at full (dropless) capacity C = T."""
    T, D = xt.shape
    K = chosen.shape[1]
    flat_e = chosen.reshape(T * K)
    if valid is not None:
        flat_e = jnp.where(valid.reshape(T * K), flat_e, E)
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    pos = (jnp.cumsum(onehot, axis=0) - 1)[jnp.arange(T * K), jnp.minimum(flat_e, E - 1)]
    pos = jnp.where(flat_e >= E, T, pos)
    idx = jnp.full((E, T), T, jnp.int32).at[flat_e, pos].set(flat_t, mode="drop")
    gw = jnp.zeros((E, T), jnp.float32).at[flat_e, pos].set(weights.reshape(T * K), mode="drop")
    xe = jnp.concatenate([xt, jnp.zeros((1, D), xt.dtype)], axis=0)[idx]
    g = jax.nn.silu(linear_tables(xe, lp, "moe_gate", jnp.float32)).astype(xt.dtype)
    ye = linear_tables(g * linear_tables(xe, lp, "moe_up"), lp, "moe_down")
    yt = jnp.zeros((T + 1, D), jnp.float32).at[idx.reshape(-1)].add(
        (ye.astype(jnp.float32) * gw[..., None]).reshape(-1, D), mode="drop")
    return yt[:T].astype(xt.dtype)


def routing(T, K, E, Et, seed):
    """top-K of random logits over ``Et`` experts, of which the first E are held."""
    logits = jax.random.normal(jax.random.PRNGKey(100 + seed), (T, Et))
    w, chosen = jax.lax.top_k(logits, K)
    return chosen, jax.nn.softmax(w, axis=-1), chosen < E


def ulps(a, b):
    """Largest distance in units of the last place between two float32 arrays."""
    a, b = (np.asarray(v, np.float32).view(np.int32).astype(np.int64) for v in (a, b))
    a, b = (np.where(v < 0, -(v & 0x7FFFFFFF), v) for v in (a, b))
    return int(np.abs(a - b).max())


# ----------------------------------------------------------------- the kernel
def grouped_matmul_xla(x, x_scale, ws, w_scales, tile_expert, n_tiles, layer, *, out_dtypes):
    """``moe_grouped_matmul``'s contract in plain XLA, the tests' second
    oracle: each tile's weights gathered, one batched matmul over the tiles."""
    K = x.shape[1]
    tiles = tile_expert.shape[0]
    M = tiles * TILE_ROWS
    xt = jnp.broadcast_to(x.reshape(-1, TILE_ROWS, K), (tiles, TILE_ROWS, K))  # a shared tile
    if x_scale is not None:
        x_scale = jnp.broadcast_to(x_scale.reshape(-1, TILE_ROWS, 1), (tiles, TILE_ROWS, 1))
    live = (jnp.arange(tiles, dtype=jnp.int32) < n_tiles)[:, None, None]
    outs = []
    for n, (w, dt) in enumerate(zip(ws, out_dtypes)):
        wt = w[layer, tile_expert]  # [tiles, K, N]
        acc = jax.lax.dot_general(
            xt, wt, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32 if x_scale is None else jnp.int32)
        if x_scale is not None:
            acc = acc.astype(jnp.float32) * x_scale * w_scales[n][layer, tile_expert][:, None, :]
        outs.append(jnp.where(live, acc, 0).astype(dt).reshape(M, -1))
    return outs


def tile_rows(tiles, K, kind, seed):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(k1, (tiles * TILE_ROWS, K))
    if kind == "int8":
        return quantize_rows(x)
    return x.astype(kind), None


@pytest.mark.parametrize("scales", ["unit", "real"])
def test_kernel_int8_accumulator_is_exact_and_rescale_within_an_ulp(scales):
    """int8 x int8 accumulates in int32, exactly: with unit scales the
    float32 output IS the accumulator (|acc| <= 96 * 127^2 < 2^24), equal to
    ``qdot_batched``'s over the tables to the last bit; with real scales the
    rescaled result is within 1 ulp."""
    E, K, N, tiles, n_live = 5, 96, 256, 6, 4
    lp = leaves(E, K, N, "int8", seed=3)
    w, s = lp["moe_gate"][None], lp["moe_gate_scale"][None]
    xq, xs = tile_rows(tiles, K, "int8", 1)
    if scales == "unit":
        s, xs = jnp.ones_like(s), jnp.ones_like(xs)
    te = jnp.asarray([0, 2, 2, 4, 4, 4], jnp.int32)  # experts 1 and 3 have no tile
    (got,) = moe_grouped_matmul(xq, xs, (w,), (s,), te, jnp.int32(n_live), jnp.int32(0),
                                out_dtypes=(jnp.float32,), interpret=True)
    # the tables: every tile's rows under its expert, all experts multiplied
    acc = jax.lax.dot_general(xq.reshape(tiles, TILE_ROWS, K), w[0][te],
                              (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.int32)
    want = acc.astype(jnp.float32) * xs.reshape(tiles, TILE_ROWS, 1) * s[0][te][:, None, :]
    want = np.asarray(want).reshape(tiles * TILE_ROWS, N)
    got = np.asarray(got)
    live = n_live * TILE_ROWS
    if scales == "unit":
        assert (got[:live] == np.asarray(acc, np.float32).reshape(-1, N)[:live]).all()
    assert ulps(got[:live], want[:live]) <= 1
    assert (got[live:] == 0).all()  # a tile past the last live one reads zero


def test_off_the_chip_the_kernel_is_interpreted_only_when_asked(monkeypatch):
    """``DYN_PALLAS_INTERPRET`` (tests/conftest.py sets it) is the one switch:
    without it the dispatch asks Mosaic, whatever the backend, and a CPU
    refuses; no plain-XLA form is picked in its place."""
    E, D, F, T = 4, 64, 32, 16
    lp = leaves(E, D, F, "float32")
    x = jax.random.normal(jax.random.PRNGKey(0), (T, D))
    chosen, w, valid = routing(T, 2, E, 8, 0)
    run = lambda: jax.jit(lambda x: moe.expert_dispatch(x, chosen, w, lp, E, valid=valid)[0])(x)
    asked = np.asarray(run())
    assert np.abs(asked).max() > 0
    monkeypatch.setenv("DYN_PALLAS_INTERPRET", "0")
    with pytest.raises(Exception, match="(?i)interpret|cpu|platform|mosaic"):
        run()


@pytest.mark.parametrize("kind", ["int8", "float32", "bfloat16"])
def test_kernel_is_the_xla_form_and_the_tables(kind):
    """Two leaves that share the rows (gate and up), a stacked leaf and a
    layer index: the Pallas walk, the same contract in plain XLA, and the
    batched matmul over tables agree."""
    L, E, K, N, tiles, n_live = 3, 4, 64, 128, 5, 5
    per_layer = [leaves(E, K, N, kind, seed=10 + l) for l in range(L)]
    stack = {n: jnp.stack([lp[n] for lp in per_layer]) for n in per_layer[0]}
    ws = (stack["moe_gate"], stack["moe_up"])
    ss = (stack["moe_gate_scale"], stack["moe_up_scale"]) if kind == "int8" else None
    x, xs = tile_rows(tiles, K, kind, 2)
    te = jnp.asarray([1, 1, 2, 3, 3], jnp.int32)
    dts = (jnp.float32, jnp.bfloat16 if kind != "float32" else jnp.float32)
    args = (x, xs, ws, ss, te, jnp.int32(n_live), jnp.int32(2))
    got = moe_grouped_matmul(*args, out_dtypes=dts, interpret=True)
    xla = grouped_matmul_xla(*args, out_dtypes=dts)
    lp = per_layer[2]
    rows = (jax.random.normal(jax.random.split(jax.random.PRNGKey(2))[0], (tiles * TILE_ROWS, K))
            .astype(jnp.float32 if kind == "int8" else kind))
    for n, name in enumerate(("moe_gate", "moe_up")):
        g, want = np.asarray(got[n], np.float32), np.asarray(xla[n], np.float32)
        assert ulps(g, want) <= (1 if dts[n] == jnp.float32 else 0) or np.allclose(g, want, rtol=1e-6)
        # the tables: tile i's rows as expert te[i]'s table
        table = jnp.zeros((E, tiles * TILE_ROWS, K), rows.dtype)
        for i, e in enumerate(np.asarray(te)):
            sl = slice(i * TILE_ROWS, (i + 1) * TILE_ROWS)
            table = table.at[e, sl].set(rows[sl])
        full = np.asarray(linear_tables(table, lp, name, dts[n]), np.float32)
        for i, e in enumerate(np.asarray(te)):
            sl = slice(i * TILE_ROWS, (i + 1) * TILE_ROWS)
            assert np.allclose(g[sl], full[e, sl], rtol=2e-2 if kind == "bfloat16" else 1e-5,
                               atol=1e-6)


@pytest.mark.parametrize("kind", ["int8", "float32"])
def test_kernel_with_one_shared_tile_of_rows_is_that_tile_repeated(kind):
    """A step of at most ``TILE_ROWS`` rows: its rows as they stand are every
    live expert's tile, passed once and never copied a tile."""
    E, K, N, tiles = 4, 64, 128, 4
    lp = leaves(E, K, N, kind, seed=5)
    ws = (lp["moe_gate"][None],)
    ss = (lp["moe_gate_scale"][None],) if kind == "int8" else None
    x, xs = tile_rows(1, K, kind, 3)
    te = jnp.asarray([0, 2, 3, 3], jnp.int32)
    args = (ws, ss, te, jnp.int32(3), jnp.int32(0))
    (shared,) = moe_grouped_matmul(x, xs, *args, out_dtypes=(jnp.float32,), interpret=True)
    (repeated,) = moe_grouped_matmul(jnp.tile(x, (tiles, 1)), None if xs is None else jnp.tile(xs, (tiles, 1)),
                                     *args, out_dtypes=(jnp.float32,), interpret=True)
    (xla,) = grouped_matmul_xla(x, xs, *args, out_dtypes=(jnp.float32,))
    assert (np.asarray(shared) == np.asarray(repeated)).all()
    assert ulps(shared, xla) <= 1 or np.allclose(shared, xla, rtol=1e-6)
    assert (np.asarray(shared)[3 * TILE_ROWS:] == 0).all()


# --------------------------------------------------------------- the dispatch
@pytest.mark.parametrize("kind", ["int8", "float32"])
@pytest.mark.parametrize("T,K,E,Et", [(16, 8, 12, 96), (48, 2, 4, 16), (200, 4, 8, 8)],
                         ids=["decode-16", "tokens-48", "three-chunks-200"])
def test_dispatch_equals_the_tables_for_random_routings(T, K, E, Et, kind):
    """Random routings, some pairs on experts not held.  T = 200 needs 33
    tiles at most: the loop over chunks of 16 tiles runs, with a dynamic
    trip count."""
    D, F = 64, 32
    lp = leaves(E, D, F, kind, seed=T)
    x = jax.random.normal(jax.random.PRNGKey(T), (T, D))
    for seed in range(2):
        chosen, w, valid = routing(T, K, E, Et, seed)
        got, load = jax.jit(lambda *a: moe.expert_dispatch(*a[:3], lp, E, valid=a[3]))(
            x, chosen, w, valid)
        want = jax.jit(lambda *a: tables_dispatch(*a[:3], lp, E, valid=a[3]))(x, chosen, w, valid)
        scale = float(jnp.max(jnp.abs(want)))
        # float32 sums of a token's K contributions in another order
        assert float(jnp.max(jnp.abs(got - want))) <= 1e-6 * max(scale, 1.0)
        assert (np.asarray(load) == np.bincount(
            np.asarray(chosen)[np.asarray(valid)], minlength=E)[:E]).all()


@pytest.mark.parametrize("kind", ["int8", "float32"])
def test_every_token_to_one_held_expert_is_dropless(kind):
    """The case the full-table branch existed for: all 80 tokens choose held
    expert 2 (their other choice lands elsewhere): three tiles of one expert,
    no pair dropped, and the experts without a row are never multiplied (their
    float weights are NaN)."""
    T, E, D, F = 80, 4, 64, 32
    lp = leaves(E, D, F, kind, seed=1)
    x = jax.random.normal(jax.random.PRNGKey(5), (T, D))
    chosen = jnp.stack([jnp.full((T,), 2), jnp.full((T,), E + 3)], axis=1)
    w = jnp.full((T, 2), 0.5, jnp.float32)
    valid = chosen < E
    want = tables_dispatch(x, chosen, w, lp, E, valid=valid)
    if kind == "float32":
        only = jnp.arange(E)[:, None, None] == 2
        lp = {n: jnp.where(only, v, jnp.nan) for n, v in lp.items()}
    got, load = jax.jit(lambda x: moe.expert_dispatch(x, chosen, w, lp, E, valid=valid))(x)
    assert np.asarray(load).tolist() == [0, 0, T, 0]
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-6
    assert float(jnp.min(jnp.abs(got).sum(axis=1))) > 0  # every token got its expert's output


def test_no_pair_on_any_held_expert_reads_no_expert():
    """Output exactly zero and no expert counted; float leaves of NaN show
    that no weight entered anything."""
    T, E, D, F = 16, 4, 64, 32
    lp = {n: jnp.full_like(v, jnp.nan) for n, v in leaves(E, D, F, "float32").items()}
    x = jax.random.normal(jax.random.PRNGKey(0), (T, D))
    chosen, w, _ = routing(T, 2, E, 16, 0)
    got, load = moe.expert_dispatch(x, chosen, w, lp, E, valid=jnp.zeros((T, 2), bool))
    assert (np.asarray(got) == 0).all() and int(np.asarray(load).sum()) == 0


@pytest.mark.parametrize("quant", [True, False], ids=["int8", "float32"])
def test_padding_tokens_pairs_are_no_pairs(quant):
    """``moe_block`` under the real-token mask: a padding token's choices add
    no row, count in no load, and read no expert; real tokens' routed output
    is what they get alone."""
    hf = {"model_type": "deepseek_v32", "hidden_size": 64, "num_hidden_layers": 2,
          "num_attention_heads": 2, "q_lora_rank": 16, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
          "qk_rope_head_dim": 8, "v_head_dim": 8, "vocab_size": 64, "intermediate_size": 32,
          "moe_intermediate_size": 32, "n_routed_experts": 4, "ep_size": 4, "ep_rank": 1,
          "n_shared_experts": 1, "first_k_dense_replace": 1, "n_group": 1, "topk_group": 1,
          "num_experts_per_tok": 2, "norm_topk_prob": True, "routed_scaling_factor": 2.5,
          "index_topk": 0, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
          "max_position_embeddings": 128}
    cfg = ModelConfig.from_hf_config(hf, name="pad").with_overrides(dtype="float32")
    draw = ds.init_params_quantized if quant else ds.init_params
    lp = {k: v[0] for k, v in draw(cfg, jax.random.PRNGKey(2))["moe"].items()}
    x = jax.random.normal(jax.random.PRNGKey(9), (16, 64), jnp.float32)
    real = jnp.arange(16) < 5
    y, load = ds.moe_block(x, lp, cfg, real)
    y_alone, load_alone = ds.moe_block(x[:5], lp, cfg)
    assert (np.asarray(load) == np.asarray(load_alone)).all()
    assert int(np.asarray(ds.moe_block(x, lp, cfg)[1]).sum()) > int(np.asarray(load).sum())
    assert (np.asarray(y[:5]) == np.asarray(y_alone)).all()


@pytest.mark.parametrize("kind", ["int8", "bfloat16"])
def test_a_tokens_output_does_not_depend_on_its_company(kind):
    """The same 16 tokens alone (a decode step) and scattered through a
    512-token step among other rows: bit-identical outputs.  int32
    accumulation is exact and a token's contributions are added in the order
    of its experts, one tile after another, whatever else the tiles hold."""
    E, Et, K, D, F = 6, 24, 4, 64, 32
    lp = leaves(E, D, F, kind, seed=4)
    dt = jnp.bfloat16
    x_all = jax.random.normal(jax.random.PRNGKey(1), (512, D)).astype(dt)
    chosen, w, valid = routing(512, K, E, Et, 7)
    at = jnp.asarray(np.random.RandomState(0).choice(512, 16, replace=False))
    run = jax.jit(lambda x, c, w, v: moe.expert_dispatch(x, c, w, lp, E, valid=v)[0])
    alone = run(x_all[at], chosen[at], w[at], valid[at])
    among = run(x_all, chosen, w, valid)[at]
    assert int(np.asarray(valid[at]).sum()) > 8  # the sixteen do land pairs
    assert (np.asarray(alone, np.float32) == np.asarray(among, np.float32)).all()


@pytest.mark.parametrize("quant", [False, True], ids=["float32", "int8"])
def test_moe_mlp_without_a_valid_mask_is_the_dense_sum(quant):
    """The llama family's caller: every expert held, ``valid`` None.  Against
    each token's experts computed one by one."""
    cfg = get_config("debug-tiny-moe").with_overrides(dtype="float32")
    E, K, D = cfg.num_experts, cfg.num_experts_per_token, cfg.hidden_size
    F = cfg.moe_intermediate_size or cfg.intermediate_size
    lp = leaves(E, D, F, "int8" if quant else "float32", seed=8)
    lp["router"] = jax.random.normal(jax.random.PRNGKey(3), (D, E)) * 0.5
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 9, D))
    got = np.asarray(moe.moe_mlp(x, lp, cfg)).reshape(18, D)
    xt = x.reshape(18, D)
    wts, chosen = jax.lax.top_k((xt @ lp["router"]).astype(jnp.float32), K)
    wts = jax.nn.softmax(wts, axis=-1)
    xe = jnp.broadcast_to(xt[None], (E, 18, D))
    g = jax.nn.silu(linear_tables(xe, lp, "moe_gate", jnp.float32))
    ye = np.asarray(linear_tables(g * linear_tables(xe, lp, "moe_up"), lp, "moe_down"))
    want = np.zeros((18, D), np.float32)
    for t in range(18):
        for k in range(K):
            want[t] += float(wts[t, k]) * ye[int(chosen[t, k]), t]
    assert np.abs(got - want).max() <= 1e-5 * max(1.0, np.abs(want).max())


def test_stacked_leaves_and_a_traced_layer_are_the_layers_own_leaves():
    """``layer``: the stacked leaf [L, E, ...] is the kernel's operand and a
    traced index (the prompt step's scan over layers) picks the layer."""
    L, E, D, F, T, K = 3, 4, 64, 32, 24, 2
    per_layer = [leaves(E, D, F, "int8", seed=20 + l) for l in range(L)]
    stack = {n: jnp.stack([lp[n] for lp in per_layer]) for n in per_layer[0]}
    x = jax.random.normal(jax.random.PRNGKey(6), (T, D))
    chosen, w, valid = routing(T, K, E, 8, 1)

    def body(_, l):
        return None, moe.expert_dispatch(x, chosen, w, stack, E, valid=valid, layer=l)[0]

    _, got = jax.lax.scan(body, None, jnp.arange(L))
    for l in range(L):
        want = moe.expert_dispatch(x, chosen, w, per_layer[l], E, valid=valid)[0]
        # (XLA fuses the float32 rescale of a scanned body its own way)
        assert np.abs(np.asarray(got[l]) - np.asarray(want)).max() <= 1e-6


@pytest.mark.parametrize("dt", ["bfloat16", "float32"])
def test_the_gated_rows_int8_form_is_quantize_rows_of_the_rounded_product(dt):
    """``_quantize_gated`` says each rounding to the activation type
    (``reduce_precision``) where a cast would leave it to XLA's fusion: the
    contract is ``quantize_rows(silu(gate).astype(dt) * up)``, row for row,
    jitted or not."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(8))
    gate = jax.random.normal(k1, (TILE_ROWS, 96)) * 3.0
    up = (jax.random.normal(k2, (TILE_ROWS, 96)) * 2.0).astype(dt)
    with jax.disable_jit():  # op by op: every cast is kept
        want_q, want_s = quantize_rows(jax.nn.silu(gate).astype(dt) * up)
    for f in (moe._quantize_gated, jax.jit(moe._quantize_gated)):
        got_q, got_s = f(gate, up)
        assert got_q.dtype == jnp.int8 and got_s.shape == (TILE_ROWS, 1)
        if dt == "bfloat16":  # float32 has nothing to round: silu's last bit is the jit's
            np.testing.assert_array_equal(np.asarray(got_q), np.asarray(want_q))
            np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))
        np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s), rtol=1e-6)
        assert np.abs(np.asarray(got_q, np.int32) - np.asarray(want_q, np.int32)).max() <= 1
