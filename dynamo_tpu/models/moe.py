"""Mixture-of-experts FFN: dropless, rows grouped by expert.

The reference only *configures* expert parallelism for TRT-LLM
(examples/tensorrt_llm/configs/llm_api_config.yaml:24-26); here MoE runs
natively.  A step's (token, expert) pairs that land on experts held here are
sorted by expert into row tiles (each expert's group padded to whole tiles),
the tiles go through one grouped matmul a projection
(ops/grouped_matmul.py: a tile is multiplied by ITS expert's weights, and an
expert without a row is not read), and the results are added back to their
tokens tile by tile.  What is gathered, multiplied and added follows the
count of landed pairs; no pair is ever dropped, whatever the routing.
"""

from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp

# ``quant_matmul.quantize_rows`` is read at trace time: chip_smoke.py's
# coarser-activations control replaces it for a pass.
from ..ops import quant_matmul
from ..ops.grouped_matmul import TILE_ROWS, moe_grouped_matmul
from ..ops.ragged_attention import pallas_interpret
from .config import ModelConfig

CHUNK_TILES = 16  # row tiles a pass of ``expert_dispatch``'s loop: 512 rows


def init_moe_params(config: ModelConfig, key: jax.Array, dt) -> Dict[str, jnp.ndarray]:
    L, D = config.num_layers, config.hidden_size
    E, F = config.num_experts, config.moe_intermediate_size or config.intermediate_size
    keys = jax.random.split(key, 4)

    def norm(k, *shape):
        return (jax.random.normal(k, shape, jnp.float32) * 0.02).astype(dt)

    return {
        "router": norm(keys[0], L, D, E),
        "moe_gate": norm(keys[1], L, E, D, F),
        "moe_up": norm(keys[2], L, E, D, F),
        "moe_down": norm(keys[3], L, E, F, D),
    }


def _quantize_gated(gate: jnp.ndarray, up: jnp.ndarray, across=()):
    """``quantize_rows(silu(gate).astype(dt) * up)`` for ``up`` of the
    activation type ``dt``, with each rounding to ``dt`` SAID
    (``reduce_precision``) and not left to a cast: XLA keeps or drops such a
    cast by what it fuses it with (beside a matmul it rounded the product but
    read the row's scale off the unrounded one; among elementwise ops alone it
    dropped the rounding), so a pair's int8 row, and with it the token served,
    would follow the program it is computed in (chip run, PR 33)."""
    dt = jnp.finfo(up.dtype)

    def rounded(v):
        return jax.lax.reduce_precision(v, dt.nexp, dt.nmant)

    h = rounded(rounded(jax.nn.silu(gate)) * up.astype(jnp.float32))
    return quant_matmul.quantize_rows(h, across) if across else quant_matmul.quantize_rows(h)


def moe_mlp(
    x: jnp.ndarray,  # [B, Sq, D]
    lp: Dict[str, jnp.ndarray],  # this layer's params (leading L stripped)
    config: ModelConfig,
    mesh=None,  # the caller's device mesh, as ``forward_ragged`` has it
) -> jnp.ndarray:
    """Softmax over the top-k experts' logits, all experts held: dropless
    (inference must not drop tokens, and dropless also keeps prefill/decode
    bit-consistent)."""
    B, Sq, D = x.shape
    T = B * Sq
    E, K = config.num_experts, config.num_experts_per_token

    xt = x.reshape(T, D)
    router_logits = (xt @ lp["router"]).astype(jnp.float32)  # [T, E]
    weights, chosen = jax.lax.top_k(router_logits, K)  # [T, K]
    weights = jax.nn.softmax(weights, axis=-1)  # renormalise over chosen

    yt, _ = expert_dispatch(xt, chosen, weights, lp, E, mesh=mesh)
    return yt.reshape(B, Sq, D)


def expert_dispatch(
    xt: jnp.ndarray,  # [T, D]
    chosen: jnp.ndarray,  # [T, K] expert id of each assignment, LOCAL to lp's E experts
    weights: jnp.ndarray,  # [T, K] f32 combine weights
    lp: Dict[str, jnp.ndarray],  # moe_gate / moe_up / moe_down [E, ...] (+ scales)
    E: int,
    valid: jnp.ndarray | None = None,  # [T, K] False = no pair: elsewhere's expert, a padding token
    layer=None,  # the leaves are STACKED [L, E, ...] and this (traced) index picks the layer
    mesh=None,  # the device mesh the leaves are sharded over (parallel/mesh.py)
):
    """(sum_k weights[t, k] * FFN_{chosen[t, k]}(xt[t]) [T, D], pairs landed
    on each expert [E] int32), over the pairs ``valid`` keeps.  A token's
    experts must be distinct (``top_k`` gives that).

    Rows go through the experts in tiles of ``TILE_ROWS`` that share an
    expert (ops/grouped_matmul.py: gate with up, then down), and only the
    experts with a pair are read.  Dropless whatever the routing, and the
    work follows the pairs that landed (``_dispatch``).  A row's arithmetic
    is the W8A8 contract, rows quantised once a token, and a token's
    contributions are added in the order of its experts' ids: its result does
    not depend on the step it is in, nor on what else shares the step.

    Under ``mesh`` (the llama family: parallel/mesh.py shards the experts
    over ``ep`` and their intermediate width over ``tp``) each device runs the
    same dispatch over the experts it holds: ``_dispatch_sharded``."""
    pair_e = (chosen if valid is None else jnp.where(valid, chosen, E)).astype(jnp.int32)  # E: no pair
    sizes = jnp.sum(pair_e.reshape(-1, 1) == jnp.arange(E), axis=0, dtype=jnp.int32)  # [E]
    tails = ("", "_scale") if lp.get("moe_gate_scale") is not None else ("",)
    # The kernel's leaves are [L, E, ...]: one layer's get L = 1.
    leaves = {n + t: lp[n + t] if layer is not None else lp[n + t][None]
              for n in ("moe_gate", "moe_up", "moe_down") for t in tails}
    li = jnp.zeros((1,), jnp.int32) if layer is None else jnp.asarray(layer, jnp.int32).reshape(1)
    args = (xt, pair_e, weights.astype(jnp.float32), li, leaves)
    y = _dispatch(*args) if mesh is None or mesh.size == 1 else _dispatch_sharded(mesh, *args)
    return y.astype(xt.dtype), sizes


def _dispatch(xt, pair_e, pair_w, li, leaves, f_axes: tuple = ()):
    """``expert_dispatch`` over the experts of ``leaves`` [L, E, ...] alone,
    layer ``li[0]``: float32 [T, D].  ``pair_e`` [T, K] names them 0..E-1, and
    E where a pair is none of theirs.

    - a step of at most ``TILE_ROWS`` rows (a decode step): every expert's
      rows fit one tile, so the step's rows AS THEY STAND are the tile of each
      expert that has a pair, and a row the expert was not chosen for gets
      weight 0.  Nothing is sorted or gathered.
    - a longer step: the pairs sorted by expert fill the tiles, an expert's
      group padded to whole tiles; ``CHUNK_TILES`` tiles at a time are
      gathered, multiplied and added to their tokens, by a loop whose trip
      count is the count of live tiles, so a 512-token step of which 128
      pairs land pays for those and a tile's padding, not for T * K rows.

    ``f_axes``: the mesh axes over which each shard's leaves hold a slice of
    the experts' intermediate width (``tp``).  A row of ``silu(gate) * up``
    is then quantised under the scale of the WHOLE row (the largest over the
    slices), and what comes back is a partial sum that the caller adds
    across the slices, left in float32 until then."""
    T, D = xt.shape
    K = pair_e.shape[1]
    E = leaves["moe_gate"].shape[1]
    P, tm, dt = T * K, TILE_ROWS, xt.dtype
    flat_e, flat_w = pair_e.reshape(P), pair_w.reshape(P)
    sizes = jnp.sum(flat_e[:, None] == jnp.arange(E), axis=0, dtype=jnp.int32)  # [E]
    quantized = "moe_gate_scale" in leaves
    matmul = functools.partial(moe_grouped_matmul, interpret=pallas_interpret())

    def ws(*names):
        return ([leaves[n] for n in names],
                [leaves[n + "_scale"] for n in names] if quantized else None)

    def ffn(rows, scales, tile_expert, n):
        """The tiles' rows through their experts: [tiles * tm, D]."""
        gate, up = matmul(rows, scales, *ws("moe_gate", "moe_up"), tile_expert, n, li[0],
                          out_dtypes=(jnp.float32, dt))
        h, hs = (_quantize_gated(gate, up, f_axes) if quantized
                 else (jax.nn.silu(gate).astype(dt) * up, None))
        return matmul(h, hs, *ws("moe_down"), tile_expert, n, li[0],
                      out_dtypes=(jnp.float32 if f_axes else dt,))[0]

    if T <= tm:
        hit = sizes > 0
        n_tiles = jnp.sum(hit, dtype=jnp.int32)
        # The experts with a pair, by id; a tile past the last names the last
        # one's expert: its weight block is the one already there.
        tile_expert = jnp.argsort(~hit, stable=True)[
            jnp.minimum(jnp.arange(E), jnp.maximum(n_tiles - 1, 0))].astype(jnp.int32)
        w_et = jnp.sum(jnp.where(flat_e.reshape(1, T, K) == jnp.arange(E)[:, None, None],
                                 flat_w.reshape(1, T, K), 0.0), axis=2)  # [E, T]
        w_tile = jnp.where((jnp.arange(E) < n_tiles)[:, None], w_et[tile_expert], 0.0)
        x_tile = jnp.pad(xt, ((0, tm - T), (0, 0)))
        rows = quant_matmul.quantize_rows(x_tile) if quantized else (x_tile, None)
        ye = ffn(*rows, tile_expert, n_tiles)
        ye = ye.reshape(E, tm, D)[:, :T].astype(jnp.float32)
        y = jnp.zeros((T, D), jnp.float32)
        for i in range(E):  # in this order: float32 sums do not commute with their grouping
            w_i = w_tile[i][:, None]
            y = y + jnp.where(w_i != 0, ye[i] * w_i, 0.0)
        return y

    order = jnp.argsort(flat_e, stable=True)  # pairs by expert, then by (t, k); no-pairs last
    group_start = jnp.cumsum(sizes) - sizes
    tiles_e = (sizes + tm - 1) // tm
    tile_end = jnp.cumsum(tiles_e)
    n_tiles = tile_end[-1]
    # Static bounds: an expert has at most T rows, the pairs at most P.
    max_tiles = min(E * -(-T // tm), P // tm + E)
    ct = min(max_tiles, CHUNK_TILES)
    n_chunks = -(-max_tiles // ct)
    tile = jnp.arange(n_chunks * ct)
    tile_expert = jnp.minimum(jnp.sum(
        tile_end[None, :] <= jnp.minimum(tile, jnp.maximum(n_tiles - 1, 0))[:, None],
        axis=1, dtype=jnp.int32), E - 1)
    r = jnp.arange(n_chunks * ct * tm)
    e_r = tile_expert[r // tm]
    j = r - (tile_end - tiles_e)[e_r] * tm  # the row's place in its expert's group
    live = (r // tm < n_tiles) & (j < sizes[e_r])
    pair = order[jnp.clip(group_start[e_r] + j, 0, P - 1)]
    # A padding row's token is out of range, and no two of a tile's are equal.
    row_token = jnp.where(live, pair // K, T + r % tm)
    row_w = jnp.where(live, flat_w[pair], 0.0)
    x_pad = jnp.concatenate([xt, jnp.zeros((1, D), dt)], axis=0)  # row T: a padding row's
    x_rows, x_scales = quant_matmul.quantize_rows(x_pad) if quantized else (x_pad, None)

    def chunk(c, y):
        """Tiles [c * ct, (c + 1) * ct): gather, multiply, add tile by tile
        (a token appears once in a tile: one expert's rows)."""
        tok = jax.lax.dynamic_slice(row_token, (c * ct * tm,), (ct * tm,))
        w_r = jax.lax.dynamic_slice(row_w, (c * ct * tm,), (ct * tm,))
        te = jax.lax.dynamic_slice(tile_expert, (c * ct,), (ct,))
        n = jnp.clip(n_tiles - c * ct, 0, ct)
        src = jnp.minimum(tok, T)
        ye = ffn(x_rows[src], x_scales[src] if quantized else None, te, n)
        yw = ye.astype(jnp.float32) * w_r[:, None]

        def add_tile(i, y):
            return y.at[jax.lax.dynamic_slice(tok, (i * tm,), (tm,))].add(
                jax.lax.dynamic_slice(yw, (i * tm, 0), (tm, D)), mode="drop", unique_indices=True)

        return jax.lax.fori_loop(0, n, add_tile, y)

    return jax.lax.fori_loop(0, (n_tiles + ct - 1) // ct, chunk, jnp.zeros((T, D), jnp.float32))


_LEAF_DIMS = {"moe_gate": "l e d f", "moe_up": "l e d f", "moe_down": "l e f d",
              "moe_gate_scale": "l e f", "moe_up_scale": "l e f", "moe_down_scale": "l e d"}


def _dispatch_sharded(mesh, xt, pair_e, pair_w, li, leaves):
    """``_dispatch`` under a mesh whose ``ep`` axis shards the leaves' experts
    and whose ``tp`` axis their intermediate width (parallel/mesh.py).  Left
    to GSPMD a Pallas call is opaque: every shard of the expert leaves would
    be gathered to every device before it.  Here each device runs the
    dispatch over the experts and the slice of F that it holds (a pair of an
    expert held elsewhere is no pair here), for its share of the tokens where
    the other axes divide them, and the partial sums are added over ``ep``
    and ``tp``.  The only collective inside is the row scale's ``pmax`` over
    ``tp``, whose peers hold the same experts and so walk the same tiles."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    over = {a: n for a, n in mesh.shape.items() if n > 1}
    ep, tp = (("ep",) if "ep" in over else ()), (("tp",) if "tp" in over else ())
    tok = tuple(a for a in over if a not in ("ep", "tp"))
    if xt.shape[0] % math.prod(over[a] for a in tok):
        tok = ()
    by_dim = {"l": None, "e": ep or None, "d": None, "f": tp or None}
    rows = P(tok or None, None)

    def shard(xt, pair_e, pair_w, li, leaves):
        E = leaves["moe_gate"].shape[1]
        local = pair_e - (jax.lax.axis_index("ep") * E if ep else 0)
        local = jnp.where((local >= 0) & (local < E), local, E)
        y = _dispatch(xt, local, pair_w, li, leaves, f_axes=tp)
        return jax.lax.psum(y, ep + tp) if ep + tp else y

    return shard_map(
        shard, mesh=mesh,
        in_specs=(rows, rows, rows, P(),
                  {n: P(*(by_dim[d] for d in _LEAF_DIMS[n].split())) for n in leaves}),
        out_specs=rows, check_vma=False,
    )(xt, pair_e, pair_w, li, leaves)
