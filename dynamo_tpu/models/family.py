"""One seam between the engine and the model families.

``family_of(config)`` returns what engine/engine.py needs of a model and
nothing else: how to draw or quantize its parameters, how to build its page
cache, its unified forward, and the few cache operations whose layout a family
owns.  The engine names no family; a new one is a module beside models/llama.py
and one entry here, chosen by ``ModelConfig.model_type``.

A new family with state of its own beside the K/V pages writes three things
and edits NO file under ``engine/``:

- a model file (its forward reads and writes the state through operands of
  ``RaggedBatch``, its ``create_cache`` takes the pools' sizes as keywords);
- one entry here, whose ``beside`` field builds the object that owns every
  host-side decision about that state (``engine/resume.py``: room for a row,
  where a prefix hit may end, what a row holds, a step's operands, what is kept
  at a resume point and what goes back);
- one kind, a subclass of ``engine.resume.Beside``, only if none fits: state
  in slots with snapshots (``SlotState``) and pages of a window pool
  (``WindowPages``) exist, over ``KvBlockManager.add_pool``'s ``UnitPool``
  (tests/test_beside_seam.py defines a third in thirty lines).
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

from .config import HYBRID_MODEL_TYPES, LATENT_MODEL_TYPES, ModelConfig
from .llama import RaggedBatch  # noqa: F401 — the engine's step input, family-neutral


class ModelFamily(NamedTuple):
    name: str
    init_params: Callable  # (config, key) -> params, activation dtype
    init_params_quantized: Callable  # (config, key) -> params, int8 leaves + scales
    quantize_params: Callable  # params -> params (no-op when already quantized)
    # params -> params with projections fused (single shard only), or None
    fuse_projections: Optional[Callable]
    create_cache: Callable  # (config, num_pages, page_size, dtype=) -> cache pytree
    # (params, config, rb, cache, **step options) -> (logits, cache, aux);
    # aux is None or a small int32 array that rides home with the sampled tokens.
    forward: Callable
    # PartitionSpec trees for a device mesh; None = single shard only.
    cache_pspec: Optional[Callable]
    # (cache, page_ids) -> pages and (cache, page_ids, pages) -> cache, for
    # the planes that move whole blocks (offload, transfer, migration); None =
    # this family's blocks cannot be moved yet and those planes are refused.
    gather_pages: Optional[Callable]
    inject_pages: Optional[Callable]
    forward_sp_prefill: Optional[Callable]
    # Names and bytes a token a layer of each page array, for /metrics.
    cache_kinds: Callable  # (config, cache) -> {name: bytes}
    # (config, engine config) -> None; raises ValueError naming the flag for
    # every engine option this family does not support.
    check: Callable
    # Host-side accounts a family keeps of its own mechanisms, or None:
    # (config, dispatch kind, first positions, token counts of the rows) at
    # every dispatch, from lengths the scheduler holds; and (aux as numpy)
    # for what ``forward`` sent home.
    count_dispatch: Optional[Callable] = None
    count_aux: Optional[Callable] = None
    # () -> those accounts as a dict, for ``dispatch_summary()``.
    counts: Optional[Callable] = None
    # (config) -> the head width the attention kernels see, where it is not
    # ``config.head_dim`` (heads packed into one lane tile); None = head_dim.
    attn_lanes: Optional[Callable] = None
    # (config, engine config) -> the ``engine.resume.Beside`` that owns what
    # this family keeps beside the K/V pages, which is also where a sequence
    # can be resumed and so where a prefix hit may end; None = K/V alone,
    # resumed at every token.
    beside: Optional[Callable] = None


def _llama(config: ModelConfig) -> ModelFamily:
    from . import llama, quant

    def forward(params, config, rb, cache, **kw):
        logits, cache = llama.forward_ragged(params, config, rb, cache, **kw)
        return logits, cache, None

    def gather(cache, page_ids):
        # OOB padding ids clamp (their slices are ignored at store time).
        return cache.pages[:, page_ids]

    def inject(cache, page_ids, new_pages):
        # Same quantization as the ragged write path (shared helper):
        # injected blocks must never diverge numerically from
        # normal-prefill blocks under the same hashes.  Padding ids are out
        # of range and dropped.
        from ..ops.ragged_attention import quantize_for_cache

        pages = cache.pages.at[:, page_ids].set(
            quantize_for_cache(new_pages, cache.pages.dtype), mode="drop"
        )
        return llama.PagedKVCache(pages)

    def cache_pspec():
        from ..parallel.mesh import pages_pspec

        return llama.PagedKVCache(pages_pspec())

    def kinds(config, cache):
        return {"kv": 2 * config.num_kv_heads * config.head_dim * cache.pages.dtype.itemsize}

    return ModelFamily(
        name="llama",
        init_params=llama.init_params,
        init_params_quantized=quant.init_params_quantized,
        quantize_params=quant.quantize_params,
        fuse_projections=quant.fuse_projections,
        create_cache=llama.PagedKVCache.create,
        forward=forward,
        cache_pspec=cache_pspec,
        gather_pages=gather,
        inject_pages=inject,
        forward_sp_prefill=llama.forward_sp_prefill,
        cache_kinds=kinds,
        check=lambda config, cfg: None,
    )


def _latent(config: ModelConfig) -> ModelFamily:
    """models/deepseek_v32.py: with the selector (``index_topk`` > 0) or
    without it, by the configuration."""
    import jax.numpy as jnp

    from ..llm.metrics import sparse_model_metrics
    from ..ops import sparse_mla
    from . import deepseek_v32 as ds

    def kinds(config, cache):
        return {name: pages.shape[-1] * pages.dtype.itemsize
                for name, pages in cache._asdict().items() if pages is not None}

    def count_dispatch(config, kind, starts, ns, step_tokens=None):
        if config.index_topk:
            form = sparse_mla.prefill_form(step_tokens) if kind == "unified" else None
            sparse_model_metrics.add_dsa(kind, config.index_topk, starts, ns, prefill_form=form)
        else:
            sparse_model_metrics.add_mla(kind, starts, ns)

    def check(config: ModelConfig, cfg: Any) -> None:
        """The engine options this family cannot serve yet, each refused by
        its flag: a latent block is not K plus V, so nothing that sizes or
        moves blocks as ``pages`` may run on it silently."""
        bad = []
        if cfg.tp * cfg.dp * cfg.ep * cfg.sp != 1:
            bad.append("--tp/--dp/--ep/--sp > 1 (no PartitionSpecs for the latent cache; the "
                       "configuration's ep_size/ep_rank say which experts this chip holds)")
        if jnp.dtype(cfg.cache_dtype).itemsize == 1:
            bad.append("--kv-cache-dtype int8/fp8 (latent and indexer pages are bfloat16 or wider)")
        _refuse(config, cfg, bad + _unmovable_blocks(cfg))

    return ModelFamily(
        name="latent",
        init_params=ds.init_params,
        init_params_quantized=ds.init_params_quantized,
        quantize_params=ds.quantize_params,
        fuse_projections=None,
        create_cache=ds.LatentKVCache.create,
        forward=ds.forward_ragged,
        cache_pspec=None,
        gather_pages=None,
        inject_pages=None,
        forward_sp_prefill=None,
        cache_kinds=kinds,
        check=check,
        count_dispatch=count_dispatch,
        count_aux=sparse_model_metrics.add_moe,
        counts=sparse_model_metrics.summary,
    )


def _refuse(config: ModelConfig, cfg: Any, bad: list) -> None:
    if bad:
        raise ValueError(
            f"model_type {config.model_type} ({config.name}) does not support: "
            + "; ".join(bad))


def _unmovable_blocks(cfg: Any) -> list:
    """The engine options no family without ``gather_pages`` / ``inject_pages``
    and PartitionSpecs can serve, each by its flag."""
    bad = []
    if cfg.host_cache_bytes or cfg.disk_cache_bytes or cfg.object_store_bytes:
        bad.append("--host-cache-mb/--disk-cache-mb/--object-store-mb (the tiers store "
                   "K-plus-V blocks)")
    if cfg.spec_decode.enable:
        bad.append("--spec-decode (verification rows of several tokens are not wired)")
    if cfg.lora.enable:
        bad.append("--lora (no adapter banks for this family's projections)")
    return bad


def _hybrid(config: ModelConfig) -> ModelFamily:
    """models/lfm2.py: gated short convolutions whose state lives in the page
    cache beside the attention layers' K/V (resumed at a block's end), or
    Mamba-2, KDA or Mamba-1 layers whose state lives in slots (resumed at a snapshot);
    the attention layers' pages K/V or, for a model with MLA, latent entries."""
    from ..llm.metrics import sparse_model_metrics, swa_metrics
    from . import lfm2

    with_kda = lfm2.kda_layers(config) > 0
    with_mamba1 = lfm2.mamba1_layers(config) > 0
    slotted = lfm2.mamba_layers(config) > 0 or with_kda or with_mamba1
    windowed = lfm2.window_layers(config) > 0
    latent_pages = lfm2.latent_attention(config)

    def kinds(config, cache):
        """K/V bytes a token an attention layer; the convolution state's
        bytes a PAGE a convolution layer (it does not grow inside a page); a
        Mamba-2, KDA or Mamba-1 layer's state and tail bytes a SLOT; a latent entry's
        bytes a token a latent layer."""
        if latent_pages:
            out = {"latent": cache.pages.shape[-1] * cache.pages.dtype.itemsize}
        else:
            out = {"kv": 2 * config.num_kv_heads * config.head_dim * cache.pages.dtype.itemsize}
        if cache.conv is not None:
            out["conv_page"] = cache.conv.shape[2] * cache.conv.shape[3] * cache.conv.dtype.itemsize
        if cache.ssm is not None:
            out["kda_slot" if with_kda else "mamba1_slot" if with_mamba1 else "ssm_slot"] = (
                cache.ssm[0, 0].size * cache.ssm.dtype.itemsize)
            out["conv_tail"] = cache.tail[0, :, 0].size * cache.tail.dtype.itemsize
        if cache.window is not None:  # a token a WINDOW layer, for the last positions only
            out["kv_window"] = out["kv"]
        return out

    def check(config: ModelConfig, cfg: Any) -> None:
        bad = []
        if cfg.tp * cfg.dp * cfg.ep * cfg.sp != 1:
            bad.append("--tp/--dp/--ep/--sp > 1 (no PartitionSpecs for the state pages or the "
                       "window pool; the configuration's ep_size/ep_rank say which experts this "
                       "chip holds)")
        import jax.numpy as jnp

        if latent_pages and jnp.dtype(cfg.cache_dtype).itemsize == 1:
            bad.append("--kv-cache-dtype int8/fp8 (latent pages are bfloat16 or wider)")
        if windowed and cfg.prefill_chunk % cfg.block_size:
            bad.append("--prefill-chunk that is no multiple of --block-size (the window pages "
                       "before a resume point are kept by whole blocks)")
        _refuse(config, cfg, bad + _unmovable_blocks(cfg))

    def beside(config: ModelConfig, cfg: Any):
        """Scan state in slots, resumed at a snapshot; else window layers'
        pages in a second pool, resumed behind kept pages; else convolution
        state held by PAGE, resumed at a block's end."""
        from ..engine.resume import Beside, SlotState, WindowPages

        if slotted:
            snapshots = lfm2.snapshot_slots(cfg.num_blocks, cfg.block_size, cfg.prefill_chunk)
            return SlotState(cfg.max_batch, snapshots, stride=cfg.prefill_chunk)
        if windowed:
            return WindowPages(*window_pool(config, cfg), stride=cfg.prefill_chunk)
        return Beside(whole_blocks=True)

    def window_pool(config: ModelConfig, cfg: Any):
        """The window pool's ONE rule (docs/k_exaone.md): the pages before
        every resume stride the K/V pages can hold, and never fewer than twice
        what ``max_batch`` running rows can hold at once."""
        row = lfm2.window_row_pages(config, cfg.block_size, max(
            cfg.prefill_chunk, (cfg.pipeline_depth + 1) * cfg.decode_steps))
        kept = lfm2.window_blocks(config, cfg.block_size) * (
            cfg.num_blocks * cfg.block_size // cfg.prefill_chunk)
        return max(kept, 2 * cfg.max_batch * row), config.sliding_window, row

    def count_window(config, kind, starts, ns, step_tokens=None):
        swa_metrics.add_queries(config.sliding_window, starts, ns)

    def count_kda(config, kind, starts, ns, step_tokens=None):
        """The KDA layers' tokens by the form they went through, and the
        latent layers' queries (whole-context attention: the ``mla_*``
        account of the latent family)."""
        sparse_model_metrics.add_kda("scan" if kind == "unified" else "step", ns)
        sparse_model_metrics.add_mla(kind, starts, ns)

    def count_mamba1(config, kind, starts, ns, step_tokens=None):
        sparse_model_metrics.add_mamba1("scan" if kind == "unified" else "step", ns)

    return ModelFamily(
        name="hybrid",
        init_params=lfm2.init_params,
        init_params_quantized=lfm2.init_params_quantized,
        quantize_params=lfm2.quantize_params,
        fuse_projections=None,
        create_cache=lfm2.HybridCache.create,
        forward=lfm2.forward_ragged,
        cache_pspec=None,
        gather_pages=None,
        inject_pages=None,
        forward_sp_prefill=None,
        cache_kinds=kinds,
        check=check,
        # The slots' account is the block manager's (admissions, snapshots).
        count_dispatch=count_kda if with_kda else count_mamba1 if with_mamba1 else None if slotted else count_window if windowed else (
            lambda config, kind, starts, ns, step_tokens=None: (
                sparse_model_metrics.add_conv(kind, starts, ns))),
        count_aux=sparse_model_metrics.add_moe,
        counts=sparse_model_metrics.summary,
        attn_lanes=lfm2.attn_lanes,
        beside=beside,
    )


_FAMILIES = {"llama": _llama, **{t: _latent for t in LATENT_MODEL_TYPES},
             **{t: _hybrid for t in HYBRID_MODEL_TYPES}}


def family_of(config: ModelConfig) -> ModelFamily:
    try:
        return _FAMILIES[config.model_type](config)
    except KeyError:
        raise ValueError(
            f"model_type {config.model_type!r} has no family; known: {sorted(_FAMILIES)}"
        ) from None
