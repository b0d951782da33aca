"""The latent family (models/deepseek_v32.py) after PR 53 moved its attention
block out of ``forward_ragged``'s closure and gave it two switches (one q
projection where ``q_lora_rank`` is 0; no rotation where ``mla_rope`` is
False) for the hybrid family's latent layers (models/lfm2.py, ``kimi_linear``):
``kimi_k2`` and ``deepseek_v32`` must build the leaves and lower to the program
text they did before.
"""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.models import deepseek_v32 as latent
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.family import RaggedBatch, family_of

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# sha256 of the StableHLO text (no locations) of both programs at the rehearsal
# sizes, read on the PARENT of PR 53 (``_scratch/lower53.py``'s recipe: CHANGES.md);
# the block's two new switches (no q_lora, no rotation) must cost these models nothing.
LATENT_PROGRAMS = {
    "deepseek-v3.2-exp-6l-ep16": ("1afe8b8080aacf3d", "b42bb130cfeb17d1"),
    "kimi-k2-6l-ep32": ("57550886b4991e9e", "a23fbf59eff9b8d1"),
}


@pytest.mark.parametrize("name", sorted(LATENT_PROGRAMS))
def test_the_latent_family_builds_the_leaves_and_the_programs_it_built_before(name):
    with open(os.path.join(ROOT, "chipbench/configs", name + ".json")) as f:
        body = json.load(f)
    serve = dict(body["serve"], **body["rehearsal"].get("serve", {}))
    mc = ModelConfig.from_hf_config(body["rehearsal"]["model"], name=name + "-lower")
    mc = mc.with_overrides(dtype=serve["dtype"])
    assert mc.mla_rope and mc.q_lora_rank > 0
    fam = family_of(mc)
    floats = jax.eval_shape(lambda k: fam.init_params(mc, k), jax.random.PRNGKey(0))
    assert {"wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "w_uk", "w_uv", "wo"} <= set(
        floats["layers"]) and "wq" not in floats["layers"]
    shapes = latent.leaf_shapes(mc)
    assert jax.tree_util.tree_map(lambda a: a.shape, floats) == {
        **{g: dict(v) for g, v in shapes.items() if g != "top"}, **shapes["top"]}
    assert serve["weight_quant"] == "int8"
    params = jax.eval_shape(lambda k: fam.init_params_quantized(mc, k), jax.random.PRNGKey(0))
    bs = serve.get("block_size", 16)
    cache = jax.eval_shape(lambda: fam.create_cache(
        mc, serve["num_blocks"], bs, dtype=jnp.dtype(serve["kv_cache_dtype"])))
    n, pp = serve["max_batch"], serve["max_model_len"] // bs
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    for decode, want in zip((False, True), LATENT_PROGRAMS[name]):
        t = n if decode else serve["prefill_chunk"]
        rb = RaggedBatch(i32(t), i32(t), i32(t), i32(n), i32(n, pp), i32(n + 1), i32(1))
        text = jax.jit(lambda p, c, rb: fam.forward(p, mc, rb, c, decode=decode),  # noqa: B023
                       donate_argnums=1).lower(params, cache, rb).as_text()
        assert hashlib.sha256(text.encode()).hexdigest()[:16] == want, (name, decode)
