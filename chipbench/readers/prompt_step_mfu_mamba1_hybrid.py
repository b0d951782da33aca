"""The prompt program of a hybrid model with Mamba-1 layers against the chip's
bf16 peak: the share of the WHOLE step's peak a full prompt chunk reaches.

Measured: the median device time of the module events matching
``module_pattern`` (the unified step; under a mix whose prompts are several
chunks long, and whose steps the scheduler packs to ``tokens_flag`` tokens,
the median step is a full chunk).  Needed: the operations of
chipbench.shapes_mamba1_hybrid.prompt_step_ops for ``serve[tokens_flag]``
tokens in one row behind half the mean prompt of the window's requests (what a
chunk attends to on average; 0.4% of the step's operations at 2048), over the
published bf16 peak.  A configuration without ``mamba_dt_rank`` has nothing to
read here.
"""

import re

from chipbench import shapes_mamba1_hybrid, stats


def read(ctx, module_pattern: str, tokens_flag: str = "prefill_chunk"):
    trace, model = ctx["trace"], ctx["model"]
    if trace is None or "mamba_dt_rank" not in model:
        return None
    rx = re.compile(module_pattern)
    ns = [dur for name, _, dur in trace.all_modules() if rx.search(name)]
    if not ns:
        return None
    step_s = stats.percentile(ns, 50, min_beyond=0) / 1e9
    prompts = [r["prompt_len"] for r in ctx["window"]["requests"] if r.get("ok")]
    cached = sum(prompts) / len(prompts) / 2 if prompts else 0.0
    ops = shapes_mamba1_hybrid.prompt_step_ops(model, ctx["serve"][tokens_flag], cached)
    return 100.0 * ops / ctx["peaks"]["bf16_flops"] / step_s
