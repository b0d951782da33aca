"""The device-side join of the fused decode chain (docs/decode_pipeline.md).

A row whose LAST prompt chunk rides the unified step ``P_k`` joins the chain
behind that step without its first token ever visiting the host: the token
is in ``P_k``'s sampled output, on the device, when the next fused chunk is
enqueued microseconds later.  One small program moves it into the chain's
carry, between the two on the device's queue:

    C_k  ->  P_k  ->  join_rows  ->  C_k+1

Right behind a chain-break merge, before the re-seeded chain's first chunk,
the carry's place is taken by the host's seed (``tok0``, ``samp.steps``): the
same program over host operands.

``join_rows`` touches the ``(token, rng-step)`` half of the carry only.  The
``[S, V]`` penalty counts are neither an operand nor a result: a row with a
penalty takes the chain-break merge (``_decode_pipeline.merge_ready``), so the
counts of a joining row are never read and the buffer is never copied.
Nothing is donated: the prompt step's tokens are also on their way to the
host (the stream's first event, the stop check), and the carry the program
reads may be the one a chunk in flight was enqueued with.
"""

from __future__ import annotations

import jax.numpy as jnp


def join_rows(tok, steps, sampled, src, first_steps):
    """The chain's carry with the joining rows in it.

    ``tok``/``steps``: ``[S]`` carry of the chunk enqueued last (the token
    each row feeds next, its rng-stream position).  ``sampled``: ``[S]``
    tokens of the prompt step, one a row OF THAT STEP.  ``src[slot]``: the
    step's row whose token the chain's ``slot`` takes, -1 to keep the carry.
    ``first_steps[slot]``: the rng-stream position of the joining row's first
    fused step, ``num_output_tokens + 1``: what the host merge writes after
    the first token's accept, so that the row samples the stream it samples
    when served alone."""
    take = src >= 0
    return (
        jnp.where(take, sampled[jnp.maximum(src, 0)], tok),
        jnp.where(take, first_steps, steps),
    )
