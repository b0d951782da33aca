"""dynamo_tpu — a TPU-native distributed LLM inference serving framework.

A from-scratch re-design of the capabilities of NVIDIA Dynamo (reference:
basetenlabs/dynamo @ 2025-05-23) for TPU hardware:

- ``runtime``  — distributed runtime: AsyncEngine/Context, pipeline graph,
  discovery (lease-based KV with prefix watches), request plane, TCP response
  streaming, event plane.  (reference: lib/runtime/)
- ``llm``      — serving library: OpenAI protocols, preprocessor, backend
  (detokenize/stop), KV-aware router, model deployment cards.
  (reference: lib/llm/)
- ``engine``   — the TPU-native JAX engine: continuous batching with paged KV
  cache in HBM, jitted prefill/decode, sampling.  (replaces the reference's
  vLLM/sglang engine adapters with a native engine.)
- ``models``   — JAX model implementations (llama family, MoE).
- ``ops``      — Pallas/XLA kernels (paged attention, block copy).
- ``parallel`` — mesh construction, shardings, collectives-based parallelism.
- ``sdk``      — service-graph SDK (@service/@endpoint/depends) + supervisor.
"""

import os

__version__ = "0.1.0"

# The checkout this package runs from: the ONE anchor for state the program
# keeps beside its code (native/build, .xla_cache, decode_tune.json) —
# nothing is read or written under ``~``.
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
