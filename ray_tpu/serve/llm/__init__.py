"""LLM inference serving plane (reference: ray-project serve.llm +
vLLM's engine split, scaled to this runtime): a token-granular engine
with prefill/decode split over ``models/gpt2.py``, a preallocated paged
KV cache, and continuous in-flight batching, served through the normal
``serve.run()`` stack with streaming, queue-depth autoscaling, and load
shedding.

Public surface::

    from ray_tpu.serve import llm

    app = llm.build_app(llm.LLMConfig(model="tiny", max_batch_size=8))
    handle = serve.run(app, name="llm")
    for ev in handle.options(stream=True).generate.remote(
        {"prompt": "hello", "max_tokens": 16}
    ):
        print(ev["token"])

Grounding: PAPERS.md "Fine-Tuning and Serving Gemma 4 31B on Google
Cloud TPU"; docs/serving.md is the operator guide.
"""

from ray_tpu.serve.llm.config import LLMConfig
from ray_tpu.serve.llm.deployment import LLMServer, build_app
from ray_tpu.serve.llm.engine import LLMEngine
from ray_tpu.serve.llm.kv_cache import BlockManager

__all__ = [
    "LLMConfig",
    "LLMServer",
    "LLMEngine",
    "BlockManager",
    "build_app",
]
