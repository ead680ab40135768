"""ray_tpu.models — JAX/Flax model families for Train/RLlib/Serve.

Flagship: GPT-2 (ray_tpu.models.gpt2) — the north-star pretraining target,
trained and served.  Served only: OLMoE (olmoe, sparse experts over
ops/moe.py).  Also: Llama family (RoPE/GQA/SwiGLU), pipeline-parallel
GPT-2 (gpt2_pp), ViT, MLP (MNIST).
"""

__all__ = ["gpt2", "gpt2_pp", "llama", "mlp", "olmoe", "vit"]


def __getattr__(name):
    if name in __all__:
        import importlib

        return importlib.import_module(f"ray_tpu.models.{name}")
    raise AttributeError(name)
