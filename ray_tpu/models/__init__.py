"""ray_tpu.models — JAX/Flax model families for Train/RLlib/Serve.

Flagship: GPT-2 (ray_tpu.models.gpt2) — the north-star pretraining target,
trained and served.  Served only: the other rows of
``serve/llm/config.py:MODEL_FAMILIES`` (olmoe, minicpm_sala, mistral4,
nemotron_h, granite_hybrid, mellum, jamba, zaya, glm_moe_dsa,
kimi_linear), built on
``common`` and ``layers``, which are no family's.  Also: MLP (MNIST).
"""

__all__ = ["gpt2", "mlp", "olmoe"]


def __getattr__(name):
    if name in __all__:
        import importlib

        return importlib.import_module(f"ray_tpu.models.{name}")
    raise AttributeError(name)
