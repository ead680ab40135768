"""LLM serving configuration (reference: vLLM EngineArgs / ray.serve.llm
LLMConfig, scaled down to the knobs this engine actually has)."""

from __future__ import annotations

import dataclasses
import importlib
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

# The model families the engine serves, one row each: the module that
# gives the engine its forwards (docs/serving.md "Model families"), that
# module's config class, and the class's presets ``LLMConfig.model`` may
# name.  A family is imported only when one of its presets is chosen.
MODEL_FAMILIES = (
    ("ray_tpu.models.gpt2", "GPT2Config", ("tiny", "small", "medium", "large")),
    ("ray_tpu.models.olmoe", "OlmoeConfig", ("olmoe_tiny", "olmoe_1b_7b", "olmoe_1b_7b_12l")),
    ("ray_tpu.models.minicpm_sala", "MiniCPMSalaConfig",
     ("minicpm_sala_tiny", "minicpm_sala", "minicpm_sala_16l")),
    ("ray_tpu.models.mistral4", "Mistral4Config",
     ("mistral_small_4_tiny", "mistral_small_4", "mistral_small_4_6l_ep4")),
    ("ray_tpu.models.nemotron_h", "NemotronHConfig",
     ("nemotron_3_nano_tiny", "nemotron_3_nano", "nemotron_3_nano_26l_ep4")),
    ("ray_tpu.models.granite_hybrid", "GraniteHybridConfig",
     ("granite_4_0_h_small_tiny", "granite_4_0_h_small", "granite_4_0_h_small_10l_ep2")),
    ("ray_tpu.models.mellum", "MellumConfig", ("mellum2_tiny", "mellum2_12b_a2_5b", "mellum2_12b_a2_5b_12l")),
    ("ray_tpu.models.jamba", "JambaConfig", ("jamba2_tiny", "jamba2_3b")),
    ("ray_tpu.models.zaya", "ZayaConfig", ("zaya1_tiny", "zaya1_8b", "zaya1_8b_20l")),
    ("ray_tpu.models.glm_moe_dsa", "GlmMoeDsaConfig", ("glm5_tiny", "glm5", "glm5_6l_ep16")),
    ("ray_tpu.models.kimi_linear", "KimiLinearConfig",
     ("kimi_linear_tiny", "kimi_linear_48b_a3b", "kimi_linear_48b_a3b_8l_ep8")),
)
# every preset ``LLMConfig.model`` may name, family by family
PRESETS = " | ".join(name for *_, presets in MODEL_FAMILIES for name in presets)


def model_family(cfg):
    """The family module of a model config: the row of MODEL_FAMILIES
    that names its class.  A family gives the engine ``init_params(cfg,
    rng)``, ``serving_params(params, cfg)`` (that tree as a server holds
    it: every leaf in the dtype the two forwards compute with),
    ``cache_spec(cfg, block_size)`` (what it caches for a sequence:
    ``models/common.py:CacheSpec``) and two forwards.  Where the cache
    is K and V of every layer and no more: ``prefill_forward(params,
    cfg, tokens, last_index)`` and ``decode_forward_paged(params, cfg,
    tok, k_pages, v_pages, block_tables, lengths, block_size)``, both
    returning (logits, k, v).  Where it states more (page extras, lane
    state, prompts by chunks: ``CacheSpec.reads_cache``):
    ``prefill_chunk(params, cfg, cache, tokens, start, last_index,
    table, lane, block_size)`` and ``decode_forward_cached(params, cfg,
    cache, tok, block_tables, lengths, block_size)``, which read the
    cache's arrays by name and return (logits, k, v, {extra: (rows,
    where)}, {state: value}), v None where the family states no V pool;
    the engine writes all of it.  Either pair
    may end with a small int32 vector of counters, named by the module's
    ``COUNTERS``.  The config has ``n_layer``, ``d_model``, ``n_head``,
    ``max_seq_len``, ``vocab_size`` and ``dtype``."""
    for module, cls, _ in MODEL_FAMILIES:
        if type(cfg).__name__ == cls:
            return importlib.import_module(module)
    raise TypeError(f"{type(cfg).__name__} is the config of no model family in MODEL_FAMILIES")


def tokenize_prompt(prompt: Any, vocab_size: int) -> list:
    """Token ids from a prompt: pass-through for int lists, byte-level
    (mod vocab) for strings.  The placeholder tokenizer shared by the
    continuous engine and the static-batch baseline — a real tokenizer
    is a follow-up (docs/serving.md)."""
    if isinstance(prompt, str):
        return [b % vocab_size for b in prompt.encode("utf-8")] or [0]
    if isinstance(prompt, (list, tuple)):
        return [int(t) for t in prompt] or [0]
    raise TypeError(f"prompt must be str or list[int], got {type(prompt)}")


@dataclass
class LLMConfig:
    """Engine + cache sizing for one LLM deployment.

    KV sizing: the block pool holds ``num_blocks * block_size`` token
    slots (block 0 is a reserved scratch block, never allocated).  A
    request reserves ``ceil((len(prompt) + max_tokens) / block_size)``
    blocks at admission — conservative, so a request admitted once can
    never die of cache exhaustion mid-decode.  ``max_batch_size`` is the
    number of decode lanes: the continuous batcher keeps them full by
    joining waiting requests at step boundaries.  A family that keeps
    state a lane (``cache_spec``) gets one slot of it for each lane: the
    ``minicpm_sala*`` presets a float32 state a lightning layer, the
    ``nemotron_3_nano*`` and ``granite_4_0_h_small*`` presets a
    convolution tail and a float32 scan state a Mamba-2 layer (25.6 MB
    and 38.2 MB a lane at the depths their ``*_ep*`` presets hold), and
    only their attention layers page K and V; the ``mellum2*`` presets a
    ring of K and of V a window layer (18.9 MB a lane at 9 window layers
    of 1,024 positions), and only their full-attention layers page; the
    ``jamba2*`` presets a convolution tail and a float32 scan state a
    Mamba-1 layer (9.32 MB a lane at the 26 Mamba layers of
    ``jamba2_3b``), and only their two attention layers page; the
    ``zaya1*`` presets page K and V in EVERY layer (256 values each a
    position) and hold a tail a layer beside them (the convolutions' and
    the value shift's last position: 107,520 B a lane at the 20 layers
    of ``zaya1_8b_20l``); the ``glm5*`` presets page a latent row and,
    in a second pool under the same block table, an index key a position
    in every layer (9,216 B a position at the 6 layers of
    ``glm5_6l_ep16``) and hold nothing a lane; the ``kimi_linear*``
    presets hold three convolution tails and a float32 delta-rule state a
    KDA layer (13.0 MB a lane at the 6 KDA layers of
    ``kimi_linear_48b_a3b_8l_ep8``) and page ONE latent row a position,
    with no V pool, in their MLA layers alone (2,560 B a position at its
    2 MLA layers).

    ``model`` names a preset of a model family, one of (from
    ``MODEL_FAMILIES``): {presets}.
    """

    # model
    model: str = "tiny"  # a preset of a row of MODEL_FAMILIES (PRESETS; the docstring lists them)
    seed: int = 0  # synthetic-weights init seed (no checkpoint loading yet)
    dtype: str = "float32"  # serving compute dtype ("bfloat16" on TPU)

    # batching / cache
    max_batch_size: int = 8  # concurrent decode lanes
    block_size: int = 16  # tokens per KV block
    num_blocks: int = 256  # pool size incl. the reserved scratch block 0
    max_model_len: int = 0  # 0 = the model's max_seq_len

    # admission / generation defaults
    max_queue: int = 256  # waiting requests beyond this are shed
    default_max_tokens: int = 32
    temperature: float = 0.0  # <= 0 means greedy
    top_k: int = 0  # 0 = off (static engine-wide truncation)
    eos_token: int = -1  # -1 = generate to max_tokens

    # multi-tenant overload armor (docs/serving.md "Overload resilience").
    # tenant_weights: DRF weight per tenant for the engine's fair waiting
    # queue (absent tenant -> weight 1.0).  tenant_quotas: per-tenant
    # token-rate quota {"rate": tokens/s, "burst": tokens} enforced at the
    # PROXY (flows there via the route table); the key set also bounds the
    # tenant metric-label domain.  preempt_wait_s: how long a
    # higher-priority request may starve before a lower-priority decode
    # lane is preempted-by-recompute.  slo_ttft_s: TTFT p95 SLO bound
    # driving the brownout ladder — 0 disables brownout entirely.
    tenant_weights: Dict[str, float] = field(default_factory=dict)
    tenant_quotas: Dict[str, dict] = field(default_factory=dict)
    preempt_wait_s: float = 0.25
    slo_ttft_s: float = 0.0
    brownout_queue_high: int = 0  # 0 -> 4 * max_batch_size
    brownout_down_ticks: int = 3
    brownout_up_ticks: int = 5
    brownout_batch_max_tokens: int = 8

    # observability
    name: str = "llm"  # metrics label (the deployment name, bounded)

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def coerce(cls, value: Optional[Any]) -> "LLMConfig":
        if value is None:
            return cls()
        if isinstance(value, cls):
            return value
        if isinstance(value, dict):
            return cls(**value)
        raise TypeError(f"llm_config must be LLMConfig or dict, got {type(value)}")

    def model_config(self):
        """Resolve the preset with the serving dtype: the config of the
        family whose row of MODEL_FAMILIES lists the name (only that
        family is imported)."""
        import jax.numpy as jnp

        for module, cls, presets in MODEL_FAMILIES:
            if self.model in presets:
                preset = getattr(getattr(importlib.import_module(module), cls), self.model)
                break
        else:
            raise ValueError(f"unknown model preset {self.model!r} (expected {PRESETS})")
        dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}.get(self.dtype)
        if dtype is None:
            raise ValueError(f"unsupported serving dtype {self.dtype!r}")
        return preset(dtype=dtype)

    @property
    def max_context(self) -> int:
        cfg = self.model_config()
        return min(self.max_model_len or cfg.max_seq_len, cfg.max_seq_len)


LLMConfig.__doc__ = LLMConfig.__doc__.format(presets=PRESETS)
