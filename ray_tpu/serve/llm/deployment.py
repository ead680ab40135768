"""Serve deployments over the LLM engine.

``LLMServer`` is the continuous-batching deployment: ``generate`` is an
async generator (one stream item per token, via the replica's
``handle_request_stream``), ``__call__`` is the one-shot completion
path.
"""

from __future__ import annotations

import asyncio
import dataclasses
import os
import uuid
import zlib
from typing import Any, AsyncIterator, Dict, List, Optional

from ray_tpu.serve.llm.config import LLMConfig
from ray_tpu.serve.llm.engine import FINISHED, LLMEngine
from ray_tpu.serve.multiplex import get_multiplexed_model_id, multiplexed

# transport-level key: when a streaming proxy asks for disconnect-cancel
# support (payload hint "__serve_stream_cancel__"), the first stream item
# is a meta dict under this key (consumed by the proxy, never forwarded)
STREAM_META_KEY = "__serve_stream_meta__"


def _parse(payload: Any) -> Dict[str, Any]:
    if isinstance(payload, dict):
        return payload
    if isinstance(payload, str):
        return {"prompt": payload}
    if isinstance(payload, (list, tuple)):
        return {"prompt": list(payload)}
    raise TypeError(f"LLM payload must be dict/str/list, got {type(payload)}")


class _EngineVariant:
    """One multiplexed model variant: a full engine whose weights derive
    from the variant id (seed offset — a stand-in for per-variant
    checkpoint loading, docs/serving.md).  Metrics keep the deployment
    name, so variants never mint label cardinality."""

    def __init__(self, owner: "LLMServer", config: LLMConfig, model_id: str):
        self._owner = owner
        self.model_id = model_id
        self.engine = LLMEngine(config)

    def __serve_unload__(self):
        """LRU eviction hook (called by the multiplex cache): count it
        and stop the variant's engine so its KV pool and step loop go
        with it."""
        self._owner._mx_evictions += 1
        try:
            from ray_tpu._private import telemetry

            telemetry.count_serve_multiplex_eviction(self.engine.config.name)
        except Exception:  # noqa: BLE001
            pass
        try:
            asyncio.get_event_loop().create_task(self.engine.stop())
        except RuntimeError:
            pass


class LLMServer:
    """The continuous-batching LLM deployment (one engine per replica;
    per-request ``model_id`` selects a multiplexed variant engine with
    LRU swap)."""

    MAX_MODELS_PER_REPLICA = 2

    def __init__(self, llm_config: Optional[Any] = None):
        self.config = LLMConfig.coerce(llm_config)
        self.engine = LLMEngine(self.config)
        self._mx_evictions = 0

    # -- multiplexed variants --------------------------------------------
    @multiplexed(max_num_models_per_replica=MAX_MODELS_PER_REPLICA)
    async def _load_variant(self, model_id: str) -> _EngineVariant:
        # deterministic per-variant weights: stable hash of the id folds
        # into the seed (same variant -> same weights on every replica)
        seed_off = 1 + zlib.crc32(model_id.encode("utf-8")) % 997
        cfg = dataclasses.replace(self.config, seed=self.config.seed + seed_off)
        return _EngineVariant(self, cfg, model_id)

    def _loaded_variants(self) -> List[_EngineVariant]:
        cache = getattr(self, self._load_variant._cache_attr, None)
        return list(cache._models.values()) if cache is not None else []

    async def _engine_for(self, spec: Dict[str, Any]) -> LLMEngine:
        """The engine serving this request: the payload's ``model_id``
        (or the handle's multiplexed_model_id) selects a variant; empty
        means the base engine."""
        model_id = spec.get("model_id") or get_multiplexed_model_id()
        if not model_id:
            return self.engine
        variant = await self._load_variant(model_id)
        return variant.engine

    def _identity(self, spec: Dict[str, Any]) -> tuple:
        """(tenant, slo) for this request: explicit payload fields win,
        else the wire-threaded request context set by the replica."""
        from ray_tpu.serve._private.request_context import get_request_meta

        meta = get_request_meta() or {}
        tenant = spec.get("tenant") or meta.get("tenant")
        slo = spec.get("slo") or spec.get("slo_class") or meta.get("slo")
        return tenant, slo

    # -- request paths ---------------------------------------------------
    async def generate(self, payload: Any) -> AsyncIterator[dict]:
        """Streaming completion: yields one event per token, then a final
        summary event.  The ``finally`` cancels the engine request when
        the stream is torn down early (disconnect/cancel) so KV blocks
        never leak."""
        spec = _parse(payload)
        engine = await self._engine_for(spec)
        tenant, slo = self._identity(spec)
        req = await engine.add_request(
            spec.get("prompt", ""),
            max_tokens=spec.get("max_tokens"),
            temperature=spec.get("temperature"),
            request_id=spec.get("request_id"),
            tenant=tenant,
            slo=slo,
        )
        if spec.get("__serve_stream_cancel__"):
            yield {STREAM_META_KEY: {"request_id": req.request_id,
                                     "cancel_method": "cancel"}}
        try:
            while True:
                ev = await req.out.get()
                if ev is FINISHED:
                    break
                engine.token_taken(req)
                yield ev
            yield {
                "request_id": req.request_id,
                "finish_reason": req.finish_reason,
                "num_tokens": req.generated,
                "done": True,
            }
        finally:
            engine.cancel(req.request_id)

    async def __call__(self, payload: Any):
        """One-shot completion (same engine, same batcher — just drained
        server-side instead of streamed).  HTTP token streaming lands
        here too: the proxy's chunked path calls ``__call__`` with the
        ``__serve_stream_cancel__`` hint (or the client passes
        ``stream: true``), and returning the ``generate`` async
        generator streams one chunk per token."""
        spec = _parse(payload)
        if isinstance(payload, dict) and (
            spec.get("stream") or spec.get("__serve_stream_cancel__")
        ):
            return self.generate(payload)
        engine = await self._engine_for(spec)
        tenant, slo = self._identity(spec)
        req = await engine.add_request(
            spec.get("prompt", ""),
            max_tokens=spec.get("max_tokens"),
            temperature=spec.get("temperature"),
            request_id=spec.get("request_id"),
            tenant=tenant,
            slo=slo,
        )
        try:
            while True:
                ev = await req.out.get()
                if ev is FINISHED:
                    break
                engine.token_taken(req)
            return {
                "request_id": req.request_id,
                "tokens": list(req.tokens),
                "num_tokens": req.generated,
                "finish_reason": req.finish_reason,
            }
        finally:
            engine.cancel(req.request_id)

    # -- control surface -------------------------------------------------
    def cancel(self, request_id: str) -> bool:
        """Cancel wherever the request lives: the base engine or any
        loaded variant (disconnect-cancel doesn't know which engine
        admitted the id)."""
        if self.engine.cancel(request_id):
            return True
        for v in self._loaded_variants():
            if v.engine.cancel(request_id):
                return True
        return False

    def stats(self) -> Dict[str, Any]:
        from ray_tpu.serve._private.dataplane import replica_counters

        out = self.engine.stats()
        # what the replica's channel endpoints counted on the way in and
        # out (zeros where requests take the RPC path): flat and numeric
        out.update(replica_counters())
        out["pid"] = os.getpid()
        out["multiplex"] = {
            "loaded_model_ids": [v.model_id for v in self._loaded_variants()],
            "evictions": self._mx_evictions,
        }
        return out

    def __serve_stats__(self) -> Dict[str, Any]:
        """Replica stats hook: the controller's autoscaler reads
        ``queued`` as this replica's queue depth."""
        queued = self.engine.queued_depth() + sum(
            v.engine.queued_depth() for v in self._loaded_variants()
        )
        return {"queued": queued, **self.stats()}

    async def __serve_shutdown__(self):
        """Replica prepare_shutdown hook: stop the step loops and drain
        (frees every KV block, finishes every open stream)."""
        await self.engine.stop()
        for v in self._loaded_variants():
            await v.engine.stop()


def build_app(
    llm_config: Optional[Any] = None,
    *,
    num_replicas: int = 1,
    max_ongoing_requests: int = 2048,
    max_queued_requests: int = -1,
    autoscaling_config: Optional[dict] = None,
    route_prefix: Optional[str] = None,
):
    """An Application serving ``LLMServer`` with serving-appropriate
    deployment defaults (streams hold a slot for their whole life, so
    ``max_ongoing_requests`` is high; admission control lives in the
    engine's ``max_queue`` and the proxy's ``max_queued_requests``).
    The LLM config's ``tenant_quotas`` flow onto the deployment so the
    route table carries them to the proxy's token-bucket admission."""
    import ray_tpu
    from ray_tpu import serve

    cfg = LLMConfig.coerce(llm_config)
    # On a cluster with TPUs each replica holds one chip as a lease: the
    # engine runs on one device, a chip belongs to one process, and the
    # scheduler then leaves a replica the chips cannot hold pending
    # instead of starting it on a chip that is taken.  Where no node
    # advertises a TPU the engine runs on the CPU backend and asks for
    # none.
    if not ray_tpu.is_initialized():
        ray_tpu.init()  # as serve.start() would
    on_tpu = ray_tpu.cluster_resources().get("TPU")
    dep = serve.deployment(
        name=cfg.name,
        ray_actor_options={"num_tpus": 1} if on_tpu else None,
        num_replicas=num_replicas,
        max_ongoing_requests=max_ongoing_requests,
        max_queued_requests=max_queued_requests,
        autoscaling_config=autoscaling_config,
        route_prefix=route_prefix,
        tenant_quotas=cfg.tenant_quotas,
    )(LLMServer)
    return dep.bind(cfg.to_dict())
