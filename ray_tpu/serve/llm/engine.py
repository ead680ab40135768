"""Token-granular LLM engine with continuous in-flight batching
(reference: vLLM LLMEngine / Ray Serve llm deployment, scaled to this
runtime; PAPERS.md "Fine-Tuning and Serving Gemma 4 31B on Google Cloud
TPU" for the TPU-native decode shape).

Execution model: one asyncio loop task per engine ("the step loop"),
which keeps one decode program in flight, and a second behind it while
the device is the one that waits.  Each iteration:

1. cancelled sequences leave the batch and free their KV blocks;
2. waiting requests join free decode lanes (admission reserved their
   whole KV need up front, so a joined request can never die of pool
   exhaustion): each join dispatches a bucketed, jitted prefill that
   writes the prompt's K/V straight into its pages and samples the
   first token (TTFT is measured where that token is fetched); where
   the model family states a chunk, a longer prompt is as many
   programs, in order, each reading what the ones before it wrote;
3. one jitted decode step for EVERY lane with tokens left is dispatched
   on the tokens of the step before it and on the lanes' lengths, block
   tables and temperatures, none of which leave the device (a join
   writes its lane's row there, a leave the device cannot foresee clears
   it), and only then are the programs dispatched before it fetched, in
   order, and their tokens emitted (docs/serving.md "What a step is made
   of"); where the loop is seldom blocked in those fetches, it waits only
   for what was dispatched before the decode step BEFORE the new one and
   takes of the rest what the device has finished (``_fetch_in_flight``).

Tokens stream to per-request asyncio queues; the serve replica's
``handle_request_stream`` path turns them into stream items.  The jit
calls run in the default executor so the replica's event loop (joins,
stream consumption, stats) stays responsive while one compiles.

Request spans (``serve.request`` -> ``serve.queue`` / ``serve.prefill``
/ ``serve.decode``) are recorded per request so ``state.traces()``
critical-path analysis attributes end-to-end latency to queue vs prefill
vs decode.  The step loop itself is timed by phase (``ENGINE_SPANS``):
each phase adds its seconds to a cumulative counter in ``stats()`` and,
while a ``jax.profiler`` trace is being taken, is a span on the clock of
the device's operations (docs/serving.md "Latency attribution").

Overload armor (docs/serving.md "Overload resilience"): requests carry
tenant + SLO-class identity.  The waiting queue is a weighted fair queue
over KV blocks and decode lanes (DRF, reusing ``_private/tenants.py``
math) with an intra-tenant order of priority-then-FIFO; a starved
higher-priority request preempts the cheapest lower-priority decode lane
by recompute (KV pages freed, generated-so-far folded into the prompt,
prefill-resume is token-exact under greedy sampling); and a brownout
ladder driven by observed TTFT/queue depth degrades batch before
standard and never sheds interactive.  All of it is inert for anonymous
traffic: identity-free requests take the original FIFO fast path.
"""

from __future__ import annotations

import asyncio
import collections
import contextlib
import functools
import gc
import logging
import time
import uuid
import weakref
from dataclasses import dataclass, field
from typing import Any, Deque, Dict, List, Optional

import numpy as np

from ray_tpu._private import tenants as tenants_mod
from ray_tpu.serve._private.request_context import get_request_meta
from ray_tpu.serve.exceptions import RequestShedError
from ray_tpu.serve.llm.config import LLMConfig, model_family
from ray_tpu.serve.llm.kv_cache import BlockManager
from ray_tpu.serve.llm.overload import (
    DegradationController,
    SLO_PRIORITY,
    normalize_slo,
)

logger = logging.getLogger(__name__)

# end-of-stream sentinel pushed onto a request's output queue
FINISHED = object()

# The spans of the step loop, by where its time goes.  ``_phase(name)``
# opens a profiler TraceAnnotation of that name around each and adds its
# seconds to ``stats()["<phase>_s"]`` (the name less "engine.", dots to
# underscores).  Each encloses synchronous code only.
ENGINE_SPANS = (
    "engine.admit",          # _reap, _preempt_victim, _next_admissible, the lanes of a step
    "engine.prefill.build",  # bucket, pad, phys_indices of one prompt
    "engine.prefill.run",    # executor thread: the prefill jit call (dispatch)
    "engine.prefill.fetch",  # loop thread: a prefill in flight: its first token to the host
    "engine.decode.build",   # advance_lanes (dispatch: the step's arguments, on the device), the step's counts
    "engine.decode.run",     # executor thread: the decode jit call (dispatch)
    "engine.decode.fetch",   # loop thread: waiting for the step in flight, np.asarray(nxt)
    "engine.emit",           # tokens onto the streams, _finish of lanes that end
    "engine.metrics",        # _push_metrics, report_device_memory in it
)
# What the loop thread awaits is timed by the same helper into counters
# only (``span=False``): an annotation held across an await would
# interleave with the coroutines that share the thread.  The two awaits
# enclose their ``run`` span: the difference is the executor hop.
_LOOP_WAITS = (
    "engine.prefill.await",
    "engine.decode.await",
    "engine.yield",          # sleep(0): what the loop's other callbacks took
    "engine.idle",           # nothing to run: waiting on _wake or the 5 ms retry
)
# A replica's set-up, as consecutive phases on the epoch clock: each a
# span under ``setup.replica`` and seconds in ``stats()["<phase>_s"]``
# (dots to underscores).  docs/serving.md "What a start is made of".
SETUP_PHASES = (
    "setup.worker",    # default_worker.main's entry to the replica's __init__ entering
    "setup.device",    # config, ``import jax``, the backend's client: to the first array on the device
    "setup.params",    # family.init_params + family.serving_params (dispatched, not waited for)
    "setup.pools",     # the cache's pools, lane state, _lane_tok, _lanes
    "setup.programs",  # the jits, the first _clear_lane, the host's state: to __init__ returning
)
# A slice of the loop (from one dispatched prefill, fetched program or
# iteration's end to the next: one device program, the host work around
# it) that takes longer than this outside ``engine.idle`` is a stall:
# counted, and logged with its milliseconds by phase.
STALL_S = 1.0
# The loop leaves a second decode step queued behind the running one
# while it is the one waited for: while, of its last AHEAD_WINDOW
# iterations' time outside ``engine.idle``, it spent under
# AHEAD_BLOCKED_SHARE blocked in the two fetch phases.  A loop whose
# device sets the pace reads 0.55-0.9 there and one the device waits
# for 0.2, 0.1 once it is ahead (PERF.md §6, PR 51): being ahead lowers
# the share, so neither state undoes itself.
AHEAD_BLOCKED_SHARE = 0.5
AHEAD_WINDOW = 32


@dataclass
class _Request:
    request_id: str
    prompt: List[int]
    max_tokens: int
    temperature: float
    out: "asyncio.Queue"
    t_submit: float
    # span plumbing: (trace_id, root_span_id, parent_span_id or None)
    trace: tuple = ()
    slot: int = -1
    generated: int = 0
    dispatched: int = 0  # tokens whose programs were dispatched: generated + what is in flight
    row_live: bool = False  # its lane's row on the device is its own (joined, and not cleared since)
    finish_reason: str = ""
    cancelled: bool = False
    t_join: float = 0.0
    t_first_token: float = 0.0
    t_done: float = 0.0
    join_step: int = -1
    finish_step: int = -1
    tokens: List[int] = field(default_factory=list)
    # overload identity + preemption state
    tenant: str = tenants_mod.DEFAULT_TENANT
    slo: str = "standard"
    priority: int = 1
    seq: int = 0  # admission order — the intra-tenant FIFO tiebreak
    preemptions: int = 0
    folded: int = 0  # tokens already folded into prompt by past preemptions
    t_enqueue: float = 0.0  # last (re)queue time — the starvation clock
    # the way in, from the request's meta (0.0 where it carried none: a
    # direct add_request): the caller's hand-over to the handle, and the
    # instant the request first existed in this process; never out of
    # order with t_submit (add_request)
    t_sent: float = 0.0
    t_rx: float = 0.0
    # the way out: when each token not yet taken off ``out`` was emitted,
    # in order, beside the events and not in them (``token_taken``)
    emit_times: Deque[float] = field(default_factory=collections.deque)


def _write_rows(pages, rows, phys):
    """pages [L, P, D] with rows [L, T, ...] (D values each) written at
    slots phys [T] of every layer, in place when pages is donated.  The
    pool is addressed as [L * P, D] so that the scatter indexes its
    major-most dim: in the 3-D form XLA re-lays the whole pool out to
    put the slots first and back again, four pool-sized copies a step."""
    import jax.numpy as jnp

    L, P, D = pages.shape
    idx = (jnp.arange(L, dtype=phys.dtype)[:, None] * P + phys[None, :]).reshape(-1)
    return pages.reshape(L * P, D).at[idx].set(rows.reshape(-1, D)).reshape(L, P, D)


def _write_back(cache, k, v, slots, rows):
    """K and V rows into their pools at ``slots`` (no V where the family
    states no V pool: it returns None for it), and each page extra's
    rows where its family said."""
    cache["k_pages"] = _write_rows(cache["k_pages"], k, slots)
    if "v_pages" in cache:
        cache["v_pages"] = _write_rows(cache["v_pages"], v, slots)
    for name, (values, where) in rows.items():
        cache[name] = _write_rows(cache[name], values, where)


def prefill_step(cfg, top_k, block_size, spec, params, *args):
    """One prefill program into the cache the family states (``spec``):
    ``args`` are the cache's arrays in the order of ``spec.names``
    (donated), then tokens [1, Tpad], phys [Tpad] (the slots of its
    rows; scratch slot 0 at pads), last_idx [1] (logits are taken at the
    last REAL position, not the pad tail), temp, rng, and, for a family
    that reads its cache, start (the first position of this chunk of the
    prompt), table [pages] and lane.  Such a family returns what to
    write: K and V rows, rows of the page extras with their places, the
    lane's new state.  Returns the first token, then the family's
    counters if it has any, in one array (one fetch brings both), then
    the cache."""
    n = len(spec.names)
    cache, (tokens, phys, last_idx, temp, rng, *chunk) = dict(zip(spec.names, args)), args[n:]
    family = model_family(cfg)
    rows, state = {}, {}
    if spec.reads_cache:
        start, table, lane = chunk
        logits, k, v, rows, state, *counters = family.prefill_chunk(
            params, cfg, cache, tokens, start, last_idx, table, lane, block_size)
    else:
        logits, k, v, *counters = family.prefill_forward(params, cfg, tokens, last_index=last_idx)
    _write_back(cache, k[:, 0], v if v is None else v[:, 0], phys, rows)
    for name, value in state.items():
        cache[name] = cache[name].at[lane].set(value)
    first = _sample(logits, rng, temp, top_k, counters)
    return (first if counters else first[0], *cache.values())


def decode_step(cfg, top_k, block_size, spec, params, *args):
    """Advance every lane one token: ``args`` are the cache's arrays in
    the order of ``spec.names`` (donated), then tok [B], lengths [B]
    (the positions a lane has cached, which is also the fed token's
    position), block_tables [B, pages] (a lane's physical blocks,
    scratch block 0 beyond them), write_phys [B], temp, rng.  Attention
    reads the lane's pages where they lie; the new K/V go back at
    write_phys (inactive lanes have length 0 and hit slot 0), and
    whatever else the family states it caches where the family says.
    Returns the lanes' tokens, then the family's counters if any, then
    the cache."""
    n = len(spec.names)
    cache, (tok, lengths, block_tables, write_phys, temp, rng) = dict(zip(spec.names, args)), args[n:]
    family = model_family(cfg)
    rows, state = {}, {}
    if spec.reads_cache:
        logits, k_new, v_new, rows, state, *counters = family.decode_forward_cached(
            params, cfg, cache, tok, block_tables, lengths, block_size)
    else:
        logits, k_new, v_new, *counters = family.decode_forward_paged(
            params, cfg, tok, cache["k_pages"], cache["v_pages"], block_tables, lengths, block_size)
    _write_back(cache, k_new, v_new, write_phys, rows)
    cache.update(state)  # every lane's state, whole
    return (_sample(logits, rng, temp, top_k, counters), *cache.values())


def _sample(logits, rng, temp, top_k, counters):
    """The sampled tokens [B], followed by the forward's counters where
    it returned any: the one array a step's one fetch brings back."""
    import jax.numpy as jnp

    from ray_tpu.models.common import sample_logits

    tokens = sample_logits(logits, rng, temp, top_k)
    return jnp.concatenate([tokens, *counters]) if counters else tokens


# What the device holds of every lane beside its newest token: the
# arguments of the next decode step that the host would otherwise build
# and send again each step.  ``rows`` is int32 [B, 2 + pages], a lane a
# row: the positions it has cached (0 where it does not run), the decode
# steps it is still to be dispatched for, its physical blocks; ``temp``
# [B] its temperature; ``step`` the decode steps dispatched so far (the
# sampling key's counter).  A lane's table and temperature are fixed
# from its join to its leave (``kv_cache.py``: a sequence's whole need
# is reserved at admission).  Few arrays, because on the host each array
# a program RETURNS costs as much as a third of a whole dispatch.
LANE_STATE = ("rows", "temp", "step")
_CACHED, _LEFT, _TABLE = 0, 1, 2  # columns of ``rows``


def put_lane(lane_tok, lanes, first, lane, row, temp):
    """A join: the request's first token (the head of ``first``, a
    prefill's output) to its lane's place, and its row (the positions
    its prompt cached, the decode steps it has left, its block table)
    and temperature to the lanes' state."""
    return lane_tok.at[lane].set(first.reshape(-1)[0]), dict(
        lanes, rows=lanes["rows"].at[lane].set(row), temp=lanes["temp"].at[lane].set(temp[0]))


def clear_lane(lanes, lane):
    """A leave the device could not foresee (eos_token, cancel(), a
    preemption, stop()): the lane runs no further step."""
    return dict(lanes, rows=lanes["rows"].at[lane].set(0))


def advance_lanes(block_size, lanes, base_key):
    """One decode step's arguments from the lanes' state, and the state
    after it: -> (rows, step, lengths, tables, write_phys, rng), the
    last four as the host built them before (zeros for a lane that does
    not run, which then reads nothing and writes scratch slot 0).  A
    lane whose last step this is has its row zeroed: nothing stale is
    left for a step to read.  The key is a function of the seed and of
    how many steps were dispatched."""
    import jax
    import jax.numpy as jnp

    rows, step = lanes["rows"], lanes["step"]
    runs = rows[:, _LEFT] > 0
    lengths = jnp.where(runs, rows[:, _CACHED], 0)  # also the fed token's position
    tables = jnp.where(runs[:, None], rows[:, _TABLE:], 0)
    block = jnp.take_along_axis(tables, (lengths // block_size)[:, None], axis=1)[:, 0]
    write_phys = block * block_size + lengths % block_size
    after = rows.at[:, _CACHED].add(1).at[:, _LEFT].add(-1)
    after = jnp.where((after[:, _LEFT] > 0)[:, None], after, 0)
    return after, step + 1, lengths, tables, write_phys, jax.random.fold_in(base_key, step)


def _host_bytes(args) -> int:
    """Bytes of the host-made arrays among a jit call's arguments: what
    the call has to send to the device before its program can run."""
    return sum(a.nbytes for a in args if isinstance(a, (np.ndarray, np.generic)))


@dataclass
class _InFlight:
    """A program dispatched and not yet fetched.  The device runs
    programs in the order they were dispatched (they chain through the
    donated pool), so these are fetched in that order too."""
    out: Any  # device array: the tokens, then the family's counters
    lanes: List[tuple]  # (index of its token in ``out``, request)
    counts: Dict[str, int]  # added to stats() when it is fetched
    decode: bool = True  # a decode step, or one request's prefill


class LLMEngine:
    """One engine per replica; owns the model params, the cache the
    model family states (``cache``: the paged K/V pools, and what else a
    page or a lane holds), and the continuous-batching step loop."""

    def __init__(self, config: Optional[Any] = None):
        from ray_tpu._private import profiling as _profiling

        # the set-up, phase by phase (docs/serving.md "What a start is
        # made of"): the worker's, where this process is a replica's
        setup = _profiling.setup_under_way() or _profiling.begin_setup()
        setup.enter("setup.device")
        try:
            # before anything compiles: imports jax, which this phase covers
            _profiling.listen_for_compiles()
            self._set_up(config, setup)
        finally:
            setup.finish()
        # seconds by phase, and the instant the last one ended: plain
        # numbers in stats()
        self._setup_s = {f"{name.replace('.', '_')}_s": setup.seconds(name) for name in SETUP_PHASES}
        self._setup_s["engine_ready_at"] = setup.at

    def _set_up(self, config, setup):
        self.config = LLMConfig.coerce(config)
        self.model_cfg = self.config.model_config()
        self.max_ctx = self.config.max_context
        # what the family states it caches: pages a sequence reserves by
        # its length, and state a lane owns whatever its length
        self._spec = model_family(self.model_cfg).cache_spec(self.model_cfg, self.config.block_size)
        self.bm = BlockManager(self.config.num_blocks, self.config.block_size,
                               state_slots=self.config.max_batch_size if self._spec.lane_state else 0)
        # usable pool excludes the reserved scratch block 0: a max-length
        # sequence must fit in the ALLOCATABLE blocks, or a max-size
        # request would pass admission bounds yet park forever
        if self.bm.blocks_needed(self.max_ctx) > self.config.num_blocks - 1:
            raise ValueError(
                "KV pool smaller than one max-length sequence: "
                f"{self.config.num_blocks - 1} usable blocks < "
                f"{self.bm.blocks_needed(self.max_ctx)} needed for "
                f"max_context {self.max_ctx}"
            )
        self._build_model(setup)
        self.slots: List[Optional[_Request]] = [None] * self.config.max_batch_size
        self.waiting: Deque[_Request] = collections.deque()
        self._by_id: Dict[str, _Request] = {}
        self._loop_task: Optional[asyncio.Task] = None
        self._wake: Optional[asyncio.Event] = None
        self._stopped = False
        # programs dispatched and not yet fetched, oldest first: between
        # two iterations at most two decode steps, the newest
        self._inflight: Deque[_InFlight] = collections.deque()
        # the loop's clock at the end of each of its last iterations:
        # (seconds outside engine.idle, seconds blocked in the two fetch
        # phases), both cumulative (_device_waits)
        self._pace: Deque[tuple] = collections.deque(maxlen=AHEAD_WINDOW + 1)
        # the executor's future of the jit call under way (stop() waits for it)
        self._dispatching: Optional[asyncio.Future] = None
        self.step_count = 0
        self._rng_counter = 0
        # (wall time, tokens emitted) per step, for the tokens/s gauge
        self._tok_window: Deque[tuple] = collections.deque(maxlen=512)
        self._total_tokens = 0
        self._phase_s = dict.fromkeys(ENGINE_SPANS + _LOOP_WAITS, 0.0)
        # cumulative counts of work, taken where the work happens; all
        # plain numbers in stats()
        self._counts: Dict[str, Any] = {
            "joined": 0, "queue_wait_s": 0.0,
            "prompt_tokens": 0, "prefill_bucket_tokens": 0, "prefill_chunks": 0,
            # bytes of lane state the programs read and wrote
            "state_bytes": 0,
            # of the positions a decode step reads (the whole pages its
            # kernel copies for the lanes in use), those a lane holds
            "kv_positions_attended": 0, "kv_positions_gathered": 0,
            "stalls": 0, "stall_s": 0.0,
            # decode steps dispatched while the one before was unfetched
            # (the pipeline engaged), those dispatched while the two
            # before were (the loop ran a step ahead), and lane-steps
            # whose request had ended (eos_token, cancel) by the time
            # their token came
            "decodes_chained": 0, "decodes_ahead": 0, "lane_steps_discarded": 0,
            # bytes of host-made arguments the decode dispatches carried
            # (0: a step sends the device nothing it already has), and
            # rows of the device's lane state written or cleared (joins
            # plus the leaves the device could not foresee)
            "decode_host_bytes": 0, "lane_edits": 0,
            # the runtime's share of a token, as sums of differences of
            # stamps on one clock (docs/serving.md "What a step is made
            # of").  In: requests that carried the handle's ``sent_at``,
            # from there to the replica's ``rx_at``, from there to
            # add_request.  First tokens, and the join they followed.
            # Out: tokens a stream's coroutine took off its queue, and
            # how long each had lain there
            "submitted": 0, "ingress_wire_s": 0.0, "ingress_loop_s": 0.0,
            "first_tokens": 0, "join_to_first_token_s": 0.0,
            "tokens_out": 0, "egress_loop_s": 0.0,
            # the interpreter's collections while the loop ran, whichever
            # thread they began in: every thread of the process stands still
            "gc_collections": 0, "gc_full_collections": 0, "gc_pause_s": 0.0,
            **dict.fromkeys(self._counter_names, 0),
        }
        self._gc_t0: Optional[float] = None
        self._gc_span = None
        # where the current slice of the loop began (_note_stall)
        self._slice_t0 = time.perf_counter()
        self._slice_before = dict(self._phase_s)
        self._slice_lowered = 0
        # the compile ledger as the last "llm engine compiled" line saw it
        self._ledger_seen: Dict[str, Dict[str, Any]] = {}
        self._shed_total = 0
        # shed attribution: {(where, tenant_label): n}, flushed at 1 Hz
        self._shed_unreported: Dict[tuple, int] = {}
        self._last_metrics_push = 0.0
        # -- overload armor state (docs/serving.md) --
        self._seq_counter = 0
        # False -> every waiting request is anonymous default-tenant
        # standard-class traffic, so admission takes the original FIFO
        # fast path (zero overhead for identity-free workloads)
        self._fair_dirty = False
        self._preempt_total = 0
        self._events: Deque[Dict[str, Any]] = collections.deque(maxlen=128)
        self._ttft_recent: Deque[float] = collections.deque(maxlen=64)
        # (wall time, tenant, tokens) for the per-tenant rate gauge
        self._tenant_tok_window: Deque[tuple] = collections.deque(maxlen=2048)
        self._registered_tenants = (
            set(self.config.tenant_quotas) | set(self.config.tenant_weights)
        )
        self._degrade = DegradationController(
            ttft_slo_s=self.config.slo_ttft_s,
            queue_high=(self.config.brownout_queue_high
                        or 4 * self.config.max_batch_size),
            down_ticks=self.config.brownout_down_ticks,
            up_ticks=self.config.brownout_up_ticks,
            batch_max_tokens=self.config.brownout_batch_max_tokens,
        )

    # -- model / jit ----------------------------------------------------
    def _build_model(self, setup):
        import jax

        import jax.numpy as jnp

        cfg = self.model_cfg
        family = model_family(cfg)
        # the first array on the device: the backend's client has started
        rng = jax.random.PRNGKey(self.config.seed)
        setup.enter("setup.params")
        # only what the two programs read is kept, each leaf in the dtype
        # they compute with: an argument in another dtype is read whole
        # and cast again by every program
        self.params = family.serving_params(family.init_params(cfg, rng=rng), cfg)
        self._param_bytes = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(self.params))
        setup.enter("setup.pools")
        # what the family's programs count and return after their tokens
        self._counter_names = tuple(getattr(family, "COUNTERS", ()))
        # the cache, by the family's statement.  A position is one row
        # of all the heads it caches: a page is then one contiguous
        # slab, which the decode kernel copies whole
        spec, lanes = self._spec, self.config.max_batch_size
        pool = (spec.paged_layers, self.bm.num_slots, spec.row_width)
        self.cache = {"k_pages": jnp.zeros(pool, cfg.dtype)}
        if spec.v_pool:
            self.cache["v_pages"] = jnp.zeros(pool, cfg.dtype)
        for name, rows_a_page, width, dtype in spec.page_extras:
            self.cache[name] = jnp.zeros((spec.paged_layers, self.bm.num_blocks * rows_a_page, width), dtype)
        for name, shape, dtype in spec.lane_state:
            self.cache[name] = jnp.zeros((lanes, *shape), dtype)
        assert tuple(self.cache) == spec.names
        self._state_bytes = sum(self.cache[name].nbytes for name, *_ in spec.lane_state)
        # where the cache lives, reported by stats(): a replica that was
        # meant for the chip and runs on the CPU is then visible
        self._device = next(iter(self.cache["k_pages"].devices()))
        # every lane's newest token, on the device: each program's token
        # goes in as it is dispatched, and is the next decode step's
        # ``tok``, so no token crosses to the host and back to be fed
        self._lane_tok = jnp.zeros(lanes, jnp.int32)
        # and the rest of what a decode step is called with (LANE_STATE):
        # written by a join, cleared by a leave the device cannot
        # foresee, advanced on the device by one small program a step
        pages = self.bm.blocks_needed(self.max_ctx)
        self._lanes = {"rows": jnp.zeros((lanes, _TABLE + pages), jnp.int32),
                       "temp": jnp.zeros(lanes, jnp.float32), "step": jnp.zeros((), jnp.int32)}
        assert tuple(self._lanes) == LANE_STATE
        setup.enter("setup.programs")
        self._put_lane = jax.jit(put_lane)
        self._clear_lane = jax.jit(clear_lane)
        self._advance_lanes = jax.jit(functools.partial(advance_lanes, self.config.block_size))
        self._lanes_of = jax.jit(lambda nxt: nxt[:lanes]) if self._counter_names else (lambda nxt: nxt)
        # prefills (and whoever replays a sequence through the two
        # programs) draw their keys from the first by the host's
        # counter, decode steps from the second by the device's
        self._base_key = jax.random.PRNGKey(self.config.seed + 1)
        self._decode_key = jax.random.PRNGKey(self.config.seed + 2)
        self._fold_in = jax.jit(jax.random.fold_in)
        # the first leave of its kind must not compile mid-service
        self._lanes = self._clear_lane(self._lanes, np.int32(0))
        top_k = self.config.top_k
        # a disabled TraceMe (well under a microsecond) outside a
        # jax.profiler session; bound here because only this method
        # imports jax
        self._annotation = jax.profiler.TraceAnnotation
        # XLA introspection on the serving hot path: compile-time/
        # retrace counters (prefill compiles once per prompt bucket —
        # a retrace storm here is a bucketing bug; docs/profiling.md).
        from ray_tpu._private import profiling as _profiling

        # both take the params, then the cache's arrays (donated), then
        # their inputs, and return their tokens, then the cache
        held = tuple(range(1, 1 + len(spec.names)))
        bound = (cfg, top_k, self.config.block_size, spec)
        self._prefill_jit = _profiling.instrument_jit(
            "serve_prefill", jax.jit(functools.partial(prefill_step, *bound), donate_argnums=held))
        self._decode_jit = _profiling.instrument_jit(
            "serve_decode", jax.jit(functools.partial(decode_step, *bound), donate_argnums=held))

    # the paged pools by name (``v_pages`` where the family states one)
    k_pages = property(lambda self: self.cache["k_pages"],
                       lambda self, pages: self.cache.__setitem__("k_pages", pages))
    v_pages = property(lambda self: self.cache["v_pages"],
                       lambda self, pages: self.cache.__setitem__("v_pages", pages))

    def _run_on_cache(self, program, *inputs):
        """One jit call on the cache: its arrays go in donated and the
        engine is rebound to what comes back, in one synchronous stretch
        (``_dispatch``).  -> the program's tokens."""
        out, *held = program(self.params, *self.cache.values(), *inputs)
        self.cache = dict(zip(self.cache, held))
        return out

    def _next_rng(self):
        """The next key of the host's stream (a prefill's, a replay's):
        ``fold_in(base, counter)``, as one jitted dispatch: with a Python
        int ``jax.random.fold_in`` is several, a millisecond on a v5e's
        host."""
        self._rng_counter += 1
        return self._fold_in(self._base_key, np.uint32(self._rng_counter))

    @staticmethod
    def _prefill_bucket(n: int, cap: int) -> int:
        """Pad prompts to power-of-two buckets (min 8) so prefill
        compiles once per bucket, not once per prompt length."""
        b = 8
        while b < n:
            b *= 2
        return min(b, cap)

    # -- public API ------------------------------------------------------
    def ensure_started(self):
        """Start (or restart) the step loop on the current event loop."""
        if self._loop_task is None or self._loop_task.done():
            self._stopped = False
            self._wake = self._wake or asyncio.Event()
            self._loop_task = asyncio.get_running_loop().create_task(self._run())

    async def stop(self):
        self._stopped = True
        if self._wake is not None:
            self._wake.set()
        task, self._loop_task = self._loop_task, None
        if task is not None:
            task.cancel()
            try:
                await task
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass
        if self._dispatching is not None:
            # a jit call under way in the executor thread has donated the
            # cache: it rebinds it before it returns
            await asyncio.wait([self._dispatching])
            self._dispatching = None
        # what is in flight is never fetched and never emitted; its K/V
        # writes land in blocks freed below, and the device runs them
        # before whatever a later owner's prefill is dispatched with
        self._inflight.clear()
        # drain everything still queued/running so blocks balance to zero
        self.slots = [None] * self.config.max_batch_size
        self.waiting.clear()
        for req in list(self._by_id.values()):
            self._finish(req, "engine_stopped")

    def tokenize(self, prompt: Any) -> List[int]:
        """Token ids from a prompt (shared byte-level placeholder
        tokenizer — docs/serving.md)."""
        from ray_tpu.serve.llm.config import tokenize_prompt

        return tokenize_prompt(prompt, self.model_cfg.vocab_size)

    def _tenant_label(self, tenant: str) -> str:
        """Clamp a wire-supplied tenant to the bounded metric domain."""
        return tenants_mod.tenant_label(tenant, self._registered_tenants)

    def _shed(self, where: str, tenant: str, message: str,
              retry_after_s: float = 1.0) -> None:
        self._shed_total += 1
        key = (where, self._tenant_label(tenant))
        self._shed_unreported[key] = self._shed_unreported.get(key, 0) + 1
        self._push_metrics(force=True)
        raise RequestShedError(message, retry_after_s=retry_after_s)

    async def add_request(
        self,
        prompt: Any,
        max_tokens: Optional[int] = None,
        temperature: Optional[float] = None,
        request_id: Optional[str] = None,
        tenant: Optional[str] = None,
        slo: Optional[str] = None,
    ) -> _Request:
        """Admit one request; its ``.out`` queue streams token events
        ending with the FINISHED sentinel.  Sheds (typed, retryable) when
        the waiting queue is at its bound or the brownout ladder sheds
        the request's SLO class."""
        self.ensure_started()
        tenant = tenants_mod.normalize_tenant(tenant)
        slo = normalize_slo(slo)
        if self._degrade.should_shed(slo):
            self._shed(
                "brownout", tenant,
                f"brownout level {self._degrade.level} sheds {slo}-class "
                "requests (interactive is never shed)",
                retry_after_s=2.0,
            )
        if len(self.waiting) >= self.config.max_queue:
            self._shed(
                "engine", tenant,
                f"engine queue full ({len(self.waiting)} waiting, "
                f"bound {self.config.max_queue})",
            )
        tokens = self.tokenize(prompt)
        if len(tokens) >= self.max_ctx:
            tokens = tokens[: self.max_ctx - 1]
        mt = max_tokens if max_tokens is not None else self.config.default_max_tokens
        mt = self._degrade.max_tokens_cap(slo, mt)
        mt = max(1, min(int(mt), self.max_ctx - len(tokens)))
        temp = self.config.temperature if temperature is None else float(temperature)
        rid = request_id or uuid.uuid4().hex[:16]
        if rid in self._by_id:
            raise ValueError(f"duplicate request id {rid!r}")
        now = time.time()
        self._seq_counter += 1
        # the way in, where the request's meta carries it.  Held to the
        # order t_sent <= t_rx <= now: a stamp from a host whose clock
        # runs ahead then counts a difference of 0, never a negative one
        meta = get_request_meta() or {}
        t_rx = min(float(meta.get("rx_at") or now), now)
        t_sent = min(float(meta.get("sent_at") or 0.0), t_rx)
        req = _Request(
            request_id=rid,
            prompt=tokens,
            max_tokens=mt,
            temperature=temp,
            out=asyncio.Queue(),
            t_submit=now,
            trace=self._mint_trace(),
            tenant=tenant,
            slo=slo,
            priority=SLO_PRIORITY[slo],
            seq=self._seq_counter,
            t_enqueue=now,
            t_sent=t_sent,
            t_rx=t_rx if t_sent else 0.0,
        )
        if t_sent:
            self._counts["submitted"] += 1
            self._counts["ingress_wire_s"] += t_rx - t_sent
            self._counts["ingress_loop_s"] += now - t_rx
        if tenant != tenants_mod.DEFAULT_TENANT or req.priority != 1:
            self._fair_dirty = True
        self._by_id[rid] = req
        self.waiting.append(req)
        self._wake.set()
        return req

    def cancel(self, request_id: str) -> bool:
        """Cancel a request (client disconnect or explicit): frees its KV
        blocks and emits the finish sentinel.  Idempotent."""
        req = self._by_id.get(request_id)
        if req is None:
            return False
        if req.slot < 0:
            # still queued: release immediately (no blocks held yet)
            try:
                self.waiting.remove(req)
            except ValueError:
                pass
            self._finish(req, "cancelled")
            return True
        # running: mark; the next step boundary frees the lane + blocks
        req.cancelled = True
        req.finish_reason = "cancelled"
        if self._wake is not None:
            self._wake.set()
        return True

    def stats(self) -> Dict[str, Any]:
        from ray_tpu._private import profiling as _profiling

        running = sum(1 for r in self.slots if r is not None)
        compiled = _profiling.compile_totals()
        tenants: Dict[str, Dict[str, int]] = {}
        for r in self.slots:
            if r is None:
                continue
            u = tenants.setdefault(
                self._tenant_label(r.tenant),
                {"waiting": 0, "running": 0, "kv_blocks": 0},
            )
            u["running"] += 1
            u["kv_blocks"] += self.bm.blocks_held(r.request_id)
        for r in self.waiting:
            u = tenants.setdefault(
                self._tenant_label(r.tenant),
                {"waiting": 0, "running": 0, "kv_blocks": 0},
            )
            u["waiting"] += 1
        return {
            "waiting": len(self.waiting),
            "running": running,
            "max_batch_size": self.config.max_batch_size,
            "platform": self._device.platform,
            "device_kind": self._device.device_kind,
            "param_bytes": self._param_bytes,
            "kv_blocks_in_use": self.bm.blocks_in_use,
            "kv_blocks_total": self.bm.num_blocks - 1,
            "kv_leak_report": self.bm.leak_report(),
            "state_slots_in_use": self.bm.state_slots_in_use,
            "state_slots_total": self.bm.state_slots,
            # what the lanes own whatever their sequences' lengths
            # (``CacheSpec.lane_state``: scan states, tails, rings)
            "state_bytes_held": self._state_bytes,
            "tokens_per_s": round(self._tokens_per_s(), 2),
            "total_tokens": self._total_tokens,
            "shed_total": self._shed_total,
            "steps": self.step_count,
            **self._counts,
            **{n[len("engine."):].replace(".", "_") + "_s": v
               for n, v in self._phase_s.items()},
            # the start, phase by phase, and the process's compile ledger
            # (docs/serving.md "What a start is made of")
            **self._setup_s,
            **{f"compile_{k}": compiled[k] for k in (
                "trace_s", "lower_s", "backend_hit_s", "backend_miss_s",
                "cache_read_s", "cache_hits", "cache_misses")},
            "programs_lowered": compiled["lowerings"],
            **{f"{f}_first_call_s": _profiling.jit_stats(f).get("first_call_s", 0.0)
               for f in ("serve_prefill", "serve_decode")},
            "preemptions_total": self._preempt_total,
            "degradation_level": self._degrade.level,
            "tenants": tenants,
            "events": list(self._events),
        }

    def queued_depth(self) -> int:
        """Autoscaling signal: requests in the engine (waiting + lanes)."""
        return len(self.waiting) + sum(1 for r in self.slots if r is not None)

    # -- step loop -------------------------------------------------------
    @contextlib.contextmanager
    def _phase(self, name: str, span: bool = True):
        """Time one phase of the step loop into its cumulative counter
        and mark it as a span in the profiler's trace (a name of
        ENGINE_SPANS), or time it alone (``span=False``: a name of
        _LOOP_WAITS, which encloses an await)."""
        t0 = time.perf_counter()
        try:
            with self._annotation(name) if span else contextlib.nullcontext():
                yield
        finally:
            self._phase_s[name] += time.perf_counter() - t0

    async def _run(self):
        loop = asyncio.get_running_loop()
        self._note_stall()  # the first slice begins now
        self._pace.clear()  # and nothing is known of the loop's pace
        self._note_pace()
        with self._gc_watched():
            while not self._stopped:
                try:
                    await self._iterate(loop)
                except asyncio.CancelledError:
                    raise
                except Exception:  # noqa: BLE001 — one bad step must not stop serving
                    logger.exception("llm engine step failed; continuing")
                    try:
                        # what is in flight ran before the step that raised:
                        # its tokens are real, and go out first
                        self._fetch_in_flight()
                    except Exception:  # noqa: BLE001 — the device's answer is lost too
                        logger.exception("llm engine could not fetch what was in flight")
                    with self._phase("engine.idle", span=False):
                        await asyncio.sleep(0.05)
                self._note_stall()

    @contextlib.contextmanager
    def _gc_watched(self):
        """While the step loop runs, the interpreter's collections are
        counted where they happen: one ``gc.callbacks`` hook, gone with
        the loop task (``stop()`` awaits its end).  It holds the engine
        weakly, so an engine nobody stopped can still be collected, and
        its loop's end then takes the hook away."""
        engine = weakref.ref(self)

        def hook(phase, info):
            eng = engine()
            if eng is not None:
                eng._on_gc(phase, info)

        gc.callbacks.append(hook)
        try:
            yield
        finally:
            gc.callbacks.remove(hook)
            self._gc_t0 = None

    def _on_gc(self, phase: str, info: dict):
        """A collection starts or stops, in whichever thread allocated
        last; collections never nest.  ``engine.gc`` is held from start
        to stop in that thread, so a gap of the device whose middle lies
        in a collection can be named in a traced run."""
        if phase == "start":
            self._gc_span = self._annotation("engine.gc")
            self._gc_span.__enter__()
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:  # else it began before the hook was there
            self._counts["gc_pause_s"] += time.perf_counter() - self._gc_t0
            self._gc_t0 = None
            self._gc_span.__exit__(None, None, None)
            self._counts["gc_collections"] += 1
            self._counts["gc_full_collections"] += int(info["generation"] == 2)

    async def _iterate(self, loop):
        """One iteration: leaves, a preemption, joins (their prefills go
        in front of this iteration's decode step), one decode step, then
        the fetches.  The loop blocks on what was dispatched before the
        new step, or, while the device is the one that waits
        (``_device_waits``), before the step BEFORE it, and takes of the
        rest what is finished: one or two decode steps stay queued."""
        with self._phase("engine.admit"):
            self._reap()
            victim, for_req = self._preempt_victim()
        if victim is not None:
            # a fold takes the victim's tokens from the host: fetch
            # whatever is in flight first, then decide again on what is
            # true now (a lane may have ended in it, the victim's too)
            self._fetch_in_flight()
            with self._phase("engine.admit"):
                victim, for_req = self._preempt_victim()
                if victim is not None:
                    self._preempt(victim, for_req)
        await self._join_waiters(loop)
        newest = await self._dispatch_decode(loop)
        if newest is None and not self._inflight and not any(r is not None for r in self.slots):
            with self._phase("engine.metrics"):
                self._push_metrics()
            with self._phase("engine.idle", span=False):
                if not self.waiting:
                    self._wake.clear()
                    try:
                        await asyncio.wait_for(self._wake.wait(), timeout=1.0)
                    except asyncio.TimeoutError:
                        pass
                else:
                    # waiting but nothing admissible: KV pool full —
                    # yield until a completion frees blocks
                    await asyncio.sleep(0.005)
            return
        # the device now has the next step to run: wait for what was
        # dispatched before it (or before the step before it), and emit
        self._fetch_in_flight(keep=newest)
        self._note_pace()
        with self._phase("engine.metrics"):
            self._push_metrics()
        # step boundary: let pending add_request/cancel callbacks run
        with self._phase("engine.yield", span=False):
            await asyncio.sleep(0)

    def _note_pace(self):
        """The end of an iteration that ran something, on the loop's
        own clock: its seconds so far outside ``engine.idle``, and those
        of them blocked on the device in the two fetch phases."""
        spent = self._phase_s
        self._pace.append((time.perf_counter() - spent["engine.idle"],
                           spent["engine.decode.fetch"] + spent["engine.prefill.fetch"]))

    def _device_waits(self) -> bool:
        """Whether the loop is the one waited for: over its last
        AHEAD_WINDOW iterations it was blocked on the device for less
        than AHEAD_BLOCKED_SHARE of its time.  False until an iteration
        has been clocked."""
        (t0, blocked0), (t1, blocked1) = self._pace[0], self._pace[-1]
        return blocked1 - blocked0 < AHEAD_BLOCKED_SHARE * (t1 - t0)

    def _note_stall(self):
        """End a slice of the loop (called after every prefill and every
        iteration).  One that took over STALL_S outside ``engine.idle``
        is counted and logged with the phases its time went to; one in
        which a program compiled is logged at INFO, with each program's
        split of the compile ledger, and not counted."""
        from ray_tpu._private import profiling as _profiling

        now = time.perf_counter()
        lowered = _profiling.programs_lowered()
        compiled = lowered != self._slice_lowered
        programs = self._compiled_since_last() if compiled else ""
        spent = {n: s - self._slice_before[n] for n, s in self._phase_s.items()}
        took_s = now - self._slice_t0 - spent["engine.idle"]
        if took_s > STALL_S:
            if not compiled:
                self._counts["stalls"] += 1
                self._counts["stall_s"] += took_s
            by_phase = " ".join(
                f"{n}={1000 * s:.0f}"
                for n, s in sorted(spent.items(), key=lambda kv: -kv[1]) if s >= 0.0005
            )
            logger.log(
                logging.INFO if compiled else logging.WARNING,
                "llm engine %s: %.0f ms at step %d, waiting=%d running=%d; ms by phase: %s%s",
                "compiled" if compiled else "slow iteration",
                1000 * took_s, self.step_count, len(self.waiting),
                sum(1 for r in self.slots if r is not None), by_phase,
                "; programs, ms: " + programs if compiled else "",
            )
        self._slice_t0 = now
        self._slice_before = dict(self._phase_s)
        self._slice_lowered = lowered

    def _compiled_since_last(self) -> str:
        """The programs that compiled since this was last asked (in the
        slice that ends, as every slice that compiled asks), each with
        its milliseconds of trace, lowering and backend: a compile, or
        the load of what the persistent cache held."""
        from ray_tpu._private import profiling as _profiling

        before, self._ledger_seen = self._ledger_seen, _profiling.jit_stats()
        parts = []
        for name, rec in self._ledger_seen.items():
            was = before.get(name, {})
            n, trace, lower, miss, hit = (rec[k] - was.get(k, 0) for k in (
                "lowerings", "trace_s", "lower_s", "backend_miss_s", "backend_hit_s"))
            if n:
                parts.append(f"{name} x{n} trace={1000 * trace:.0f} lower={1000 * lower:.0f} "
                             f"backend_miss={1000 * miss:.0f} backend_hit={1000 * hit:.0f}")
        return ", ".join(parts)

    def _reap(self):
        """Step-boundary cleanup: cancelled lanes leave, blocks freed."""
        for i, req in enumerate(self.slots):
            if req is not None and req.cancelled:
                self.slots[i] = None
                self._finish(req, "cancelled")

    async def _join_waiters(self, loop) -> int:
        """Admit waiting requests into free lanes — the continuous-batch
        join point: new requests enter at a step boundary instead of
        waiting for the running batch to drain."""
        joined = 0
        for i in range(len(self.slots)):
            if not self._lane_is_free(i):
                continue
            with self._phase("engine.admit"):
                req = self._next_admissible()
            if req is None:
                break
            req.slot = i
            self.bm.hold_state_slot(req.request_id, i)
            req.t_join = time.time()
            req.join_step = self.step_count
            self.slots[i] = req
            self._counts["joined"] += 1
            self._counts["queue_wait_s"] += req.t_join - req.t_enqueue
            try:
                await self._prefill(loop, req)
                self._note_stall()
            except Exception as e:  # noqa: BLE001 — a bad prompt must not kill the loop
                logger.exception("prefill failed for %s", req.request_id)
                self.slots[i] = None
                req.finish_reason = f"error: {type(e).__name__}"
                self._finish(req, req.finish_reason)
                continue
            joined += 1
        return joined

    def _lane_is_free(self, i: int) -> bool:
        """Nobody holds lane ``i``, or its holder's every token has been
        dispatched: the holder ends when the step in flight is fetched,
        its K/V writes run before anything dispatched from now on, and a
        successor that joins now leaves the lane empty for no step."""
        req = self.slots[i]
        return req is None or req.dispatched >= req.max_tokens

    def _vacate(self, req: _Request):
        """``req`` leaves its lane, unless a successor has it already."""
        if self.slots[req.slot] is req:
            self.slots[req.slot] = None

    def _clear_row(self, req: _Request):
        """``req`` runs no further decode step: where the device still
        counts some for it, its lane's row is cleared, by one small
        program dispatched behind the steps it was in.  A lane that ends
        by length needs none (its count reached 0 on the device in the
        step the host stopped counting it), and by then the row may be
        a successor's.  Called from the loop's thread between its
        dispatches, never beside one."""
        if req.row_live and req.dispatched < req.max_tokens:
            self._lanes = self._clear_lane(self._lanes, np.int32(req.slot))
            self._counts["lane_edits"] += 1
        req.row_live = False

    @staticmethod
    def _kv_need(req: _Request) -> int:
        """Remaining KV reservation.  Invariant under preemption folds:
        after a fold, len(prompt) grew by exactly the generated tokens it
        absorbed, so the need is always len(prompt0) + max_tokens."""
        return len(req.prompt) + req.max_tokens - req.generated

    def _next_admissible(self) -> Optional[_Request]:
        if not self._fair_dirty:
            # fast path: all waiting traffic is anonymous default-tenant
            # standard class — plain FIFO, identical to the pre-tenant
            # engine (this is the high-throughput bench path)
            while self.waiting:
                req = self.waiting.popleft()
                if req.cancelled:
                    self._finish(req, "cancelled")
                    continue
                need = self._kv_need(req)
                if not self.bm.can_allocate(need):
                    # head-of-line blocks until capacity frees: put it
                    # back and stop (FIFO — no small-request overtaking)
                    self.waiting.appendleft(req)
                    return None
                self.bm.allocate(req.request_id, need)
                return req
            return None
        return self._next_admissible_fair()

    def _next_admissible_fair(self) -> Optional[_Request]:
        """Weighted-fair admission: per tenant, the head is its best
        (priority desc, then admission order — no intra-tenant
        overtaking) waiting request; across tenants, heads are served in
        ascending DRF dominant share over {KV blocks, decode lanes}
        (weights from ``tenant_weights``).  Work-conserving: a head that
        does not fit the pool is skipped, and the skipped tenant's low
        share makes it first in line once capacity frees."""
        if not self.waiting:
            self._fair_dirty = False
            return None
        alive = []
        for req in self.waiting:
            if req.cancelled:
                self._finish(req, "cancelled")
            else:
                alive.append(req)
        if len(alive) != len(self.waiting):
            self.waiting = collections.deque(alive)
        if not alive:
            self._fair_dirty = False
            return None
        heads: Dict[str, _Request] = {}
        for req in alive:
            cur = heads.get(req.tenant)
            if cur is None or (-req.priority, req.seq) < (-cur.priority, cur.seq):
                heads[req.tenant] = req
        usage: Dict[str, Dict[str, float]] = {}
        for r in self.slots:
            if r is None:
                continue
            u = usage.setdefault(r.tenant, {"kv": 0.0, "lanes": 0.0})
            u["kv"] += self.bm.blocks_held(r.request_id)
            u["lanes"] += 1.0
        totals = {
            "kv": float(self.bm.num_blocks - 1),
            "lanes": float(self.config.max_batch_size),
        }
        weights = self.config.tenant_weights

        def rank(t: str):
            share = tenants_mod.dominant_share(
                usage.get(t, {}), totals, float(weights.get(t, 1.0))
            )
            h = heads[t]
            return (share, -h.priority, h.seq)

        for t in sorted(heads, key=rank):
            req = heads[t]
            need = self._kv_need(req)
            if self.bm.can_allocate(need):
                self.waiting.remove(req)
                self.bm.allocate(req.request_id, need)
                return req
        return None

    # -- priority preemption (preempt-by-recompute) ----------------------
    def _preempt_victim(self) -> tuple:
        """When a higher-priority request has starved past
        ``preempt_wait_s`` and cannot join (no lane, or KV pool full):
        the cheapest strictly-lower-priority running lane to evict, and
        the request it makes room for; else (None, None).  At most one
        victim per step boundary — the loop converges over steps instead
        of mass-evicting on a transient spike."""
        nobody = (None, None)
        if not self._fair_dirty or not self.waiting:
            return nobody
        cand = None
        for req in self.waiting:
            if req.cancelled:
                continue
            if cand is None or (-req.priority, req.seq) < (-cand.priority, cand.seq):
                cand = req
        if cand is None:
            return nobody
        now = time.time()
        if now - (cand.t_enqueue or cand.t_submit) < self.config.preempt_wait_s:
            return nobody
        if (any(self._lane_is_free(i) for i in range(len(self.slots)))
                and self.bm.can_allocate(self._kv_need(cand))):
            return nobody  # joins normally this boundary; nothing to evict
        victims = [
            r for r in self.slots
            if r is not None and not r.cancelled and r.priority < cand.priority
        ]
        if not victims:
            return nobody
        # cheapest recompute first: lowest priority, least generated
        # (smallest refill), youngest lane
        return min(victims, key=lambda r: (r.priority, r.generated, -r.t_join)), cand

    def _preempt(self, req: _Request, for_req: Optional[_Request] = None):
        """Evict a running lane by recompute: free its KV pages, fold the
        tokens generated so far into its prompt, and re-queue it.  On
        resume, prefill replays the folded context and samples the next
        token — under greedy decoding that argmax is exactly the token
        the uninterrupted run would have produced (parity-tested).
        Nothing of ``req`` may be in flight: what is folded is what was
        emitted, and the client was sent every token of it."""
        import os

        from ray_tpu._private.chaos import CHAOS

        if req.slot >= 0:
            self.slots[req.slot] = None
        self._clear_row(req)
        req.slot = -1
        self.bm.free(req.request_id)
        # Chaos fault point: "@serve.preempt.evict:kill:at=N" dies after
        # the pages are freed but before the requeue — the replica-crash
        # window the zero-leak drill drives.
        if CHAOS.active and CHAOS.maybe_kill("serve.preempt.evict"):
            logger.warning("chaos: killing replica mid-preemption (evict)")
            os._exit(1)
        req.prompt = list(req.prompt) + req.tokens[req.folded:]
        req.folded = len(req.tokens)
        req.t_enqueue = time.time()
        req.preemptions += 1
        self._preempt_total += 1
        self._events.append({
            "type": "preemption",
            "t": req.t_enqueue,
            "victim": req.request_id,
            "victim_slo": req.slo,
            "victim_tenant": self._tenant_label(req.tenant),
            "for": for_req.request_id if for_req is not None else "",
            "generated": req.generated,
            "preemptions": req.preemptions,
        })
        try:
            from ray_tpu._private import telemetry

            telemetry.count_serve_preemption(self.config.name, req.slo)
        except Exception:  # noqa: BLE001
            pass
        if CHAOS.active and CHAOS.maybe_kill("serve.preempt.requeue"):
            logger.warning("chaos: killing replica mid-preemption (requeue)")
            os._exit(1)
        self.waiting.append(req)
        self._fair_dirty = True

    async def _dispatch(self, loop, name: str, call):
        """Run one jit call in the executor thread, timed as ``<name>.run``
        inside ``<name>.await``.  ``call`` donates the cache and rebinds
        it (``_run_on_cache``) in one synchronous stretch of that
        thread; the await is shielded, so a ``stop()`` that cancels the
        loop task here leaves the call to finish and finds the engine
        bound to live buffers."""

        def run():
            with self._phase(name + ".run"):
                return call()

        with self._phase(name + ".await", span=False):
            try:
                call_under_way = self._dispatching = loop.run_in_executor(None, run)
            except RuntimeError:
                # the default executor is shut down: the process is on
                # its way out and the loop ends here.  Nobody is sent
                # FINISHED, so open streams break as a dead replica's do
                self._stopped = True
                raise
            try:
                return await asyncio.shield(call_under_way)
            finally:
                if call_under_way.done():
                    self._dispatching = None

    async def _prefill(self, loop, req: _Request):
        """Dispatch one prompt's prefill behind whatever is in flight:
        one program, or, where the family states a ``prefill_chunk`` and
        the prompt is longer, one program a chunk, in order, each
        reading what the ones before it wrote (K and V through the block
        table, the lane's state from its slot; the first starts from a
        state of zeros).  The last one's token is the request's first:
        it goes to the lane's place on the device with the lane's row
        (``put_lane``, in the same dispatch), for the next decode step,
        and to the host when its turn to be fetched comes."""
        n = len(req.prompt)
        most = min(self._spec.prefill_chunk or self.max_ctx, self.max_ctx)
        lane = np.int32(req.slot)
        for start in range(0, n, most):
            with self._phase("engine.prefill.build"):
                m = min(most, n - start)
                bucket = self._prefill_bucket(m, most)
                toks = np.zeros((1, bucket), dtype=np.int32)
                toks[0, :m] = req.prompt[start:start + m]
                self.bm.advance(req.request_id, m)
                last = start + m == n
                temp = np.array([req.temperature], dtype=np.float32)
                inputs = [toks, self.bm.phys_indices(req.request_id, start + m, bucket, start=start),
                          np.array([m - 1], dtype=np.int32), temp, self._next_rng()]
                if last or self._spec.reads_cache:
                    table = self.bm.block_table(req.request_id, self.bm.blocks_needed(self.max_ctx))
                if self._spec.reads_cache:
                    inputs += [np.int32(start), table, lane]
                counts = {"prompt_tokens": m, "prefill_bucket_tokens": bucket, "prefill_chunks": 1,
                          "state_bytes": 2 * self._state_bytes // self.config.max_batch_size}
                if last:
                    # the lane's row: n positions cached, a decode step
                    # for every token but this program's own, its blocks
                    # (all zeros where the prefill's token is its last)
                    left = req.max_tokens - req.dispatched - 1
                    row = np.concatenate([np.array([n, left], dtype=np.int32), table]) * np.int32(left > 0)

            def call():
                first_tok = self._run_on_cache(self._prefill_jit, *inputs)
                if last:
                    self._lane_tok, self._lanes = self._put_lane(
                        self._lane_tok, self._lanes, first_tok, lane, row, temp)
                    # said here, beside the edit: a stop() that cancels
                    # the await below must still find the row to clear
                    req.row_live = True
                    self._counts["lane_edits"] += 1
                return first_tok

            first_tok = await self._dispatch(loop, "engine.prefill", call)
            # a chunk before the last is fetched for its counters alone
            self._enqueue(_InFlight(first_tok, [(0, req)] if last else [], counts, decode=False))
        req.dispatched += 1

    async def _dispatch_decode(self, loop) -> Optional[_InFlight]:
        """Dispatch one decode step for every lane with tokens left, on
        the lanes' newest tokens and on their lengths, block tables,
        write slots and temperatures where all of them lie on the
        device; None where no lane has any.  Those follow from how many
        tokens a lane was DISPATCHED for, never from what they were: the
        device advances them itself (``advance_lanes``), so the step is
        dispatched while the one before it runs and is sent nothing.
        The host keeps its mirror (``bm.seq_len``, ``dispatched``) for
        what only it decides: who holds a lane, and who has ended."""
        with self._phase("engine.admit"):
            # a lane that ends by length is known a step ahead, here and
            # on the device, and left out; one that ends by eos_token or
            # cancel() is found out when its token comes, a lane-step
            # late, or right here, and its row is cleared where it is
            lanes = []
            for i, req in enumerate(self.slots):
                if self._lane_is_free(i):
                    continue
                if req.cancelled:
                    self._clear_row(req)
                else:
                    lanes.append((i, req))
        if not lanes:
            return None
        with self._phase("engine.decode.build"):
            bs = self.bm.block_size
            unfetched = sum(p.decode for p in self._inflight)
            counts = {"decodes_chained": int(unfetched >= 1), "decodes_ahead": int(unfetched >= 2),
                      "state_bytes": 2 * self._state_bytes}
            if "kv_positions_gathered" not in self._counter_names:
                # every cached position of every lane is read; a family
                # that reads a selection counts what it reads itself
                cached = [self.bm.seq_len(req.request_id) for _, req in lanes]
                counts.update(kv_positions_attended=sum(cached),
                              kv_positions_gathered=sum(-(-n // bs) * bs for n in cached))  # whole pages
            # dispatched from this thread: both dispatches in the one
            # executor hop cost a v5e's host 0.4 ms a step more than one
            # here and one there (PERF.md §6, PR 34)
            rows, step, lengths, tables, write_phys, rng = self._advance_lanes(self._lanes, self._decode_key)
            inputs = (self._lane_tok, lengths, tables, write_phys, self._lanes["temp"], rng)
            counts["decode_host_bytes"] = _host_bytes((*self._lanes.values(), self._decode_key, *inputs))

        def call():
            # the state is rebound only once the step is dispatched: a
            # call that raises leaves device and mirror where they were
            nxt = self._run_on_cache(self._decode_jit, *inputs)
            self._lanes, self._lane_tok = dict(self._lanes, rows=rows, step=step), self._lanes_of(nxt)
            return nxt

        nxt = await self._dispatch(loop, "engine.decode", call)
        for _, req in lanes:
            req.dispatched += 1
            self.bm.advance(req.request_id, 1)
        return self._enqueue(_InFlight(nxt, lanes, counts))

    def _enqueue(self, prog: _InFlight) -> _InFlight:
        """A dispatched program joins those in flight, and its tokens
        start for the host where it ends on the device, not where the
        loop comes to fetch it: the fetch of a finished program then
        finds them there."""
        prog.out.copy_to_host_async()
        self._inflight.append(prog)
        return prog

    def _fetch_in_flight(self, keep: Optional[_InFlight] = None):
        """Fetch the programs in flight, oldest first, and emit their
        tokens; all of them, or all dispatched before ``keep``, the
        newest decode step.  While the device is the one that waits
        (``_device_waits``) the loop blocks only on those dispatched
        before the decode step before ``keep`` and fetches of the others
        what the device has finished (``is_ready()``): that step stays
        queued behind what runs."""
        wait_before = keep
        if keep is not None and self._device_waits():
            wait_before = next((p for p in reversed(self._inflight) if p.decode and p is not keep),
                               self._inflight[0])
        blocking = True
        while self._inflight and self._inflight[0] is not keep:
            blocking = blocking and self._inflight[0] is not wait_before
            if not (blocking or self._inflight[0].out.is_ready()):
                break
            self._fetch(self._inflight.popleft())

    def _fetch(self, prog: _InFlight):
        with self._phase("engine.decode.fetch" if prog.decode else "engine.prefill.fetch"):
            out = np.asarray(prog.out).reshape(-1)
        with self._phase("engine.emit"):
            # counted here, with the program's own counters, so that
            # stats() at any instant counts whole programs
            self.step_count += int(prog.decode)
            self._count_program(out[len(out) - len(self._counter_names):].tolist())
            for name, n in prog.counts.items():
                self._counts[name] += n
            now = time.time()
            emitted = 0
            for i, req in prog.lanes:
                if self._by_id.get(req.request_id) is not req:
                    # ended by eos_token or cancel() after this was
                    # dispatched: the token is dropped
                    self._counts["lane_steps_discarded"] += 1
                    continue
                t = int(out[i])
                self._emit(req, t, now=now)
                emitted += 1
                if req.cancelled or self._is_finished(req, t):
                    self._vacate(req)
                    self._finish(req, req.finish_reason or "length")
            if emitted:
                self._tok_window.append((now, emitted))
        self._note_stall()

    # -- bookkeeping -----------------------------------------------------
    def _count_program(self, counted):
        """Add what one program counted (the family's COUNTERS, fetched
        with its tokens) to stats()."""
        for name, n in zip(self._counter_names, counted):
            self._counts[name] += n

    def _emit(self, req: _Request, token: int, now: float):
        req.tokens.append(token)
        req.generated += 1
        self._total_tokens += 1
        if self._fair_dirty or req.tenant != tenants_mod.DEFAULT_TENANT:
            self._tenant_tok_window.append((now, req.tenant, 1))
        if req.t_first_token == 0.0:
            req.t_first_token = now
            self._counts["first_tokens"] += 1
            self._counts["join_to_first_token_s"] += now - req.t_join
        req.emit_times.append(now)
        req.out.put_nowait(
            {
                "request_id": req.request_id,
                "token": token,
                "index": req.generated - 1,
            }
        )

    def token_taken(self, req: _Request):
        """A stream's coroutine took a token's event off ``req.out``
        (called by whoever awaits that queue on behalf of a client, as
        ``LLMServer`` does): the seconds the token lay there, waiting for
        the loop to reach that coroutine, go to ``egress_loop_s``.  Both
        stamps are this thread's, so the difference is not clamped."""
        counts = self._counts
        counts["tokens_out"] += 1
        counts["egress_loop_s"] += time.time() - req.emit_times.popleft()

    def _is_finished(self, req: _Request, token: int) -> bool:
        eos = self.config.eos_token
        if eos >= 0 and token == eos:
            req.finish_reason = "eos"
            return True
        if req.generated >= req.max_tokens:
            req.finish_reason = "length"
            return True
        return False

    def _finish(self, req: _Request, reason: str):
        """Terminal bookkeeping — the ONLY place a request leaves the
        engine: frees blocks, emits the sentinel, records spans/TTFT."""
        if self._by_id.pop(req.request_id, None) is None:
            return
        self._clear_row(req)
        self.bm.free(req.request_id)
        req.finish_reason = req.finish_reason or reason
        req.t_done = time.time()
        req.finish_step = self.step_count
        req.out.put_nowait(FINISHED)
        self._record_spans(req)
        self._observe_ttft(req)

    # -- observability ---------------------------------------------------
    def _mint_trace(self) -> tuple:
        from ray_tpu.util import tracing

        ctx = tracing.current_context()
        trace_id = ctx[0] if ctx else uuid.uuid4().hex
        parent = ctx[1] if ctx else None
        return (trace_id, uuid.uuid4().hex[:16], parent)

    def _record_spans(self, req: _Request):
        """serve.request -> {serve.ingress, serve.queue, serve.prefill,
        serve.decode}: the per-request latency decomposition that
        critical-path analysis surfaces (docs/serving.md).  The first
        only where the request came through a handle (``t_sent``)."""
        try:
            from ray_tpu.util import tracing

            trace_id, root_id, parent = req.trace
            end = req.t_done or time.time()
            # the chain begins where the caller's clock does
            start = req.t_sent or req.t_submit
            if req.t_sent:
                tracing.record_span(
                    "serve.ingress", start, req.t_submit,
                    {"wire_s": req.t_rx - req.t_sent, "loop_s": req.t_submit - req.t_rx},
                    context=(trace_id, uuid.uuid4().hex[:16], root_id),
                )
            tracing.record_span(
                "serve.request", start, end,
                {
                    "request_id": req.request_id,
                    "deployment": self.config.name,
                    "tokens": req.generated,
                    "finish_reason": req.finish_reason,
                },
                context=(trace_id, root_id, parent),
            )
            t_join = req.t_join or end
            tracing.record_span(
                "serve.queue", req.t_submit, t_join, None,
                context=(trace_id, uuid.uuid4().hex[:16], root_id),
            )
            if req.t_join:
                t_first = req.t_first_token or end
                tracing.record_span(
                    "serve.prefill", req.t_join, t_first, None,
                    context=(trace_id, uuid.uuid4().hex[:16], root_id),
                )
                tracing.record_span(
                    "serve.decode", t_first, end, {"tokens": req.generated},
                    context=(trace_id, uuid.uuid4().hex[:16], root_id),
                )
        except Exception:  # noqa: BLE001 — observability must not fail serving
            pass

    def _observe_ttft(self, req: _Request):
        if not req.t_first_token:
            return
        self._ttft_recent.append(req.t_first_token - req.t_submit)
        try:
            from ray_tpu._private import telemetry

            telemetry.observe_serve_ttft(
                self.config.name, req.t_first_token - req.t_submit
            )
        except Exception:  # noqa: BLE001
            pass

    def _tokens_per_s(self) -> float:
        now = time.time()
        window = [(t, n) for t, n in self._tok_window if now - t <= 5.0]
        if not window:
            return 0.0
        span = max(now - window[0][0], 1e-3)
        return sum(n for _, n in window) / span

    def _ttft_p95(self) -> Optional[float]:
        if not self._ttft_recent:
            return None
        vals = sorted(self._ttft_recent)
        return vals[min(len(vals) - 1, int(0.95 * len(vals)))]

    def _push_metrics(self, force: bool = False):
        now = time.time()
        if not force and now - self._last_metrics_push < 1.0:
            return
        self._last_metrics_push = now
        # brownout control tick rides the 1 Hz metrics cadence (inert
        # when slo_ttft_s == 0 — the controller is disabled)
        if self._degrade.enabled:
            before = self._degrade.level
            level = self._degrade.tick(self._ttft_p95(), len(self.waiting))
            if level != before:
                self._events.append({
                    "type": "degradation",
                    "t": now,
                    "from": before,
                    "to": level,
                    "queue": len(self.waiting),
                })
                logger.info(
                    "brownout level %d -> %d (queue=%d)",
                    before, level, len(self.waiting),
                )
        try:
            from ray_tpu._private import telemetry

            name = self.config.name
            telemetry.set_serve_queue_depth(name, len(self.waiting))
            telemetry.set_serve_kv_blocks(name, self.bm.blocks_in_use)
            telemetry.set_serve_tokens_per_s(name, self._tokens_per_s())
            if self._degrade.enabled:
                telemetry.set_serve_degradation(name, self._degrade.level)
            for tenant, rate in self._tenant_tokens_per_s().items():
                telemetry.set_serve_tenant_tokens_per_s(name, tenant, rate)
            # Device memory attribution for the paged KV cache (no-op on
            # backends without memory_stats; internally rate-limited).
            from ray_tpu._private import profiling as profiling_mod

            profiling_mod.report_device_memory()
            if self._shed_unreported:
                pending, self._shed_unreported = self._shed_unreported, {}
                for (where, tenant), n in pending.items():
                    telemetry.count_serve_shed(name, where, n, tenant=tenant)
        except Exception:  # noqa: BLE001
            pass

    def _tenant_tokens_per_s(self) -> Dict[str, float]:
        """Per-tenant token rate over the 5 s window, labels clamped to
        the registered domain (empty for pure anonymous traffic — the
        window is only fed once identity appears)."""
        now = time.time()
        window = [(t, ten, n) for t, ten, n in self._tenant_tok_window
                  if now - t <= 5.0]
        if not window:
            return {}
        span = max(now - window[0][0], 1e-3)
        out: Dict[str, float] = {}
        for _, ten, n in window:
            label = self._tenant_label(ten)
            out[label] = out.get(label, 0.0) + n
        return {k: v / span for k, v in out.items()}
