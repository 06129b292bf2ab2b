"""Continuous-batching serving engine (iteration-level scheduling).

Orca-style iteration-level scheduling (Yu et al., OSDI '22) over the
vLLM paged KV cache (Kwon et al., SOSP '23), restated for TPU static
shapes: every device program in the serving hot path comes from ONE
compiled step family per bucketed shape —

  - ``paged_decode_step`` at batch buckets (1, 2, 4, ..., max_batch):
    one token for every RUNNING sequence through the fused
    paged-attention update kernel (ops/paged_attention.py);
  - ``paged_prefill_chunk`` at the fixed chunk bucket: one slice of
    ONE admitted prompt, interleaved with the decode batches so long
    prompts never head-of-line-block token generation; its attention
    walks the live blocks of the sequence's table only
    (``paged_prefill_attention``);
  - ``paged_prefill_chunk_with_decode`` at (chunk bucket, max_batch):
    the chunk AND the decode batch in one layer scan, for an iteration
    that has both, so that the weights stream once for it and the host
    launches and waits once (piggybacked decodes: Sarathi-Serve). Only
    where the model's serving object offers it (``models/llama.py``
    ``LlamaServing``: the plain cache on one chip); without it such an
    iteration runs the two programs above one after the other.

Recompiles are therefore bounded by ``len(decode_buckets) + 2`` and
counted (``serve.compile.*`` counters + StepMetrics.record_compile).

**The engine never holds logits.** Every jitted program ends in the greedy
head (``ops/sampling.py``): it returns, before the cache, a token (int32,
the first index of the logits' maximum) and a finite flag (bool, every
logit of the row finite) for each row it scored: ``decode`` two arrays of
[bucket], ``prefill`` two scalars, ``prefill+decode`` the chunk's two and
the rows' two, ``verify`` its (out, commit_len, fin_ok). A step's ``.wait``
span fetches those few bytes (``_fetch``; counted as ``fetch_bytes`` on the
span and ``step_fetch_bytes_total`` in the registry), ``_commit_rows`` and
``_prefill_done`` take tokens, and the NaN screens read the flags.

Scheduling per ``step()`` iteration:
  1. admit waiting requests while the free-block budget covers their
     prompt (plus one decode block of headroom);
  2. run one prefill chunk for the oldest admitted prompt, allocating
     its blocks lazily per chunk;
  3. run one decode batch over all RUNNING sequences, allocating each
     sequence's next block as it crosses a block boundary and
     PREEMPTING-BY-EVICTION (youngest RUNNING sequence back to the
     waiting queue, blocks freed, recompute-on-readmission) when the
     pool runs dry. With the program that carries the batch, 2 and 3
     are planned together, launched once and committed in this order;
     a prompt whose last chunk ran so decodes from the next iteration.

Telemetry: queue depth, batch occupancy, block-pool utilization and
prefill-vs-decode time share per iteration through StepMetrics, counter
markers on every scheduling event, and a ``serve.*`` span at every phase
boundary of an iteration (``_span``): a profiler annotation on the device
trace's clock while a profiler session runs, and the same name in the
``RequestTracer`` when that is on.

Overload + fault contract (PR 14):

  - ``submit()`` returns an :class:`Admission` decision instead of
    queueing unboundedly: a bounded waiting queue, a token-bucket rate
    limit and a free-block-aware overcommit estimate each produce a
    deterministic ``rejected`` outcome with a cause.
  - Requests carry optional TTFT/total deadlines and a priority; the
    scheduler sheds queued requests whose deadline has already passed
    (engine-clock arithmetic only, so shedding replays bit-identically)
    and evicts lowest-priority-first under pool pressure, shrinking a
    prefill chunk's live span (same compiled shape) before evicting.
  - A request whose prefill raises, or whose prefill/decode logits go
    non-finite, is QUARANTINED: blocks released, marked failed with a
    cause, the decode batch re-driven without it — one poisoned request
    never takes down the engine.
  - With a journal path, every accepted request and emitted token is
    appended to a crash-recoverable JSONL journal (inference/journal.py);
    a fresh engine's :meth:`InferenceEngine.recover` re-drives to
    bit-identical token streams. ``faults.py`` points ``serve.admit.*``/
    ``serve.prefill.*``/``serve.decode.*``/``serve.swap.*`` let the
    crash-matrix test kill the engine at every stage.

Every request the engine ever saw ends in exactly one terminal state —
finished, rejected, shed, or failed — with a cause (:meth:`outcomes`);
nothing is silently dropped.
"""
from __future__ import annotations

import dataclasses
import itertools
import os
import signal
import threading
import time
import warnings
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from .. import envs
from ..testing import faults
from ..observability.flight_recorder import (FlightRecorder,
                                             flight_recorder_enabled)
from ..observability.histogram import LogHistogram
from ..observability.registry import MetricsRegistry
from ..observability.metrics import StepMetrics
from ..observability.request_trace import RequestTracer
from ..observability.trace import record_counter, span
from .journal import EngineJournal, JournalCompatError, read_journal
from .kv_cache import (BlockPool, PrefixCache, SlotPool, pad_table,
                       pool_bytes_per_rank)

ENV_TRACE_REQUESTS = "PADDLE_TPU_TRACE_REQUESTS"
ENV_SERVE_MAX_QUEUE = "PADDLE_TPU_SERVE_MAX_QUEUE"
ENV_SERVE_RATE = "PADDLE_TPU_SERVE_RATE"
ENV_SERVE_BURST = "PADDLE_TPU_SERVE_BURST"
ENV_SERVE_OVERCOMMIT = "PADDLE_TPU_SERVE_OVERCOMMIT"
ENV_SERVE_NAN_CHECK = "PADDLE_TPU_SERVE_NAN_CHECK"
ENV_SERVE_JOURNAL = "PADDLE_TPU_SERVE_JOURNAL"
ENV_SERVE_JOURNAL_FSYNC = "PADDLE_TPU_SERVE_JOURNAL_FSYNC"
ENV_SERVE_PREFIX_CACHE = "PADDLE_TPU_SERVE_PREFIX_CACHE"
ENV_SERVE_KV_DTYPE = "PADDLE_TPU_SERVE_KV_DTYPE"
ENV_SERVE_SPEC = "PADDLE_TPU_SERVE_SPEC"
ENV_SERVE_SPEC_K = "PADDLE_TPU_SERVE_SPEC_K"
ENV_SERVE_MP = "PADDLE_TPU_SERVE_MP"

WAITING, PREFILL, RUNNING, FINISHED = "waiting", "prefill", "running", \
    "finished"
SHED, FAILED = "shed", "failed"


class PoisonError(RuntimeError):
    """A poisoned per-request computation, attributable to ``rid``.
    Raised by the engine's own non-finite logit screens, and usable from
    a fault-injection corrupt callable to simulate a request whose
    device computation raises (``PoisonError(ctx['rids'][0])``)."""

    def __init__(self, rid: int, cause: str = "poisoned request"):
        super().__init__(f"request {rid}: {cause}")
        self.rid = rid
        self.cause = cause


@dataclasses.dataclass
class Request:
    """One generation request. ``arrival`` is seconds from engine start
    (wall mode) or the iteration index (deterministic replay mode).
    ``ttft_deadline``/``deadline`` are engine-clock spans from arrival
    (first token / full completion); a queued request past its deadline
    is shed. Higher ``priority`` survives eviction longer."""
    prompt: Sequence[int]
    max_new_tokens: int = 16
    request_id: Optional[int] = None
    eos_id: Optional[int] = None
    arrival: float = 0.0
    priority: int = 0
    ttft_deadline: Optional[float] = None
    deadline: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class Admission:
    """The ``submit()`` outcome: accepted into the bounded queue, or
    rejected with a deterministic cause (``queue_full`` | ``overcommit``
    | ``rate_limit``)."""
    accepted: bool
    request_id: int
    cause: Optional[str] = None


class _TokenBucket:
    """``rate`` admissions per engine-clock unit, capacity ``burst``.
    Refill arithmetic uses the ENGINE clock (iteration index in
    deterministic replay), never wall time, so admission decisions
    replay bit-identically."""

    def __init__(self, rate: float, burst: float):
        self.rate = float(rate)
        self.burst = float(burst)
        self._level = float(burst)
        self._at = 0.0

    def take(self, now: float) -> bool:
        if now > self._at:
            self._level = min(self.burst,
                              self._level + (now - self._at) * self.rate)
            self._at = now
        if self._level < 1.0:
            return False
        self._level -= 1.0
        return True


class AdmissionController:
    """Explicit admit/reject decision at ``submit()``.

    Three independent valves, checked in order (first hit wins):
    ``queue_full`` (bounded waiting queue), ``overcommit`` (the worst-
    case block demand of everything queued+active plus this request
    exceeds ``overcommit`` x the usable pool — a free-block-aware
    estimate, since admitted work is never silently dropped) and
    ``rate_limit`` (token bucket; checked last so rejected-anyway
    requests do not drain the bucket)."""

    def __init__(self, max_queue: int, rate: Optional[float],
                 burst: float, overcommit: float):
        self.max_queue = int(max_queue)
        self.overcommit = float(overcommit)
        self.bucket = _TokenBucket(rate, burst) if rate else None

    def decide(self, queue_len: int, demand_blocks: int,
               worst_blocks: int, usable_blocks: int,
               now: float) -> Optional[str]:
        """None to accept, else the rejection cause."""
        if queue_len >= self.max_queue:
            return "queue_full"
        if demand_blocks + worst_blocks > self.overcommit * usable_blocks:
            return "overcommit"
        if self.bucket is not None and not self.bucket.take(now):
            return "rate_limit"
        return None


@dataclasses.dataclass
class ServeConfig:
    block_size: int = 128
    num_blocks: int = 64          # includes the reserved null block 0
    max_batch: int = 8
    prefill_chunk: int = 64
    max_seq_len: int = 1024       # bounds the block-table width
    decode_buckets: Optional[Tuple[int, ...]] = None
    # overload valves (PR 14); None defers to the PADDLE_TPU_SERVE_*
    # knob, which in turn falls back to the documented default
    max_queue: Optional[int] = None       # default 4 x max_batch
    rate_limit: Optional[float] = None    # admissions/clock-unit; 0=off
    burst: Optional[int] = None           # default max(2, max_batch)
    overcommit: Optional[float] = None    # default 4.0 x usable blocks
    nan_check: Optional[bool] = None      # default True
    # PR 16 capacity features; None defers to the knob (both default
    # to the legacy behavior: no sharing, model-dtype fp KV)
    prefix_cache: Optional[bool] = None   # COW shared prefix blocks
    kv_dtype: Optional[str] = None        # "auto" (model dtype) | "int8"
    speculative: Optional[bool] = None    # draft + batched verification
    draft_k: Optional[int] = None         # proposals/seq/iteration (>=1)
    # tensor-parallel serving (PR 19): model-parallel degree of the
    # engine's mesh; weights slice per param_pspecs, KV pools shard by
    # kv-head. None defers to PADDLE_TPU_SERVE_MP (default 1 = the
    # single-device path, bit-identical to pre-PR-19).
    mp: Optional[int] = None

    def __post_init__(self):
        if self.decode_buckets is None:
            b, buckets = 1, []
            while b < self.max_batch:
                buckets.append(b)
                b *= 2
            self.decode_buckets = tuple(buckets) + (self.max_batch,)
        self.decode_buckets = tuple(sorted(set(self.decode_buckets)))
        if self.decode_buckets[-1] != self.max_batch:
            raise ValueError("largest decode bucket must equal max_batch")

    @property
    def max_nb(self) -> int:
        return -(-self.max_seq_len // self.block_size)


class _Seq:
    """Scheduler-side sequence state. Invariant while RUNNING:
    n_cached == len(tokens) - 1, and the next decode feeds tokens[-1]
    at position n_cached."""

    def __init__(self, req: Request, now: float):
        self.req = req
        self.tokens: List[int] = [int(t) for t in req.prompt]
        self.n_prompt = len(self.tokens)
        self.n_cached = 0
        self.blocks: List[int] = []
        # a model with recurrent state: the sequence's slot of it, held
        # exactly while it holds blocks
        self.slot: Optional[int] = None
        self.state = WAITING
        self.arrival = now
        self.order = 0                 # submission sequence number
        self.first_token_t: Optional[float] = None
        self.token_times: List[float] = []
        self.n_preempted = 0
        self.fail_cause: Optional[str] = None   # shed/quarantine cause
        self.recovered = False                  # rebuilt from a journal
        # tokens whose KV the DRAFT pools hold; always <= n_cached after
        # a verify (rejected lookahead KV is simply re-proposed over)
        self.draft_pos = 0

    @property
    def generated(self) -> List[int]:
        return self.tokens[self.n_prompt:]

    @property
    def prefill_target(self) -> int:
        # fresh prompts cache every prompt token and sample from the
        # final chunk's logits; a preempted sequence re-caches all but
        # its newest (never-fed) token and resumes decoding instead
        return len(self.tokens) - (1 if self.generated else 0)

    def done(self) -> bool:
        g = self.generated
        return (len(g) >= self.req.max_new_tokens
                or (self.req.eos_id is not None and g
                    and g[-1] == self.req.eos_id))


class _Phase:
    """One phase boundary on the engine's thread, feeding every sink from
    one call site: the profiler annotation (``observability.span``), the
    iteration's per-phase milliseconds (telemetry, flight recorder) and,
    when the request tracer is on, ``RequestTracer.phase`` under the same
    name with the enclosing phase as its parent."""

    __slots__ = ("eng", "name", "ann", "parent", "t0", "t1")

    def __init__(self, eng: "InferenceEngine", name: str, args: dict):
        self.eng, self.name = eng, name
        self.ann = span(name, **args)

    def __enter__(self) -> "_Phase":
        open_ = self.eng._open_phases
        self.parent = open_[-1] if open_ else None
        open_.append(self.name)
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def note(self, **args) -> None:
        """Arguments known only once the phase is under way."""
        self.ann.set_metadata(**args)

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter()
        self.ann.__exit__(*exc)
        eng = self.eng
        eng._open_phases.pop()
        ms = eng._phase_ms
        ms[self.name] = ms.get(self.name, 0.0) + (self.t1 - self.t0) * 1e3
        if eng.tracer is not None:
            eng.tracer.phase(self.name, self.t0, self.t1, eng.iteration,
                             self.parent)


# what a phase attempted and what of it was useful, by the argument's name
# on the span: the registry counter it adds to
_WORK_TOTALS = {"rows": "decode_rows_total", "bucket": "decode_slots_total",
                "fetch_bytes": "step_fetch_bytes_total",
                "n_live": "prefill_tokens_total",
                "chunk": "prefill_slots_total",
                "ctx_blocks": "prefill_ctx_blocks_total",
                "table_blocks": "prefill_table_blocks_total"}


def _serving_for(config):
    """The serving side of ``config``'s model: what a model hands the engine
    lives beside the model (see ``models/llama.py`` ``LlamaServing``), and
    this module imports nothing else of ``paddle_tpu.models``."""
    from ..models import deepseek, falcon_h1, llama
    if isinstance(config, deepseek.DeepSeekConfig):
        return deepseek.DeepSeekServing
    if isinstance(config, falcon_h1.FalconH1Config):
        return falcon_h1.FalconH1Serving
    return llama.LlamaServing


class InferenceEngine:
    """Continuous-batching engine over a paged KV cache.

    >>> eng = InferenceEngine(params, config, ServeConfig())
    >>> stats = eng.run([Request(prompt, max_new_tokens=32), ...])

    Greedy decoding; one engine owns its device pools, so drive it from
    a single thread."""

    def __init__(self, params: Dict[str, Any], config: Any,
                 serve: Optional[ServeConfig] = None,
                 telemetry: Optional[StepMetrics] = None,
                 record_events: bool = False,
                 trace_requests: Optional[bool] = None,
                 flight_recorder: Optional[bool] = None,
                 journal: Optional[str] = None,
                 draft_params: Optional[Dict[str, Any]] = None,
                 draft_config: Any = None):
        self.params = params
        self.config = config
        self.serve = serve or ServeConfig()
        # tensor-parallel serving (PR 19): mp > 1 runs every jitted step
        # family inside an ('mp',)-sharded mesh — weights sliced per
        # param_pspecs, KV/scale pools sharded by kv-head — while the
        # host-side scheduler (admission, shedding, quarantine, journal,
        # BlockPool, PrefixCache) stays rank-agnostic: one process
        # drives all ranks with rank-replicated block tables. Streams
        # stay token-identical to mp=1 (PARITY.md PR 19).
        self.mp = int(self.serve.mp if self.serve.mp is not None
                      else envs.get(ENV_SERVE_MP))
        if self.mp < 1:
            raise ValueError(f"ServeConfig.mp must be >= 1, got {self.mp}")
        self.mesh = None
        self.pool = BlockPool(self.serve.num_blocks, self.serve.block_size)
        # KV storage dtype: "auto" keeps the model dtype (the pre-PR-16
        # path, bit-identical); "int8" halves pool bytes with per-column
        # scale pools dequantized inside the paged kernels
        self.kv_dtype = (self.serve.kv_dtype
                         if self.serve.kv_dtype is not None
                         else envs.get(ENV_SERVE_KV_DTYPE))
        if self.kv_dtype not in ("auto", "int8"):
            raise ValueError(
                f"ServeConfig.kv_dtype must be 'auto' or 'int8', "
                f"got {self.kv_dtype!r}")
        spec = (self.serve.speculative
                if self.serve.speculative is not None
                else envs.get(ENV_SERVE_SPEC))
        # COW prefix cache: full prompt blocks stay indexed after
        # release and later identical prompts share them ref-counted
        prefix_on = (self.serve.prefix_cache
                     if self.serve.prefix_cache is not None
                     else envs.get(ENV_SERVE_PREFIX_CACHE))
        # what the model brings (see _serving_for); it refuses loudly, here,
        # what it cannot run
        self.model = _serving_for(config)
        self.model.refuse(mp=self.mp, kv_dtype=self.kv_dtype,
                          speculative=bool(spec),
                          draft=draft_params is not None,
                          prefix_cache=bool(prefix_on))
        # the cache: a tuple of arrays, each indexed by block id on axis 1.
        # Copy-on-write, the liveness check and the pool's bytes treat it so;
        # only the model's own programs know what an array holds (Llama:
        # (k, v[, k_scale, v_scale]); latent attention: one pool)
        self.kv: Tuple = self.model.init_cache(
            config, self.serve.num_blocks, self.serve.block_size,
            self.kv_dtype)
        self.kv_draft: Tuple = ()
        # recurrent state, for a model that has it (``init_state``): a
        # second tuple of arrays, each indexed by SLOT on axis 1, one slot a
        # running sequence and slot 0 the null slot. Slots are given and
        # freed with the blocks; copy-on-write never touches them (nothing
        # shares a state); the steps take them after the cache, and a slot
        # a row as their last input
        init_state = getattr(self.model, "init_state", None)
        self.slots: Optional[SlotPool] = None
        self.state: Tuple = ()
        if init_state is not None:
            self.slots = SlotPool(self.serve.max_batch + 1)
            self.state = init_state(config, self.slots.num_slots)
        self.cache: Optional[PrefixCache] = \
            PrefixCache(self.pool) if prefix_on else None
        self._cow_copies = 0
        # speculative decoding (PR 18): a draft model proposes up to K
        # tokens per sequence per iteration and ONE batched verify pass
        # scores all K+1 positions. Emitted tokens are always the BASE
        # model's greedy argmax, so streams are bit-identical to
        # sequential decode regardless of draft quality (PARITY.md) —
        # the draft only moves latency.
        self.speculative = bool(spec)
        self.draft_k = int(self.serve.draft_k
                           if self.serve.draft_k is not None
                           else envs.get(ENV_SERVE_SPEC_K))
        if self.draft_k < 1:
            raise ValueError(f"draft_k must be >= 1, got {self.draft_k}")
        self.draft_params: Optional[Dict[str, Any]] = None
        self.draft_config: Any = None
        self._draft_frozen: Optional[Tuple] = None
        self._spec_proposed = 0
        self._spec_accepted = 0
        if self.speculative:
            if draft_params is None:
                # default draft: the base model truncated to its first
                # layer, sharing embedding/head weights by reference
                draft_params, draft_config = self.model.draft(params, config)
            elif draft_config is None:
                raise ValueError("draft_params given without draft_config")
            self.draft_params = draft_params
            self.draft_config = draft_config
            self._draft_frozen = self.model.freeze(draft_config)
            # the draft pools mirror the base pool's block geometry (one
            # shared block table per sequence) but always store the
            # model dtype: draft KV only shapes proposals, never output
            # bytes, so int8 buys nothing there
            self.kv_draft = self.model.init_cache(
                draft_config, self.serve.num_blocks, self.serve.block_size,
                "auto")
        if self.mp > 1:
            # the mesh, the weights sliced over it and every pool sharded by
            # kv head: the model knows its own parameter tree
            (self.mesh, self.params, self.kv, self.draft_params,
             self.kv_draft) = self.model.place(
                self.mp, params, config, self.kv, self.draft_params,
                self.draft_config, self.kv_draft)
        self.metrics = telemetry
        self.record_events = record_events
        # request-lifecycle tracing is measurement-only: spans are recorded
        # from timestamps the scheduler already crosses, never consulted by
        # it, so tokens are bit-identical with tracing on or off
        if trace_requests is None:
            trace_requests = envs.get(ENV_TRACE_REQUESTS)
        self.tracer: Optional[RequestTracer] = \
            RequestTracer() if trace_requests else None
        self.recorder: Optional[FlightRecorder] = (
            FlightRecorder(source="engine")
            if flight_recorder_enabled(flight_recorder) else None)
        # streaming SLO histograms, always on (one list increment per
        # token); values are in the ENGINE clock — seconds in wall mode,
        # iterations in deterministic mode — matching stats()
        self.slo: Dict[str, LogHistogram] = {
            "ttft": LogHistogram(), "tpot": LogHistogram(),
            "queue_wait": LogHistogram()}
        self.events: List[Tuple] = []
        self.waiting: List[_Seq] = []
        self.active: List[_Seq] = []      # PREFILL + RUNNING, FCFS order
        self.finished: List[_Seq] = []
        self.rejected: List[Tuple[Request, str]] = []
        self.shed: List[_Seq] = []
        self.failed: List[_Seq] = []
        self.iteration = 0
        self.preemptions = 0
        self._last_tokens = 0
        self._redrives = 0
        self._recovered = 0
        self._jtoks: List[Tuple[int, int]] = []  # this iteration's tokens
        # phase spans (_span): the open ones, and this iteration's
        # milliseconds by phase name and work counts by argument name
        self._open_phases: List[str] = []
        self._phase_ms: Dict[str, float] = {}
        self._iter_work: Dict[str, int] = {}
        self._compiled_at = -1           # the last iteration that compiled
        self._work_names = dict(_WORK_TOTALS, **self.model.work)
        self.work_totals = dict.fromkeys(
            [*self._work_names.values(), "prefill_chunks_total",
             "prefill_chunks_with_decode_total"], 0)
        # unified exposition (PR 15): the SLO histograms register by
        # reference, scheduler gauges as render-time callbacks; the
        # registration order IS the metrics_snapshot() key order
        self.registry = MetricsRegistry(prefix="paddle_tpu_serve")
        self._register_metrics()
        # admission valves: explicit ServeConfig fields win, then the
        # PADDLE_TPU_SERVE_* knobs, then the documented defaults
        sv = self.serve
        max_queue = (sv.max_queue if sv.max_queue is not None
                     else envs.get(ENV_SERVE_MAX_QUEUE) or 4 * sv.max_batch)
        rate = (sv.rate_limit if sv.rate_limit is not None
                else envs.get(ENV_SERVE_RATE))
        burst = (sv.burst if sv.burst is not None
                 else envs.get(ENV_SERVE_BURST) or max(2, sv.max_batch))
        overcommit = (sv.overcommit if sv.overcommit is not None
                      else envs.get(ENV_SERVE_OVERCOMMIT))
        self.admission = AdmissionController(max_queue, rate, burst,
                                             overcommit)
        self._nan_check = (sv.nan_check if sv.nan_check is not None
                           else envs.get(ENV_SERVE_NAN_CHECK))
        # crash-recoverable request/token journal (inference/journal.py)
        self.journal_path = (journal if journal is not None
                             else envs.get(ENV_SERVE_JOURNAL)) or None
        self._journal: Optional[EngineJournal] = None
        if self.journal_path:
            self._journal = EngineJournal(
                self.journal_path,
                fsync=envs.get(ENV_SERVE_JOURNAL_FSYNC),
                meta=self._journal_meta())
        self._rid = itertools.count()
        self._seqno = itertools.count()
        self._frozen = self.model.freeze(config)
        self._compiled: Dict[Tuple, float] = {}
        # an iteration with a chunk and running rows is one program where
        # the model offers it for this cache (speculation decodes through
        # the verify step, which no chunk carries)
        self._chunk_carries = (
            not self.speculative
            and self._step_fn("prefill+decode", self._frozen) is not None)
        self._clock = 0.0
        # preemption + live weight push (PR 13)
        self._preempt = threading.Event()
        self._was_preempted = False
        self._signum: Optional[int] = None
        self._prev_handler: Any = None
        self._pending_swap: Optional[Tuple[Any, int]] = None
        self.swaps = 0
        self.last_swap: Optional[Dict[str, Any]] = None
        # drain mode (PR 20): submit() rejects with cause 'draining'
        # while existing work runs to completion (drain() / the fleet
        # router's rolling swap both flip this)
        self._draining = False

    def _step_fn(self, kind: str, frozen, quant: Optional[bool] = None):
        """The model's jitted program of one kind (prefill, decode, verify,
        prefill+decode) for the cache this engine holds (``quant=False``:
        for the draft's, which is never int8), or None where the model
        offers none of that kind; every scheduler call site dispatches
        through here, and nothing else changes with mp or the cache's
        type."""
        if quant is None:
            quant = self.kv_dtype == "int8"
        return self.model.step_fn(kind, frozen, quant, self.mesh)

    # the cache's arrays under the names they had while Llama's (k, v) was
    # the only cache
    k_pool = property(lambda self: self.kv[0])
    v_pool = property(lambda self: self.kv[1])
    k_scale = property(lambda self: self.kv[2] if len(self.kv) > 2 else None)
    v_scale = property(lambda self: self.kv[3] if len(self.kv) > 2 else None)
    k_draft = property(
        lambda self: self.kv_draft[0] if self.kv_draft else None)
    v_draft = property(
        lambda self: self.kv_draft[1] if self.kv_draft else None)

    def _register_metrics(self) -> None:
        """Register every engine metric into the unified registry: the
        live SLO histograms by reference (zero double bookkeeping) and
        the scheduler gauges as callbacks read at render time — all
        host-side ``len()``s and counters, so scraping never touches the
        device."""
        r = self.registry
        r.summary("ttft_seconds", hist=self.slo["ttft"],
                  help="time to first token (engine clock)")
        r.summary("tpot_seconds", hist=self.slo["tpot"],
                  help="time per output token (engine clock)")
        r.summary("queue_wait_seconds", hist=self.slo["queue_wait"],
                  help="submit-to-first-schedule wait (engine clock)")
        r.gauge("queue_depth", fn=lambda: len(self.waiting),
                help="requests admitted but not yet scheduled")
        r.gauge("running", fn=lambda: sum(1 for s in self.active
                                          if s.state == RUNNING),
                help="sequences in decode")
        r.gauge("prefilling", fn=lambda: sum(1 for s in self.active
                                             if s.state == PREFILL),
                help="sequences in chunked prefill")
        r.gauge("batch_capacity", fn=lambda: self.serve.max_batch,
                help="configured max decode batch")
        r.gauge("pool_utilization", fn=lambda: self.pool.utilization,
                help="fraction of KV blocks in use")
        r.gauge("iterations", fn=lambda: self.iteration,
                help="scheduler iterations run")
        r.gauge("preemptions", fn=lambda: self.preemptions,
                help="sequences evicted for memory pressure")
        r.gauge("finished_requests", fn=lambda: len(self.finished),
                help="requests completed")
        r.gauge("rejected_requests", fn=lambda: len(self.rejected),
                help="requests refused at admission")
        r.gauge("shed_requests", fn=lambda: len(self.shed),
                help="requests shed past their deadline")
        r.gauge("failed_requests", fn=lambda: len(self.failed),
                help="requests quarantined or failed")
        r.gauge("decode_redrives", fn=lambda: self._redrives,
                help="decode steps re-driven during journal recovery")
        r.gauge("generated_tokens",
                fn=lambda: sum(len(s.generated) for s in self.finished),
                help="tokens generated by finished requests")
        # useful over attempted, counted where the phase spans are: weights
        # stream for ``slots`` to serve ``rows``, a chunk's program runs
        # ``slots`` positions to cache ``tokens``, its attention walks
        # ``ctx_blocks`` live blocks of a table of ``table_blocks``. Monotonic.
        for name, what in (
                ("decode_rows_total", "sequences advanced by decode steps"),
                ("decode_slots_total", "batch slots (the bucket) of the "
                                       "decode steps run"),
                ("step_fetch_bytes_total", "bytes the steps' waits read "
                                           "back from the device"),
                ("prefill_tokens_total", "prompt tokens cached by prefill "
                                         "chunks"),
                ("prefill_slots_total", "token slots (the chunk) of the "
                                        "prefill chunks run"),
                ("prefill_ctx_blocks_total", "live KV blocks the prefill "
                                             "chunks' attention walked"),
                ("prefill_table_blocks_total", "block-table slots of the "
                                               "prefill chunks run"),
                ("prefill_chunks_total", "prefill chunks run"),
                ("prefill_chunks_with_decode_total",
                 "prefill chunks whose program carried the decode batch")):
            r.gauge(name, fn=lambda n=name: self.work_totals[n], help=what)
        for name in self.model.work.values():       # the model's own counts
            r.gauge(name, fn=lambda n=name: self.work_totals[n],
                    help="counted by the model's jitted steps")
        if self.slots is not None:
            r.gauge("state_slots_in_use", fn=lambda: self.slots.used_slots,
                    help="recurrent-state slots held by sequences")
        # PR 16 capacity gauges, only when the cache is live: the
        # default exposition stays byte-compatible with the pre-PR-15
        # legacy dict (pinned by the metrics-registry golden test)
        if self.cache is not None:
            r.gauge("prefix_cache_hits", fn=lambda: self.cache.hits,
                    help="admissions served a shared prefix from the "
                         "cache")
            r.gauge("prefix_cache_hit_tokens",
                    fn=lambda: self.cache.hit_tokens,
                    help="prompt tokens whose prefill was skipped via "
                         "cache")
            r.gauge("prefix_cached_blocks",
                    fn=lambda: self.pool.cached_blocks,
                    help="parked prefix-cache blocks (zero refs, "
                         "reclaimable)")
            r.gauge("cow_copies", fn=lambda: self._cow_copies,
                    help="shared blocks copied on write")
        # PR 18 speculative-decode gauges, only when speculation is live
        if self.speculative:
            r.gauge("spec_proposed_tokens", fn=lambda: self._spec_proposed,
                    help="draft tokens proposed for verification")
            r.gauge("spec_accepted_tokens", fn=lambda: self._spec_accepted,
                    help="draft tokens the base model accepted")
            r.gauge("spec_accept_rate",
                    fn=lambda: (self._spec_accepted / self._spec_proposed
                                if self._spec_proposed else 0.0),
                    help="accepted / proposed draft tokens")

    # -- bookkeeping --------------------------------------------------------

    def _journal_meta(self) -> Dict[str, Any]:
        """Audit-only open-record fields: which capacity features were
        live. Cache STATE is derived (bytes are a pure function of the
        token prefix), so recovery never needs it journaled."""
        return {"kv_dtype": self.kv_dtype,
                "prefix_cache": self.cache is not None,
                "speculative": self.speculative,
                "mp": self.mp}

    def _event(self, *ev):
        if self.record_events:
            self.events.append((self.iteration,) + tuple(ev))

    def _alloc_for(self, seq: _Seq, n_tokens: int) -> bool:
        """Grow ``seq`` to cover ``n_tokens`` cached tokens; False (and
        no change) when the pool is dry."""
        need = self.pool.blocks_for(n_tokens) - len(seq.blocks)
        if need <= 0:
            return True
        wants_slot = self.slots is not None and seq.slot is None
        if wants_slot and not self.slots.free_slots:
            return False
        got = self.pool.alloc(need)
        if got is None:
            return False
        seq.blocks.extend(got)
        if wants_slot:
            # with its first blocks; the slot's old bytes are never read: a
            # chunk that starts at 0 begins from zeros inside the program
            seq.slot = self.slots.alloc()
        record_counter("serve.blocks_alloc", need)
        return True

    def _release(self, seq: _Seq):
        if seq.blocks:
            record_counter("serve.blocks_free", len(seq.blocks))
            self.pool.free(seq.blocks)
            seq.blocks = []
        if seq.slot is not None:
            self.slots.free(seq.slot)
            seq.slot = None

    def _cow_span(self, seq: _Seq, start: int, n_tokens: int) -> bool:
        """Copy-on-write guard: make every block covering positions
        [start, start+n) privately writable before the device writes.
        With sharing on, scheduler writes land past the hit span by
        construction (hits are block-aligned and capped below
        prefill_target; registration covers only full immutable
        blocks), so this is a defensive invariant — but it is THE
        contract that keeps shared bytes immutable: a block with other
        readers is copied (device blit + table swap), a registered
        ref-1 block has its index entry invalidated instead. False if
        the pool cannot supply a copy block (caller evicts/stalls)."""
        if self.cache is None or n_tokens < 1:
            return True
        bs = self.pool.block_size
        for bi in range(start // bs, (start + n_tokens - 1) // bs + 1):
            if bi >= len(seq.blocks):
                continue
            b = seq.blocks[bi]
            if self.pool.ref_count(b) > 1:
                got = self.pool.alloc(1)
                if got is None:
                    return False
                nb = got[0]
                # device-side blit of the shared block's slabs (host
                # decision, one copy — never a cache reshape/compact)
                # every array of the cache; the draft pools share the
                # block table, so the draft's slab moves with the base's
                self.kv = tuple(a.at[:, nb].set(a[:, b]) for a in self.kv)
                self.kv_draft = tuple(a.at[:, nb].set(a[:, b])
                                      for a in self.kv_draft)
                self.pool.free([b])
                seq.blocks[bi] = nb
                self._cow_copies += 1
                record_counter("serve.cow_copy")
                self._event("cow_copy", seq.req.request_id, b, nb)
            elif self.pool.is_registered(b):
                # sole owner, but the index still maps a prefix to this
                # block: writing would corrupt future hits' bytes —
                # forget the entry, keep the block private
                self.cache.invalidate_block(b)
        return True

    def _evict_one(self, protect: Optional[_Seq] = None) -> bool:
        """Preempt the lowest-priority, then YOUNGEST running sequence:
        free its blocks and push it to the FRONT of the waiting queue for
        recompute-style readmission (its generated tokens are kept; the
        KV prefix is re-prefilled)."""
        victims = [s for s in self.active
                   if s.state == RUNNING and s is not protect]
        if not victims:
            return False

        def restorable(s: _Seq) -> int:
            # ref-count-aware tiebreak (PR 16): blocks that back prefix-
            # cache entries survive this sequence's eviction (they park
            # or stay shared), so readmission re-hits them — evicting
            # the most-cached victim costs the least recompute. Zero
            # for every sequence when the cache is off.
            if self.cache is None:
                return 0
            return sum(1 for b in s.blocks if self.pool.is_registered(b))

        # lowest priority goes first; then the victim whose prefix is
        # best covered by the cache (cheapest to restore); within that,
        # ties on arrival (e.g. a burst submitted at the same instant)
        # break toward the latest-submitted sequence, deterministically
        victim = max(victims,
                     key=lambda s: (-s.req.priority, restorable(s),
                                    s.arrival, s.order))
        self.active.remove(victim)
        self._release(victim)
        victim.state = WAITING
        victim.n_cached = 0
        victim.draft_pos = 0
        victim.n_preempted += 1
        self.waiting.insert(0, victim)
        self.preemptions += 1
        record_counter("serve.preempt")
        self._event("evict", victim.req.request_id)
        if self.tracer is not None:
            self.tracer.evict(victim.req.request_id, time.perf_counter(),
                              victim.n_preempted)
        if self.recorder is not None:
            self.recorder.note_eviction(self.iteration)
        return True

    def _finish_seq(self, seq: _Seq, t: float):
        seq.state = FINISHED
        if seq in self.active:
            self.active.remove(seq)
        self._release(seq)
        self.finished.append(seq)
        record_counter("serve.finish")
        if self.tracer is not None:
            self.tracer.finish(seq.req.request_id, t, len(seq.generated))

    def _shed_seq(self, seq: _Seq, cause: str):
        """Terminal shed of a QUEUED sequence (deadline already missed)."""
        self._release(seq)
        seq.state = SHED
        seq.fail_cause = cause
        self.shed.append(seq)
        record_counter("serve.shed")
        self._event("shed", seq.req.request_id, cause)
        if self.tracer is not None:
            self.tracer.shed(seq.req.request_id, time.perf_counter(),
                             cause)
        if self.recorder is not None:
            self.recorder.record({"iteration": self.iteration,
                                  "event": "shed",
                                  "rid": seq.req.request_id,
                                  "cause": cause})
        if self._journal is not None:
            self._journal.shed(seq.req.request_id, cause)

    def _shed_expired(self):
        """Deadline-based load shedding over the waiting queue: a queued
        request past its TTFT or total deadline can no longer meet it —
        shed it now instead of burning pool blocks on a dead request.
        Pure engine-clock arithmetic, so replays shed identically."""
        if not self.waiting:
            return
        kept = []
        for seq in self.waiting:
            r, waited = seq.req, self._clock - seq.arrival
            if r.deadline is not None and waited > r.deadline:
                self._shed_seq(seq, "deadline")
            elif (r.ttft_deadline is not None and not seq.generated
                    and waited > r.ttft_deadline):
                self._shed_seq(seq, "ttft_deadline")
            else:
                kept.append(seq)
        self.waiting = kept

    def _quarantine(self, seq: _Seq, cause: str):
        """Poisoned request: release its blocks, mark it failed with the
        cause, keep serving everyone else."""
        if seq in self.active:
            self.active.remove(seq)
        self._release(seq)
        seq.state = FAILED
        seq.fail_cause = cause
        self.failed.append(seq)
        record_counter("serve.quarantine")
        self._event("quarantine", seq.req.request_id, cause)
        if self.tracer is not None:
            self.tracer.quarantine(seq.req.request_id,
                                   time.perf_counter(), cause)
        if self.recorder is not None:
            self.recorder.record({"iteration": self.iteration,
                                  "event": "quarantine",
                                  "rid": seq.req.request_id,
                                  "cause": cause})
        if self._journal is not None:
            self._journal.failed(seq.req.request_id, cause)

    def _pools_alive(self) -> bool:
        """False when an exception killed a kernel AFTER its donated
        k/v pool buffers were invalidated — unrecoverable in-process
        (the journal recovery path owns that failure mode)."""
        for pool in self.kv + self.kv_draft + self.state:
            deleted = getattr(pool, "is_deleted", None)
            if deleted is not None and deleted():
                return False
        return True

    def _mark_compiled(self, key: Tuple, t_call: float):
        """``key``: the program's kind, then its shape (``("decode", 8)``,
        ``("prefill+decode", chunk, rows)``)."""
        if key not in self._compiled:
            self._compiled[key] = t_call
            self._compiled_at = self.iteration
            record_counter(f"serve.compile.{key[0]}")
            if self.metrics is not None:
                self.metrics.record_compile(compile_s=t_call)
            if self.recorder is not None:
                self.recorder.record_compile("_".join(map(str, key)), t_call)

    def _span(self, name: str, **args) -> _Phase:
        """``with self._span("serve.decode.plan"): ...`` at a phase
        boundary; see :class:`_Phase`."""
        return _Phase(self, name, args)

    def _launch_span(self, name: str, key: Tuple) -> _Phase:
        """A launch phase, marked ``first_call`` when its program is new
        to ``_compiled`` (the call then traces and compiles)."""
        return _Phase(self, name,
                      {} if key in self._compiled else {"first_call": 1})

    def _fetch(self, sp: _Phase, heads: Sequence, counts: Sequence = ()
               ) -> List[np.ndarray]:
        """The one device-to-host read of a step, inside its ``.wait`` span:
        ``heads`` (tokens, finite flags, accept lengths: a few bytes a row,
        never logits) as host arrays, every copy started before the first
        is waited for; the model's ``counts`` come over in the same read
        (``model.counted`` then finds them on the host). The bytes of both
        go onto ``sp`` as ``fetch_bytes``."""
        self._note_work(sp, fetch_bytes=sum(
            a.nbytes for a in (*heads, *counts)))
        return jax.device_get([*heads, *counts])[:len(heads)]  # noqa: PTA006 -- step boundary: sampled tokens must reach the scheduler

    def _note_work(self, sp: _Phase, **counts: int) -> None:
        """The useful-over-attempted counts of a phase (``rows`` of
        ``bucket``, ``n_live`` of ``chunk``): onto its span, into this
        iteration's record and into the registry's totals."""
        sp.note(**counts)
        self._iter_work.update(counts)
        for k, v in counts.items():
            self.work_totals[self._work_names[k]] += v

    # -- public API ---------------------------------------------------------

    def _demand_and_shared(self, req: Optional[Request]
                           ) -> Tuple[int, int]:
        """Worst-case block demand of everything queued + active, and
        the new request's estimated prefix-shared blocks.

        With the prefix cache on (PR 16), shared prefix blocks are
        free-by-construction — N requests over one cached prefix cost
        its blocks ONCE — so each request's worst case shrinks by its
        expected hit length. Queued-but-unprefilled prompts count too
        (``pending`` keys), so a same-instant burst of identical
        prompts is admitted against one copy of the shared span, which
        is exactly the ROADMAP's "admission estimate could subtract
        shared blocks" item. Cache off: identical to the PR-14 sum."""
        demand = 0
        cache = self.cache
        pending: set = set()
        for s in itertools.chain(self.waiting, self.active):
            # speculative lookahead needs no extra headroom here: the
            # per-iteration cap t_cap <= max_new - generated keeps every
            # allocation within blocks_for(prompt + max_new), the same
            # worst case sequential decode plans for
            worst = self.pool.blocks_for(
                len(s.req.prompt) + s.req.max_new_tokens)
            if cache is not None:
                limit = (len(s.req.prompt) - 1) // self.pool.block_size
                shared = cache.match_len(s.req.prompt, limit, pending)
                worst -= min(shared, worst - 1)
                pending.update(cache.prospective_keys(s.req.prompt,
                                                      limit))
            demand += worst
        new_shared = 0
        if req is not None and cache is not None:
            limit = (len(req.prompt) - 1) // self.pool.block_size
            new_shared = cache.match_len(req.prompt, limit, pending)
        return demand, new_shared

    def _demand_blocks(self) -> int:
        """Worst-case block demand of everything queued + active."""
        return self._demand_and_shared(None)[0]

    def submit(self, req: Request) -> Admission:
        """Admit ``req`` into the bounded queue or reject it with a
        deterministic cause. Malformed requests (can never be served at
        any load) still raise ValueError; overload is an Admission
        outcome, not an exception."""
        if req.request_id is None:
            req.request_id = next(self._rid)
        with self._span("serve.submit", rid=req.request_id) as sp:
            adm = self._submit(req)
            sp.note(accepted=int(adm.accepted))
        return adm

    def _submit(self, req: Request) -> Admission:
        worst = len(req.prompt) + req.max_new_tokens
        if worst > self.serve.max_seq_len:
            raise ValueError(
                f"request {req.request_id}: prompt+max_new_tokens {worst} "
                f"exceeds max_seq_len {self.serve.max_seq_len}")
        if self.pool.blocks_for(worst) > self.serve.num_blocks - 1:
            raise ValueError(
                f"request {req.request_id} can never fit the pool "
                f"({worst} tokens > {self.serve.num_blocks - 1} blocks)")
        if not len(req.prompt):
            raise ValueError(f"request {req.request_id}: empty prompt")
        faults.inject("serve.admit.before", rid=req.request_id)
        if self._draining:
            # a draining engine admits nothing new — checked before the
            # admission valves so draining never spends bucket tokens
            cause = "draining"
        else:
            demand, new_shared = self._demand_and_shared(req)
            worst_blocks = max(self.pool.blocks_for(worst) - new_shared, 1)
            cause = self.admission.decide(
                queue_len=len(self.waiting),
                demand_blocks=demand,
                worst_blocks=worst_blocks,
                usable_blocks=self.serve.num_blocks - 1,
                now=self._clock)
        if cause is not None:
            self.rejected.append((req, cause))
            record_counter("serve.reject")
            self._event("reject", req.request_id, cause)
            if self.tracer is not None:
                self.tracer.reject(req.request_id, time.perf_counter(),
                                   cause)
            if self.recorder is not None:
                self.recorder.record({"iteration": self.iteration,
                                      "event": "reject",
                                      "rid": req.request_id,
                                      "cause": cause})
            if self._journal is not None:
                self._journal.reject(req.request_id, cause)
            return Admission(False, req.request_id, cause)
        seq = _Seq(req, self._clock)
        seq.order = next(self._seqno)
        self.waiting.append(seq)
        self._event("submit", req.request_id)
        if self.tracer is not None:
            self.tracer.submit(req.request_id, time.perf_counter())
        if self._journal is not None:
            self._journal.submit(req)
        faults.inject("serve.admit.after", rid=req.request_id)
        return Admission(True, req.request_id)

    def adopt(self, req: Request,
              generated: Sequence[int] = ()) -> None:
        """Adopt an already-ACCEPTED request migrated from another
        engine (fleet journal migration, PR 20): enqueue it BYPASSING
        the admission valves — accepted work is never re-rejected —
        with its already-emitted tokens attached, exactly as
        ``recover()`` rebuilds an unfinished rid. Greedy decode is
        deterministic in (prompt + history), so the continuation stream
        is bit-identical to the donor's would-have-been stream. The
        request is re-journaled on THIS engine (submit + inherited
        tokens), so a second crash recovers from this journal alone,
        without the dead donor's file."""
        if req.request_id is None:
            req.request_id = next(self._rid)
        seq = _Seq(req, self._clock)
        seq.order = next(self._seqno)
        seq.tokens.extend(int(t) for t in generated)
        seq.recovered = True
        if seq.generated:
            # its first token predates this engine: never re-measure TTFT
            seq.first_token_t = seq.arrival
        if self._journal is not None:
            self._journal.submit(req)
            if seq.generated:
                self._journal.tokens(
                    self.iteration,
                    [(req.request_id, t) for t in seq.generated])
        if seq.done():
            # the donor emitted its last token but never journaled the
            # finish mark: already complete, no re-drive
            seq.state = FINISHED
            self.finished.append(seq)
            if self._journal is not None:
                self._journal.finish(req.request_id)
        else:
            self.waiting.append(seq)
        self._recovered += 1
        record_counter("serve.adopt")
        self._event("adopt", req.request_id, len(seq.generated))

    def load_signal(self) -> Tuple[float, float, float]:
        """Composite load for fleet routing (PR 20), host-side and
        cheap: (queue depth + in-flight, -available blocks, streaming
        TTFT p99). Every component is a pure function of scheduler
        state and the engine clock, so identical replays expose
        identical load and routing stays deterministic."""
        p99 = self.slo["ttft"].percentile(99)
        return (float(len(self.waiting) + len(self.active)),
                -float(self.pool.available_blocks),
                float(p99 if p99 is not None else 0.0))

    def step(self) -> List[_Seq]:
        """One scheduler iteration: admit, one prefill chunk, one decode
        batch; where there is work of both kinds and the model offers the
        program, the batch rides the chunk's program and the iteration
        launches once. Returns sequences that finished this iteration."""
        self._phase_ms = {}
        self._iter_work = {}
        with self._span("serve.step", iteration=self.iteration + 1) as st:
            with self._span("serve.admit") as sp:
                # the gap between step() calls is the engine's safe
                # boundary: the previous decode already synced its tokens
                # to the host, nothing is in flight — scheduled weight
                # swaps land exactly here
                if self._pending_swap is not None \
                        and self.iteration + 1 >= self._pending_swap[1]:
                    source, _ = self._pending_swap
                    self._pending_swap = None
                    self._apply_swap(source)
                self.iteration += 1
                self._last_tokens = 0
                self._jtoks = []
                if faults.fires("serve.preempt_storm"):
                    # injected pool-pressure fault: forcibly evict the
                    # youngest running sequence, as if a burst had stolen
                    # its blocks
                    self._evict_one()
                waiting = len(self.waiting)
                self._shed_expired()
                sp.note(waiting=waiting, admitted=self._admit())
            done: List[_Seq] = []
            ran_prefill, owed = False, None
            seq = next((s for s in self.active if s.state == PREFILL), None)
            if seq is not None:
                with self._span("serve.prefill", rid=seq.req.request_id,
                                start=seq.n_cached) as sp:
                    ran_prefill, owed = self._prefill_chunk(
                        seq, sp, done, carry=self._chunk_carries and any(
                            s.state == RUNNING for s in self.active))
            # a prompt whose last chunk carried the batch decodes from the
            # next iteration on; after a chunk alone, in this one
            if (any(s.state == RUNNING for s in self.active) if owed is None
                    else owed):
                with self._span("serve.decode") as sp:
                    done += (self._decode_spec_batch(sp) if self.speculative
                             else self._decode_batch(sp, redrive=owed))
            with self._span("serve.report") as rp:
                self._report(done, ran_prefill, rp.t0 - st.t0, rp.t0)
        return done

    def _report(self, done: List[_Seq], ran_prefill: bool,
                step_time_s: float, t_report: float) -> None:
        """The end of an iteration: event log, journal, telemetry and the
        flight recorder, fed from the phase boundaries the iteration
        crossed. ``step_time_s`` runs from the start of ``step()`` to the
        start of this report, as it always has."""
        for seq in done:
            self._event("finish", seq.req.request_id, len(seq.generated))
        if self._journal is not None:
            # one tokens record per iteration; finish marks AFTER it so
            # a torn tail can lose a finish mark but never a finished
            # request's tokens (recover() re-derives the mark)
            self._journal.tokens(self.iteration, self._jtoks)
            for seq in done:
                self._journal.finish(seq.req.request_id)
        if self.metrics is None and self.recorder is None:
            return
        n_run = n_pre = 0
        for s in self.active:
            if s.state == RUNNING:
                n_run += 1
            elif s.state == PREFILL:
                n_pre += 1
        ms = self._phase_ms
        fields = dict(
            step_time_s=step_time_s,
            tokens=self._last_tokens,
            queue_depth=len(self.waiting),
            n_running=n_run,
            n_prefill=n_pre,
            batch_occupancy=n_run / self.serve.max_batch,
            pool_utilization=self.pool.utilization,
            prefill_ms=ms.get("serve.prefill", 0.0),
            decode_ms=ms.get("serve.decode", 0.0),
        )
        if self.metrics is not None:
            self.metrics.step(**fields)
        if self.recorder is not None:
            # every phase the iteration crossed, "serve.decode.wait" as
            # decode_wait_ms; the report's own time up to here, so that a
            # stall in the journal shows and is checked too
            report_ms = (time.perf_counter() - t_report) * 1e3
            self.recorder.record({
                "iteration": self.iteration, **fields,
                **{k[len("serve."):].replace(".", "_") + "_ms": v
                   for k, v in ms.items()},
                "report_ms": report_ms, **self._iter_work})
            if self._compiled_at != self.iteration:
                # a first call compiles: the ring has it as a compile
                # event, and it is no spike of either kind
                self.recorder.check_step_time(
                    step_time_s + report_ms * 1e-3,
                    kind="chunk" if ran_prefill else "decode")

    def idle(self) -> bool:
        return not self.waiting and not self.active

    # -- scheduler phases ---------------------------------------------------

    def _admit(self) -> int:
        """Admit from the head of the queue while batch and pool allow;
        returns how many were admitted."""
        admitted = 0
        while self.waiting and len(self.active) < self.serve.max_batch:
            seq = self.waiting[0]
            # prefix-cache hit (PR 16): the longest chain of cached
            # full blocks prefixing this prompt, capped one token short
            # of prefill_target so the final chunk always has a live
            # token to produce the sampling logits. Hit blocks are
            # shared ref-counted (COW), never re-prefilled.
            hit: List[int] = []
            if self.cache is not None and not seq.blocks:
                limit = (seq.prefill_target - 1) // self.pool.block_size
                hit = self.cache.match(seq.tokens, limit)
            need = self.pool.blocks_for(seq.prefill_target) + 1 - len(hit)
            if not self.pool.can_alloc(need):
                break
            self.waiting.pop(0)
            seq.state = PREFILL
            if hit:
                self.pool.acquire(hit)
                seq.blocks = list(hit)
                seq.n_cached = len(hit) * self.pool.block_size
                record_counter("serve.prefix_hit")
                record_counter("serve.prefix_hit_tokens", seq.n_cached)
                self._event("prefix_hit", seq.req.request_id, len(hit))
            else:
                seq.n_cached = 0
            self.active.append(seq)
            admitted += 1
            record_counter("serve.admit")
            self._event("admit", seq.req.request_id)
            if not seq.generated:
                # first admission: queue wait from submit to here (a
                # readmitted sequence's renewed wait shows in its trace
                # requeue span, not the SLO histogram)
                self.slo["queue_wait"].record(self._clock - seq.arrival)
            if self.tracer is not None:
                self.tracer.admit(seq.req.request_id, time.perf_counter(),
                                  seq.n_preempted)
        return admitted

    def _prefill_chunk(self, seq: _Seq, sp: _Phase, done_out: List[_Seq],
                       carry: bool
                       ) -> Tuple[bool, Optional[List[_Seq]]]:
        """One chunk of ``seq``'s prompt inside its ``serve.prefill`` span
        ``sp``: plan (blocks, inputs), launch, wait for the token, commit.
        With ``carry`` the running rows are planned beside it and ride the
        chunk's program (``prefill+decode``, always ``max_batch`` rows
        wide): one launch and one wait for both, then the chunk's commit
        and the rows'. Returns (the chunk ran: False when the pool is dry
        and it stalls; the carried rows still owed a token by the decode
        program in this iteration: none once they are committed, the
        survivors of a poisoned row's batch, None where no row was carried
        and the decode batch runs as after a chunk alone)."""
        rid = seq.req.request_id
        c = self.serve.prefill_chunk
        with self._span("serve.prefill.plan"):
            faults.inject("serve.prefill.before", rid=rid)
            n_live = min(c, seq.prefill_target - seq.n_cached)
            # graceful degradation: under pool pressure, shrink this
            # chunk's LIVE span to the headroom the pool still has (n_live
            # is data, not shape — same compiled step) before resorting to
            # eviction. available_blocks counts parked cache blocks:
            # alloc() reclaims them LRU-oldest after the free list, so
            # caching never shrinks a chunk a cache-off engine could run
            # whole
            headroom = ((len(seq.blocks) + self.pool.available_blocks)
                        * self.pool.block_size - seq.n_cached)
            if 1 <= headroom < n_live:
                n_live = headroom
                record_counter("serve.prefill_shrink")
                self._event("prefill_shrink", rid, n_live)
            if not (self._alloc_for(seq, seq.n_cached + n_live)
                    and self._cow_span(seq, seq.n_cached, n_live)):
                # pool dry mid-prompt: steal from the youngest decoder; if
                # there is none, stall — decode progress will free blocks
                if not (self._evict_one(protect=seq)
                        and self._alloc_for(seq, seq.n_cached + n_live)
                        and self._cow_span(seq, seq.n_cached, n_live)):
                    return False, None
            ids = np.zeros((c,), np.int32)
            ids[:n_live] = seq.tokens[seq.n_cached:seq.n_cached + n_live]
            table = pad_table(seq.blocks, self.serve.max_nb)
            rows = self._plan_rows() if carry else []
            if rows:
                rids, n_rows, toks, positions, tables = self._decode_inputs(
                    rows, self.serve.max_batch)
        # the chunk's attention walks ctx_blocks of the table's table_blocks
        self._note_work(
            sp, n_live=int(n_live), chunk=c,
            ctx_blocks=self.pool.blocks_for(seq.n_cached + int(n_live)),
            table_blocks=self.serve.max_nb)
        self.work_totals["prefill_chunks_total"] += 1
        key = ("prefill+decode", c, n_rows) if rows else ("prefill", c)
        failure: Optional[Exception] = None
        row_heads = None
        try:
            faults.inject("serve.prefill.poison", rid=rid)
            with self._launch_span("serve.prefill.launch", key) as launch:
                chunk_in = (jnp.asarray(table), np.int32(seq.n_cached),
                            jnp.asarray(ids), np.int32(n_live))
                # a model with state: the chunk's slot, then the rows'
                slot_in = () if self.slots is None else (np.int32(seq.slot),)
                if rows:
                    out = self._step_fn("prefill+decode", self._frozen)(
                        self.params, *self.kv, *self.state, *chunk_in,
                        jnp.asarray(tables), jnp.asarray(positions),
                        jnp.asarray(toks), *slot_in,
                        *self._row_slots(rows, n_rows))
                else:
                    out = self._step_fn("prefill", self._frozen)(
                        self.params, *self.kv, *self.state, *chunk_in,
                        *slot_in)
                heads, counts = self._unpack(out, 4 if rows else 2)
            with self._span("serve.prefill.wait") as wait:
                token, finite, *row_heads = self._fetch(sp, heads, counts)
                if counts:
                    # a model's own work counts ride the sync just paid:
                    # the chunk's context and, where they rode, the rows'
                    ctx = [[seq.n_cached + int(n_live)]]
                    if rows:
                        ctx.append([s.n_cached + 1 for s in rows])
                    self._note_work(sp, **self.model.counted(
                        key[0], counts, *ctx))
        except Exception as e:  # noqa: BLE001 -- quarantine boundary
            failure, row_heads = e, None
        with self._span("serve.prefill.commit"):
            if failure is None:
                try:
                    faults.inject("serve.prefill.logits", rid=rid,
                                  tokens=token, finite=finite)
                    if self._nan_check and not bool(finite):
                        raise PoisonError(rid, "non-finite prefill logits")
                except Exception as e:  # noqa: BLE001 -- quarantine boundary
                    failure = e
            if failure is not None:
                if not self._pools_alive():
                    # donated pools died mid-kernel: journal recovery
                    raise failure
                # the chunk's side touches exactly one request, so ANY
                # failure here is laid at its door: quarantine it, keep
                # serving. Rows that rode a program which never returned
                # go through the decode program, which knows whom to blame
                cause = (failure.cause if isinstance(failure, PoisonError)
                         else f"prefill: {failure!r}")
                self._quarantine(seq, cause)
            else:
                self._mark_compiled(key, wait.t1 - launch.t0)
                if self.tracer is not None:
                    self.tracer.prefill_chunk(
                        rid, launch.t0, wait.t1, int(n_live),
                        recompute=bool(seq.generated))
                seq.n_cached += n_live
                if seq.n_cached == seq.prefill_target:
                    self._prefill_done(seq, int(token), done_out)
                faults.inject("serve.prefill.after", rid=rid)
            if not row_heads:
                return True, None
            # the rows' side, behind the decode batch's hooks in their
            # order (``serve.decode.before`` too: here it fires after the
            # launch, the chunk's commit between them as ever); a poisoned
            # row is quarantined and the others are owed a re-drive through
            # the decode program in this iteration. The rows count, and the
            # chunk counts as having carried them, once they commit
            faults.inject("serve.decode.before", rids=rids)
            try:
                faults.inject("serve.decode.poison", rids=rids)
                faults.inject("serve.decode.logits", rids=rids,
                              tokens=row_heads[0], finite=row_heads[1])
            except PoisonError as e:
                # the program that carried the rows has run
                rows, row_heads = self._drop_poisoned(rows, e, row_heads)
                if row_heads is None:
                    return True, rows
            self._note_work(sp, rows=len(rows), bucket=n_rows)
            self.work_totals["prefill_chunks_with_decode_total"] += 1
            done_out += self._commit_rows(rows, *row_heads, launch.t0,
                                          wait.t1)
        return True, []

    def _prefill_done(self, seq: _Seq, token: int,
                      done_out: List[_Seq]) -> None:
        """The prompt's last chunk has landed: register its blocks, take
        ``token`` (the greedy head of the chunk's last live position, as
        the program returned it) for the first new one, start decoding (or
        finish)."""
        rid = seq.req.request_id
        if self.cache is not None:
            # register the prompt's FULL blocks — wholly below
            # prefill_target, so their bytes are immutable from here
            # on (decode writes land at >= prefill_target). A
            # quarantined prefill never reaches this line.
            n_reg = seq.prefill_target // self.pool.block_size
            if n_reg:
                added = self.cache.register(seq.tokens, seq.blocks, n_reg)
                if added:
                    self._event("prefix_register", rid, added)
        if not seq.generated:
            # fresh prompt: the final chunk's token is the first new one
            seq.tokens.append(token)
            seq.first_token_t = self._now()
            seq.token_times.append(seq.first_token_t)
            self._last_tokens += 1
            self._jtoks.append((rid, seq.tokens[-1]))
            self.slo["ttft"].record(seq.first_token_t - seq.arrival)
        if seq.done():
            # eos/max_new on the very first token: finish here so
            # "done() implies finished" holds at every iteration
            # boundary (recover() relies on the invariant)
            self._finish_seq(seq, time.perf_counter())
            done_out.append(seq)
        else:
            seq.state = RUNNING
            if self.speculative:
                # bring the draft's cache up to n_cached before the
                # first decode iteration touches this row; covers
                # fresh, readmitted, recovered and prefix-hit
                # sequences uniformly (the draft re-prefills shared
                # blocks with identical bytes — pure function of
                # the token prefix)
                self._draft_prefill(seq)

    def _draft_prefill(self, seq: _Seq):
        """Chunked prefill of ``seq``'s prompt through the DRAFT model
        into the draft pools (shared block table). Draft state is fully
        derived — never journaled, never recovered — so a crash here
        costs nothing but the re-prefill on readmission."""
        c = self.serve.prefill_chunk
        fn = self._step_fn("prefill", self._draft_frozen, quant=False)
        table = jnp.asarray(pad_table(seq.blocks, self.serve.max_nb))
        start, target = 0, seq.n_cached
        with self._launch_span("serve.draft.prefill",
                               ("draft_prefill", c)) as sp:
            while start < target:
                n_live = min(c, target - start)
                ids = np.zeros((c,), np.int32)
                ids[:n_live] = seq.tokens[start:start + n_live]
                self.kv_draft = fn(
                    self.draft_params, *self.kv_draft,
                    table, np.int32(start), jnp.asarray(ids),
                    np.int32(n_live))[2:]
                start += n_live
        self._mark_compiled(("draft_prefill", c), sp.t1 - sp.t0)
        seq.draft_pos = target

    def _plan_rows(self) -> List[_Seq]:
        """The RUNNING rows that can take one more token, each grown
        across its block boundary, evicting youngest-first when the pool
        runs dry (an evicted row drops out of the batch by losing RUNNING
        state); with nothing evictable the row stalls an iteration instead
        — finishing rows free its blocks."""
        ready: List[_Seq] = []
        for seq in [s for s in self.active if s.state == RUNNING]:
            if seq.state != RUNNING:
                continue
            ok = (self._alloc_for(seq, seq.n_cached + 1)
                  and self._cow_span(seq, seq.n_cached, 1))
            while not ok and self._evict_one(protect=seq):
                ok = (self._alloc_for(seq, seq.n_cached + 1)
                      and self._cow_span(seq, seq.n_cached, 1))
            if ok:
                ready.append(seq)
            else:
                record_counter("serve.decode_stall")
        return [s for s in ready if s.state == RUNNING]

    def _decode_inputs(self, rows: List[_Seq], bucket: Optional[int] = None):
        """(rids, bucket, toks, positions, tables) of one decode launch,
        padded to ``bucket`` (default: the smallest that holds the rows)
        with rows at null block 0, position 0."""
        if bucket is None:
            bucket = next(b for b in self.serve.decode_buckets
                          if b >= len(rows))
        toks = np.zeros((bucket,), np.int32)
        positions = np.zeros((bucket,), np.int32)
        tables = np.zeros((bucket, self.serve.max_nb), np.int32)
        for i, seq in enumerate(rows):
            toks[i] = seq.tokens[-1]
            positions[i] = seq.n_cached
            tables[i] = pad_table(seq.blocks, self.serve.max_nb)
        return ([s.req.request_id for s in rows], bucket, toks, positions,
                tables)

    def _row_slots(self, rows: List[_Seq], bucket: int) -> Tuple:
        """A model with state: (the rows' slots [bucket] i32, padding rows at
        the null slot 0), the steps' last input; nothing for any other."""
        if self.slots is None:
            return ()
        slots = np.zeros((bucket,), np.int32)
        slots[:len(rows)] = [s.slot for s in rows]
        return (jnp.asarray(slots),)

    def _unpack(self, out: Sequence, n_heads: int) -> Tuple[Sequence, Sequence]:
        """A step's outputs taken apart: the donated caches back into
        ``self.kv`` and ``self.state``; returns (heads, counts)."""
        n_kv, n_st = len(self.kv), len(self.state)
        self.kv = tuple(out[n_heads:n_heads + n_kv])
        self.state = tuple(out[n_heads + n_kv:n_heads + n_kv + n_st])
        return out[:n_heads], out[n_heads + n_kv + n_st:]

    def _drop_poisoned(self, rows: List[_Seq], e: PoisonError,
                       ran: Optional[Tuple[np.ndarray, np.ndarray]] = None
                       ) -> Tuple[List[_Seq], Optional[Tuple]]:
        """Quarantine the row ``e`` names; returns (its batchmates, what
        they are owed). Rows are independent (disjoint blocks and slots,
        per-row tables), so survivors' tokens are bit-identical to a batch
        that never held the poison. THE RULE: a fault raised BEFORE the
        launch has moved nothing, and the batchmates are owed a re-drive
        through the decode program (None; counted in ``decode_redrives``).
        A fault raised once the program has RUN (``ran``: the tokens and
        finite flags it returned, a row each) finds the survivors' KV column
        written and, for a model with recurrent state, their state advanced
        by one token: rewriting a KV column is idempotent, advancing a state
        twice is not. So a model with state is never launched again for
        them: they are owed the commit of that run's tokens, returned here
        as (tokens, finite) cut to the survivors. A model without state
        re-drives in both cases, as it always has."""
        if not self._pools_alive():
            raise e  # donated pools died mid-kernel: journal path
        bad = next((s for s in rows if s.req.request_id == e.rid), None)
        if bad is None:
            raise e  # not attributable to this batch
        self._quarantine(bad, e.cause)
        keep = [i for i, s in enumerate(rows) if s is not bad]
        left = [rows[i] for i in keep]
        if ran is not None and self.slots is not None:
            return left, tuple(a[keep] for a in ran)
        self._redrives += 1
        record_counter("serve.decode_redrive")
        return left, None

    def _decode_batch(self, sp: _Phase,
                      redrive: Optional[List[_Seq]] = None) -> List[_Seq]:
        """One token for every RUNNING sequence inside the ``serve.decode``
        span ``sp``: plan (blocks, inputs), launch, wait for the tokens,
        commit. ``redrive``: the rows a chunk's program carried beside a
        poisoned one, in place of every RUNNING sequence; their blocks are
        planned and ``serve.decode.before`` has fired for them."""
        with self._span("serve.decode.plan"):
            rows = self._plan_rows() if redrive is None else redrive
            if not rows:
                return []
            inputs = self._decode_inputs(rows)
        if redrive is None:
            faults.inject("serve.decode.before", rids=inputs[0])
        # re-drive loop: a PoisonError attributable to one row drops that
        # row (quarantined) and re-runs the batch without it
        while True:
            rids, bucket, toks, positions, tables = inputs
            key = ("decode", bucket)
            ran = None          # the program's heads, once it has run
            try:
                faults.inject("serve.decode.poison", rids=rids)
                with self._launch_span("serve.decode.launch", key) as launch:
                    fn = self._step_fn("decode", self._frozen)
                    out = fn(
                        self.params, *self.kv, *self.state,
                        jnp.asarray(tables), jnp.asarray(positions),
                        jnp.asarray(toks), *self._row_slots(rows, bucket))
                    heads, counts = self._unpack(out, 2)
                with self._span("serve.decode.wait") as wait:
                    tokens, finite = ran = self._fetch(sp, heads, counts)
                    if counts:
                        self._note_work(sp, **self.model.counted(
                            "decode", counts, [s.n_cached + 1 for s in rows]))
                faults.inject("serve.decode.logits", rids=rids,
                              tokens=tokens, finite=finite)
            except PoisonError as e:
                rows, owed = self._drop_poisoned(rows, e, ran)
                if not rows:
                    return []
                if owed is None:
                    with self._span("serve.decode.plan"):
                        inputs = self._decode_inputs(rows)
                    continue
                tokens, finite = owed
            break
        self._note_work(sp, rows=len(rows), bucket=bucket)
        with self._span("serve.decode.commit"):
            self._mark_compiled(key, wait.t1 - launch.t0)
            return self._commit_rows(rows, tokens, finite, launch.t0,
                                     wait.t1)

    def _commit_rows(self, rows: List[_Seq], next_tok: np.ndarray,
                     finite: np.ndarray, t0: float, t1: float) -> List[_Seq]:
        """The decoded rows' bookkeeping from what their program's greedy
        head returned (``next_tok[i]`` and ``finite[i]`` are ``rows[i]``'s:
        the first index of its logits' maximum, and whether all of them
        were finite; entries past the rows are padding's): NaN screen,
        stamps, journal pairs, finish. The engine never holds logits.
        Returns the rows that finished."""
        live = list(enumerate(rows))
        if self._nan_check:
            # per-row screen: quarantine rows whose logits went
            # non-finite; the survivors' tokens stand (rows are
            # independent)
            finite = finite[:len(rows)]
            if not bool(finite.all()):
                for i, seq in [p for p in live if not finite[p[0]]]:
                    self._quarantine(seq, "non-finite decode logits")
                live = [p for p in live if finite[p[0]]]
        if self.tracer is not None:
            self.tracer.decode([s.req.request_id for _, s in live],
                               t0, t1, self.iteration)
        self._last_tokens += len(live)
        done = []
        now = self._now()
        for i, seq in live:
            seq.n_cached += 1
            seq.tokens.append(int(next_tok[i]))
            self._jtoks.append((seq.req.request_id, seq.tokens[-1]))
            if seq.first_token_t is None:
                seq.first_token_t = now
                self.slo["ttft"].record(now - seq.arrival)
            elif seq.token_times:
                self.slo["tpot"].record(now - seq.token_times[-1])
            seq.token_times.append(now)
            if seq.done():
                self._finish_seq(seq, t1)
                done.append(seq)
        faults.inject("serve.decode.after",
                      rids=[s.req.request_id for _, s in live])
        return done

    def _decode_spec_batch(self, sp: _Phase) -> List[_Seq]:
        """Speculative decode iteration: up to K host-chained DRAFT
        steps propose lookahead tokens per RUNNING row, then ONE batched
        base-model verification pass scores all K+1 positions through
        the multi-token paged read and commits only the accepted
        prefix's KV (ops/paged_attention paged_verify_commit*).

        Determinism contract: every emitted token is the BASE model's
        own greedy argmax at its position — the draft only chooses how
        many positions one iteration can confirm — so the stream is
        bit-identical to sequential decode (PARITY.md) and the journal
        only ever sees verified tokens."""
        K = self.draft_k
        with self._span("serve.decode.plan"):
            # per-row lookahead cap: never past max_new (admission's worst-
            # case bound) or the table width; floor 1 means the degenerate
            # row still advances one token — the verify path IS the decode
            # path, one uniform program family
            ready: List[_Seq] = []
            caps: Dict[int, int] = {}
            for seq in [s for s in self.active if s.state == RUNNING]:
                if seq.state != RUNNING:
                    continue
                remaining = seq.req.max_new_tokens - len(seq.generated)
                t_cap = max(1, min(K + 1, remaining,
                                   self.serve.max_seq_len - seq.n_cached))
                ok = (self._alloc_for(seq, seq.n_cached + t_cap)
                      and self._cow_span(seq, seq.n_cached, t_cap))
                # shrink the lookahead before evicting anyone: in-flight
                # draft tokens are free to drop (they cost accept-rate,
                # never correctness)
                while not ok and t_cap > 1:
                    t_cap -= 1
                    record_counter("serve.spec_shrink")
                    ok = (self._alloc_for(seq, seq.n_cached + t_cap)
                          and self._cow_span(seq, seq.n_cached, t_cap))
                while not ok and self._evict_one(protect=seq):
                    t_cap = 1
                    ok = (self._alloc_for(seq, seq.n_cached + 1)
                          and self._cow_span(seq, seq.n_cached, 1))
                if ok:
                    ready.append(seq)
                    caps[seq.req.request_id] = t_cap
                else:
                    record_counter("serve.decode_stall")
            rows = [s for s in ready if s.state == RUNNING]
            if not rows:
                return []
        faults.inject("serve.decode.before",
                      rids=[s.req.request_id for s in rows])
        # -- draft phase: K batched single-token steps, host-chained.
        # Each step feeds one token per still-proposing row; rows past
        # their window become padding rows (null table -> block-0
        # scribble, the established convention). The first proposing
        # step for a row feeds tokens[-1] — identical to what verify
        # feeds as fed[:, 0] — so catch-up and proposal steps are the
        # same compiled program.
        proposals: Dict[int, List[int]] = {}
        last_out: Dict[int, int] = {}
        bucket = next(b for b in self.serve.decode_buckets
                      if b >= len(rows))
        with self._span("serve.draft", rows=len(rows), bucket=bucket, k=K):
            for _ in range(K):
                toks = np.zeros((bucket,), np.int32)
                positions = np.zeros((bucket,), np.int32)
                tables = np.zeros((bucket, self.serve.max_nb), np.int32)
                stepping = []
                for i, seq in enumerate(rows):
                    rid = seq.req.request_id
                    if seq.draft_pos >= seq.n_cached + caps[rid] - 1:
                        continue  # window proposed through: padding row
                    p = seq.draft_pos
                    toks[i] = (seq.tokens[p] if p < len(seq.tokens)
                               else last_out[rid])
                    positions[i] = p
                    tables[i] = pad_table(seq.blocks, self.serve.max_nb)
                    stepping.append((i, seq))
                if not stepping:
                    break
                with self._launch_span("serve.draft.launch",
                                       ("draft", bucket)) as launch:
                    fn = self._step_fn("decode", self._draft_frozen,
                                       quant=False)
                    res = fn(
                        self.draft_params, *self.kv_draft,
                        jnp.asarray(tables), jnp.asarray(positions),
                        jnp.asarray(toks))
                    nxt, self.kv_draft = res[0], res[2:]
                with self._span("serve.draft.wait") as wait:
                    nxt, = self._fetch(sp, [nxt])  # host-chained: each draft token feeds the next draft step
                self._mark_compiled(("draft", bucket), wait.t1 - launch.t0)
                for i, seq in stepping:
                    rid = seq.req.request_id
                    seq.draft_pos += 1
                    last_out[rid] = int(nxt[i])
                    if seq.draft_pos > seq.n_cached:
                        proposals.setdefault(rid, []).append(int(nxt[i]))
        # -- verify phase: one batched K+1-position base pass; the
        # re-drive loop mirrors sequential decode's (rows independent)
        T = K + 1
        out = clen = fin = None
        key = None
        with self._span("serve.verify", k=K) as verify:
            while rows:
                rids = [s.req.request_id for s in rows]
                bucket = next(b for b in self.serve.decode_buckets
                              if b >= len(rows))
                fed = np.zeros((bucket, T), np.int32)
                qstart = np.zeros((bucket,), np.int32)
                t_live = np.zeros((bucket,), np.int32)
                tables = np.zeros((bucket, self.serve.max_nb), np.int32)
                for i, seq in enumerate(rows):
                    rid = seq.req.request_id
                    props = proposals.get(rid, [])[:caps[rid] - 1]
                    fed[i, 0] = seq.tokens[-1]
                    fed[i, 1:1 + len(props)] = props
                    qstart[i] = seq.n_cached
                    t_live[i] = 1 + len(props)
                    tables[i] = pad_table(seq.blocks, self.serve.max_nb)
                key = ("verify", bucket)
                try:
                    faults.inject("serve.decode.poison", rids=rids)
                    with self._launch_span("serve.verify.launch",
                                           key) as launch:
                        fn = self._step_fn("verify", self._frozen)
                        res = fn(
                            self.params, *self.kv,
                            jnp.asarray(tables), jnp.asarray(qstart),
                            jnp.asarray(t_live), jnp.asarray(fed))
                        (out, clen, fin), self.kv = res[:3], res[3:]
                    with self._span("serve.verify.wait") as wait:
                        out, clen, fin = self._fetch(sp, [out, clen, fin])
                    faults.inject("serve.decode.logits", rids=rids,
                                  tokens=out, finite=fin)
                except PoisonError as e:
                    if not self._pools_alive():
                        raise  # donated pools died mid-kernel: journal path
                    bad = next((s for s in rows
                                if s.req.request_id == e.rid), None)
                    if bad is None:
                        raise  # not attributable to this batch
                    self._quarantine(bad, e.cause)
                    rows = [s for s in rows if s is not bad]
                    self._redrives += 1
                    record_counter("serve.decode_redrive")
                    continue
                break
            if not rows:
                return []
            verify.note(rows=len(rows), bucket=bucket)
        self._note_work(sp, rows=len(rows), bucket=bucket)
        with self._span("serve.decode.commit"):
            t0, t1 = launch.t0, wait.t1
            self._mark_compiled(key, t1 - t0)
            live = list(enumerate(rows))
            if self._nan_check:
                # the verify step returns tokens, not logits, so the
                # finite screen is computed inside the jit and surfaced
                # per row
                finite = fin[:len(rows)]
                if not bool(finite.all()):
                    for i, seq in [p for p in live if not finite[p[0]]]:
                        self._quarantine(seq, "non-finite decode logits")
                    live = [p for p in live if finite[p[0]]]
            if self.tracer is not None:
                self.tracer.decode([s.req.request_id for _, s in live],
                                   t0, t1, self.iteration)
            done: List[_Seq] = []
            now = self._now()
            for i, seq in live:
                rid = seq.req.request_id
                self._spec_proposed += int(t_live[i]) - 1
                # accepted draft credit = commit_len - 1: the +1 is the
                # base's own correction/next token, not the draft's
                self._spec_accepted += max(0, int(clen[i]) - 1)
                emitted = 0
                for j in range(int(clen[i])):
                    seq.n_cached += 1
                    seq.tokens.append(int(out[i, j]))
                    self._jtoks.append((rid, seq.tokens[-1]))
                    emitted += 1
                    if seq.first_token_t is None:
                        seq.first_token_t = now
                        self.slo["ttft"].record(now - seq.arrival)
                    elif seq.token_times:
                        self.slo["tpot"].record(now - seq.token_times[-1])
                    seq.token_times.append(now)
                    if seq.done():
                        # eos/max_new inside the window: later verified
                        # tokens are exactly what sequential decode would
                        # have produced AFTER stopping — discard them
                        break
                self._last_tokens += emitted
                # roll the draft back to the last verified position: its
                # cache past the accepted prefix reflects rejected tokens
                seq.draft_pos = min(seq.draft_pos, seq.n_cached)
                if seq.done():
                    self._finish_seq(seq, t1)
                    done.append(seq)
            faults.inject("serve.decode.after",
                          rids=[s.req.request_id for _, s in live])
        return done

    # -- preemption + live weight push (PR 13) ------------------------------

    def request_preemption(self) -> None:
        """Signal a graceful stop: run() exits at the next iteration
        boundary with queued/active requests intact (thread/signal safe)."""
        self._preempt.set()

    def clear_preemption(self) -> None:
        """Re-arm a preempted engine: run() continues from intact queue/
        active state (deterministic replay resumes bit-identically)."""
        self._preempt.clear()

    def install_preemption_handler(self, signum: int = signal.SIGTERM) -> None:
        """SIGTERM -> request_preemption(); the loop itself never runs
        device code from the handler."""
        try:
            self._prev_handler = signal.signal(
                signum, lambda s, f: self._preempt.set())
            self._signum = signum
        except ValueError:
            warnings.warn(
                "cannot install a signal handler off the main thread; "
                "use request_preemption()", RuntimeWarning)

    def uninstall_preemption_handler(self) -> None:
        if self._signum is not None:
            signal.signal(self._signum, self._prev_handler or signal.SIG_DFL)
            self._signum = None
            self._prev_handler = None

    def swap_weights(self, source, at_iteration: Optional[int] = None
                     ) -> Dict[str, Any]:
        """Live weight push: replace the model weights without restarting
        the engine or dropping a request.

        `source` is a checkpoint directory (a ``save_state_dict`` dir or a
        CheckpointManager root, whose newest complete checkpoint is used)
        or an in-memory param pytree. The new tree must match the current
        one exactly — same structure, shapes, dtypes (same compiled step
        family, so no recompile). Each leaf is placed onto the CURRENT
        leaf's sharding and rebound in place, one leaf at a time (peak
        extra memory = one weight); the KV pools, block tables and all
        scheduler state are untouched.

        With ``at_iteration`` the swap is deferred to that iteration's
        boundary — the safe drain point: the previous decode has synced
        its sampled tokens, nothing is in flight. Called without it, the
        swap applies immediately (between run() calls, or before serving
        starts). With identical weights the post-swap token stream is
        bit-identical; in-flight sequences keep their KV prefix either
        way (their earlier tokens reflect the old weights — the standard
        live-update contract)."""
        if at_iteration is not None and at_iteration > self.iteration:
            self._pending_swap = (source, int(at_iteration))
            self._event("swap_scheduled", int(at_iteration))
            return {"scheduled_at": int(at_iteration)}
        return self._apply_swap(source)

    def _resolve_swap_source(self, source):
        if not isinstance(source, str):
            return source, None
        path = os.path.abspath(source)
        from ..distributed.checkpoint import save_load as sl
        from ..distributed.checkpoint.manager import (CheckpointManager,
                                                      _STEP_RE)
        try:
            entries = os.listdir(path)
        except OSError:
            entries = []
        if any(_STEP_RE.match(n) for n in entries):
            # a manager root: serve from its newest complete checkpoint
            resolved = CheckpointManager(path).latest_path()
            if resolved is None:
                raise FileNotFoundError(
                    f"swap_weights: no complete checkpoint under {path!r}")
            path = resolved
        with sl._pending_lock:
            prev = sl._pending.get(path)
        if prev is not None:
            prev.wait()  # an in-flight async save to this very dir
        import orbax.checkpoint as ocp
        restored = ocp.PyTreeCheckpointer().restore(path)
        if isinstance(restored, dict):
            for sidecar in ("sharding_meta.json", "manifest.json",
                            "COMMIT.json"):
                restored.pop(sidecar, None)
            # a TrainStep/manager checkpoint nests weights under "params"
            if "params" in restored and "params" not in self.params:
                restored = restored["params"]
        return restored, path

    def _apply_swap(self, source) -> Dict[str, Any]:
        faults.inject("serve.swap.before", iteration=self.iteration)
        t0 = time.perf_counter()
        new_tree, path = self._resolve_swap_source(source)
        n_leaves = [0]

        def swap_fill(target, saved, leaf_path):
            if isinstance(target, dict):
                if not isinstance(saved, dict) or set(target) != set(saved):
                    raise ValueError(
                        f"swap_weights: param tree mismatch at "
                        f"{leaf_path or '<root>'!r}: engine has "
                        f"{sorted(target) if isinstance(target, dict) else type(target)}, "
                        f"source has "
                        f"{sorted(saved) if isinstance(saved, dict) else type(saved)}")
                for k in target:
                    target[k] = swap_fill(
                        target[k], saved[k],
                        f"{leaf_path}.{k}" if leaf_path else str(k))
                return target
            if isinstance(target, (list, tuple)):
                if not isinstance(saved, (list, tuple)) \
                        or len(target) != len(saved):
                    raise ValueError(
                        f"swap_weights: param tree mismatch at "
                        f"{leaf_path!r}")
                out = [swap_fill(t, s, f"{leaf_path}[{i}]")
                       for i, (t, s) in enumerate(zip(target, saved))]
                return type(target)(out)
            shape = tuple(np.shape(saved))
            if tuple(target.shape) != shape:
                raise ValueError(
                    f"swap_weights: shape mismatch at {leaf_path!r}: "
                    f"engine {tuple(target.shape)}, source {shape}")
            # place onto the CURRENT leaf's sharding/dtype: the compiled
            # decode/prefill steps see identical avals, so no recompile;
            # the old buffer frees as soon as this rebind drops it
            arr = jnp.asarray(np.asarray(saved), dtype=target.dtype)  # noqa: PTA006 -- swap boundary is a drain point by contract; source is host-resident
            sh = getattr(target, "sharding", None)
            if sh is not None:
                arr = jax.device_put(arr, sh)
            n_leaves[0] += 1
            return arr

        drained_running = sum(1 for s in self.active if s.state == RUNNING)
        drained_prefill = sum(1 for s in self.active if s.state == PREFILL)
        if isinstance(self.params, dict):
            swap_fill(self.params, new_tree, "")
        else:
            self.params = swap_fill(self.params, new_tree, "")
        self.swaps += 1
        record_counter("serve.swap")
        stats = {
            "iteration": self.iteration,
            "swap_ms": (time.perf_counter() - t0) * 1e3,
            "n_leaves": n_leaves[0],
            "in_flight_running": drained_running,
            "in_flight_prefill": drained_prefill,
            "source": path,
        }
        self.last_swap = stats
        self._event("swap", n_leaves[0])
        if self.recorder is not None:
            self.recorder.record({"iteration": self.iteration,
                                  "event": "swap", **{
                                      k: v for k, v in stats.items()
                                      if k != "iteration"}})
        if self._journal is not None:
            self._journal.swap(self.iteration, path)
        faults.inject("serve.swap.after", iteration=self.iteration)
        return stats

    # -- driving loops ------------------------------------------------------

    def _now(self) -> float:
        return self._clock

    def run(self, requests: Sequence[Request],
            deterministic: bool = False, max_iterations: int = 100000
            ) -> Dict[str, Any]:
        """Drive the engine until every request finishes.

        Wall mode (default): ``arrival`` is seconds from start; the
        engine clock is wall time and idle gaps are slept through.
        Deterministic mode: ``arrival`` is an ITERATION index and the
        clock counts iterations — replaying the same trace must
        reproduce the same event log and tokens bit-for-bit
        (scheduling never consults wall time)."""
        pending = sorted(requests, key=lambda r: r.arrival)
        t0 = time.perf_counter()
        try:
            while pending or not self.idle():
                if self._preempt.is_set() or faults.fires("serve.preempt"):
                    # graceful preemption: stop at the iteration boundary
                    # (nothing in flight), dump the post-mortem ring and
                    # return — queued/active work stays intact for a
                    # successor engine to re-drive
                    self._was_preempted = True
                    record_counter("serve.preempted")
                    self._event("preempt_stop")
                    if self.recorder is not None:
                        self.recorder.dump("preemption")
                    break
                if self.iteration >= max_iterations:
                    raise RuntimeError("engine exceeded max_iterations")
                self._clock = (float(self.iteration) if deterministic
                               else time.perf_counter() - t0)
                while pending and pending[0].arrival <= self._clock:
                    self.submit(pending.pop(0))
                if self.idle() and pending:
                    if deterministic:
                        self.iteration += 1
                    else:
                        time.sleep(min(
                            pending[0].arrival - self._clock, 0.01))
                    continue
                self.step()
                if not deterministic:
                    self._clock = time.perf_counter() - t0
        except BaseException:
            # a crashed run must leave a LEAK-FREE pool: demote every
            # live sequence to the front of the waiting queue (eviction-
            # style, order preserved) with its blocks released, so a
            # successor engine — or recover() — inherits clean state
            while self.active:
                seq = self.active.pop()
                self._release(seq)
                seq.state = WAITING
                seq.n_cached = 0
                seq.draft_pos = 0
                self.waiting.insert(0, seq)
            # crash post-mortem: dump the last N iteration records before
            # the exception leaves the engine (no-op without a recorder
            # or a telemetry dir)
            if self.recorder is not None:
                self.recorder.dump("exception")
            raise
        if self._journal is not None:
            # clean exit: drain the buffered tokens/finish marks so the
            # on-disk journal of an idle engine is always complete
            self._journal.flush()
        return self.stats()

    def drain(self, deterministic: bool = False,
              max_iterations: int = 100000
              ) -> Dict[int, Tuple[str, Optional[str]]]:
        """Graceful wind-down: stop admitting (every later ``submit()``
        rejects with cause ``draining``), run the already-accepted work
        to completion, and return the total :meth:`outcomes` map. The
        overload contract holds throughout — ``outcomes()`` stays total
        during and after the drain, with drained-away submissions
        showing as ``("rejected", "draining")``. The engine stays
        usable: :meth:`undrain` re-opens admissions (the fleet's
        rolling weight swap drains, swaps, then undrains each replica
        in turn)."""
        self._draining = True
        record_counter("serve.drain")
        self._event("drain")
        self.run([], deterministic=deterministic,
                 max_iterations=max_iterations)
        return self.outcomes()

    def undrain(self) -> None:
        """Re-open admissions after :meth:`drain`."""
        self._draining = False
        self._event("undrain")

    def recover(self, journal_path: Optional[str] = None
                ) -> Dict[str, Any]:
        """Rebuild scheduler state from an engine journal after a crash.

        The journal holds every accepted request and every token the
        dead engine emitted. Greedy decoding is deterministic in
        (prompt + generated history), so re-queueing each unfinished
        request with its journaled tokens and re-driving it through the
        ordinary preempted-sequence path (re-prefill the cached
        context, resume decoding) reproduces the remaining stream
        bit-identically — tokens emitted after the journal's last flush
        are simply re-derived. Call on a FRESH engine, or on one whose
        ``run()`` raised (its demoted sequences are discarded in favor
        of the journal's authoritative record); then ``run([])`` drives
        the recovered requests to completion. The journal is reopened
        for append, so the recovered engine keeps journaling."""
        path = journal_path or self.journal_path
        if not path:
            raise ValueError(
                "recover() needs a journal: pass journal_path= or build "
                "the engine with journal=/PADDLE_TPU_SERVE_JOURNAL")
        st = read_journal(path)
        # up-front portability screen (PR 20): either this engine can
        # re-drive the journal bit-identically, or refuse before any
        # state is touched. kv_dtype is the one stream-changing axis
        # (int8 quantization is the documented numeric deviation);
        # mp / prefix_cache / speculative differences recover freely —
        # PARITY.md pins their streams as bit-identical.
        j_dtype = st.meta.get("kv_dtype")
        if j_dtype is not None and j_dtype != self.kv_dtype:
            raise JournalCompatError(
                f"recover(): journal {path!r} was written with "
                f"kv_dtype={j_dtype!r} but this engine stores "
                f"{self.kv_dtype!r}; crossing the int8 quantization "
                f"boundary changes token streams, so the re-drive "
                f"would not be bit-identical")
        for rid in st.unfinished_rids():
            rec = st.requests[rid]
            worst = len(rec["prompt"]) + int(rec["max_new_tokens"])
            if worst > self.serve.max_seq_len:
                raise JournalCompatError(
                    f"recover(): journaled request {rid} needs {worst} "
                    f"tokens but this engine's max_seq_len is "
                    f"{self.serve.max_seq_len}")
            if self.pool.blocks_for(worst) > self.serve.num_blocks - 1:
                raise JournalCompatError(
                    f"recover(): journaled request {rid} can never fit "
                    f"this engine's pool ({worst} tokens > "
                    f"{self.serve.num_blocks - 1} usable blocks)")
        for seq in itertools.chain(self.active, self.waiting):
            self._release(seq)
        self.active, self.waiting = [], []
        if self.pool.used_blocks:
            raise RuntimeError(
                f"recover(): pool leaked {self.pool.used_blocks} blocks")
        terminal = st.terminal_rids()
        n_replayed = n_prefinished = 0
        for rid in st.unfinished_rids():
            rec = st.requests[rid]
            req = Request(
                prompt=rec["prompt"],
                max_new_tokens=rec["max_new_tokens"],
                request_id=rid, eos_id=rec.get("eos_id"),
                arrival=float(rec.get("arrival", 0.0)),
                priority=int(rec.get("priority", 0)),
                ttft_deadline=rec.get("ttft_deadline"),
                deadline=rec.get("deadline"))
            seq = _Seq(req, self._clock)
            seq.order = next(self._seqno)
            seq.tokens.extend(st.tokens.get(rid, ()))
            seq.recovered = True
            if seq.generated:
                # its first token predates this engine: keep the SLO
                # histograms honest by not re-measuring TTFT
                seq.first_token_t = seq.arrival
            if seq.done():
                # crashed after its last token but before its finish
                # mark was journaled: already complete, no re-drive
                seq.state = FINISHED
                self.finished.append(seq)
                n_prefinished += 1
            else:
                self.waiting.append(seq)
                n_replayed += 1
        self._recovered = n_replayed + n_prefinished
        known = list(st.requests) + list(st.rejected)
        if known:
            self._rid = itertools.count(max(known) + 1)
        if self._journal is None:
            self._journal = EngineJournal(
                path, fsync=envs.get(ENV_SERVE_JOURNAL_FSYNC),
                resume=True, meta=self._journal_meta())
            self.journal_path = path
        else:
            # in-place recovery after run() raised: the writer may hold
            # token pairs from before the crash — they predate the read
            # above, and draining them now would duplicate streams
            self._journal.discard_pending()
        self._journal.recovered(self._recovered, st.torn_lines)
        for seq in self.finished[len(self.finished) - n_prefinished:]:
            self._journal.finish(seq.req.request_id)
        record_counter("serve.recover")
        self._event("recover", self._recovered)
        return {
            "recovered": self._recovered,
            "replayed": n_replayed,
            "already_finished": n_prefinished,
            "terminal_in_journal": len(terminal),
            "torn_lines": st.torn_lines,
            "journal_swaps": st.swaps,
        }

    def stats(self) -> Dict[str, Any]:
        """Throughput/latency aggregates over finished requests (times
        in the engine clock: seconds in wall mode, iterations in
        deterministic mode).

        Requests that never produced a first token — still queued, mid-
        prefill, or evicted at shutdown — are counted in ``unfinished``
        rather than silently dropped, so the TTFT percentiles are
        explicitly conditioned on completion instead of optimistically
        biased. The ``*_stream_*`` entries are the live log-bucketed
        histogram estimates next to the exact percentiles (they must
        agree within one bucket)."""
        seqs = self.finished
        gen = sum(len(s.generated) for s in seqs)
        ttfts = [s.first_token_t - s.arrival for s in seqs
                 if s.first_token_t is not None]
        gaps: List[float] = []
        for s in seqs:
            gaps.extend(np.diff(s.token_times).tolist())  # noqa: PTA006 -- host timing stats over Python floats, no device data
        span = (max((s.token_times[-1] for s in seqs if s.token_times),
                    default=0.0)
                - min((s.arrival for s in seqs), default=0.0))
        pct = (lambda a, q: float(np.percentile(a, q)) if a else None)
        unfinished = (len(self.waiting) + len(self.active)
                      + sum(1 for s in seqs if s.first_token_t is None))
        return {
            "requests": len(seqs),
            "unfinished": unfinished,
            "generated_tokens": gen,
            "elapsed_s": span,
            "tokens_per_sec": gen / span if span > 0 else None,
            "ttft_p50_s": pct(ttfts, 50),
            "ttft_p99_s": pct(ttfts, 99),
            "tpot_p50_s": pct(gaps, 50),
            "tpot_p99_s": pct(gaps, 99),
            "ttft_stream_p50_s": self.slo["ttft"].percentile(50),
            "ttft_stream_p99_s": self.slo["ttft"].percentile(99),
            "tpot_stream_p50_s": self.slo["tpot"].percentile(50),
            "tpot_stream_p99_s": self.slo["tpot"].percentile(99),
            "preemptions": self.preemptions,
            "preempted": self._was_preempted,
            "weight_swaps": self.swaps,
            "iterations": self.iteration,
            "compiles": {"_".join(map(str, key)): round(t, 3)
                         for key, t in sorted(self._compiled.items())},
            "pool_blocks": self.serve.num_blocks - 1,
            "mp": self.mp,
            "pool_bytes_per_rank": pool_bytes_per_rank(
                self.kv + self.kv_draft, self.mp),
            # recurrent state (a model that has it): the slot-indexed arrays'
            # bytes and the slots sequences hold now, beside the pool's
            "state_bytes": sum(a.nbytes for a in self.state),
            "state_slots_in_use": (self.slots.used_slots
                                   if self.slots is not None else 0),
            "rejected": len(self.rejected),
            "shed": len(self.shed),
            "failed": len(self.failed),
            "decode_redrives": self._redrives,
            "recovered": self._recovered,
            "kv_dtype": self.kv_dtype,
            "prefix_cache": (dict(self.cache.stats(),
                                  cached_blocks=self.pool.cached_blocks,
                                  cow_copies=self._cow_copies)
                             if self.cache is not None else None),
            "speculative": ({
                "draft_k": self.draft_k,
                "draft_layers": self.draft_config.num_hidden_layers,
                "proposed": self._spec_proposed,
                "accepted": self._spec_accepted,
                "accept_rate": (self._spec_accepted / self._spec_proposed
                                if self._spec_proposed else None),
            } if self.speculative else None),
            "outcomes": self.outcomes(),
        }

    def outcomes(self) -> Dict[int, Tuple[str, Optional[str]]]:
        """Disposition of EVERY request this engine has seen:
        ``rid -> (state, cause)``. The overload contract — nothing is
        silently dropped — means each submitted request appears here in
        exactly one state (terminal: finished/rejected/shed/failed with
        a cause; live requests report their current scheduler state)."""
        out: Dict[int, Tuple[str, Optional[str]]] = {}
        for req, cause in self.rejected:
            out[req.request_id] = ("rejected", cause)
        for seq in self.finished:
            out[seq.req.request_id] = (FINISHED, None)
        for seq in self.shed:
            out[seq.req.request_id] = (SHED, seq.fail_cause)
        for seq in self.failed:
            out[seq.req.request_id] = (FAILED, seq.fail_cause)
        for seq in itertools.chain(self.waiting, self.active):
            out[seq.req.request_id] = (seq.state, None)
        return out

    def metrics_snapshot(self) -> Dict[str, Any]:
        """Live metric snapshot, any time mid-run: the streaming SLO
        histograms plus scheduler gauges, straight from the unified
        :class:`~paddle_tpu.observability.MetricsRegistry` (key order is
        the registration order, unchanged from the pre-PR-15 dict)."""
        return self.registry.snapshot()

    def render_prometheus(self) -> str:
        """Prometheus text exposition via the unified registry (sample
        lines byte-identical to the legacy dict renderer; ``# HELP``/
        ``# TYPE`` pairs ahead of each family)."""
        return self.registry.render_prometheus()
