"""Continuous-batching serving engine for the Llama family.

Reference capability: the reference's serving path — AnalysisPredictor +
paged `block_multi_head_attention` / `masked_multihead_attention`
kernels (`fluid/inference/api/analysis_predictor.h:100`,
`phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu`). The
reference has no in-tree continuous-batching scheduler; this engine goes
beyond it (vLLM-style): requests are admitted and retired on the fly,
every live sequence decodes one token per engine step in a single
batched program, and KV lives in a shared paged pool so ragged contexts
waste no HBM.

Design (TPU-first, chunked prefill over ONE mixed program):
- ONE :class:`PageAllocator` shared by all layers (page structure is
  identical per layer); per-layer K/V pools are device arrays updated
  functionally.
- EVERY engine step is one dispatch of a single **mixed program** over
  a token-packed batch: variable-length prefill chunks and single-token
  decode rows ride in the same static-shape dispatch, attention served
  by the Pallas ``ragged_paged_attention`` kernel (per-row
  ``(q_start, q_len, kv_len)`` metadata over the shared block tables —
  the *Ragged Paged Attention* design, arXiv 2604.15464). There is no
  separate prefill program, no per-bucket compilation, and no
  wave-then-burst phase split: a long prompt is split into
  ``chunk_block``-sized chunks that interleave with live decodes under
  a per-step ``chunk_budget`` token budget, so admitting a 10k-token
  prompt never stalls a live decode for more than one chunk.
- The program packs real tokens [T = chunk_budget] (embed → per layer:
  rms_norm → qkv → rope at per-token positions → page write → ragged
  paged attention → o_proj → swiglu MLP → logits at each row's last
  token → greedy argmax); pad tokens scatter to a reserved trash page
  and inactive rows carry ``kv_len 0``, so shapes never change and two
  executables (the ``chunk_budget``-token mixed shape and the
  [max_batch]-token decode-only shape) cover the engine's lifetime.
- The continuous loops (:meth:`LlamaServingEngine.step_ahead`: the
  replica's worker loop, ``generate``, ``drain``) run ONE DISPATCH
  AHEAD: a turn plans, builds and enqueues dispatch n+1 and only then
  waits for dispatch n's tokens and applies them, so the host's turn
  runs beside the device's step, not between two of them. What a plan
  needs of the dispatch in flight does not depend on its tokens' values
  (prompt positions prefilled, tokens emitted against
  ``max_new_tokens``); a decode row's input token is taken where the
  device left it (`DispatchLayout`'s ``prev_idx``). A row launched for
  a sequence that ended meanwhile is stale and dropped at apply.
  ``step()`` launches a dispatch and finishes it, as it always did.
- Sustained decode amortizes the host round trip with ``lax.scan``
  over the SAME mixed step (``decode_ticks`` tokens per sequence per
  dispatch, pages reserved up front, lengths advancing on device as
  the scan carry) — the scan body is the one mixed-program function,
  not a separate decode path.

Speculative decoding (latency layer, ROADMAP item 3a):
- With ``spec_k > 0`` every fully-prefilled decoder may carry up to k
  draft tokens from a per-sequence self-speculative drafter
  (:mod:`paddle_tpu.inference.speculative` — an n-gram prompt-lookup
  table over the request's own prompt+output; no extra weights). The
  scheduler packs the row into the mixed step as a (q_len = k+1)
  chunk over pages the drafts were tentatively written to; batched
  verification reads the argmax at EVERY position and accepts the
  longest exactly-matching draft prefix, so greedy outputs are
  token-exact vs the non-speculative engine by construction. Rejected
  draft pages roll back via :meth:`PageAllocator.rollback` before the
  next step, and when the drafter has nothing to propose the engine
  falls back to ordinary decode (scans included) — speculation never
  costs more than not speculating.

Int8 KV pages (capacity layer, ROADMAP item 3b):
- ``kv_dtype="int8"`` (or ``PADDLE_TPU_KV_DTYPE=int8``) stores the
  page pools as int8 with per-head per-slot f32 scale sidecars,
  quantizing on write and dequantizing inside the ragged kernel's kv
  loop — half (bf16) to a quarter (f32) of the HBM bytes per cached
  token (``kv_page_bytes_per_token``), so the same pool admits ~2x
  the batch/context before the degradation ladder fires. Sidecars
  are indexed by page id, so prefix-shared pages carry their scales
  and a copy-on-write copies both.

Shared-prefix KV cache (scale-out layer):
- Page-aligned prompt prefixes are content-addressed
  (:mod:`paddle_tpu.inference.prefix_cache`): a cold prompt's full
  pages are pinned once its prefill completes, and a later prompt
  sharing that prefix admits directly against the cached pages
  (refcounted in :class:`PageAllocator`, copy-on-write on any write
  into a shared page). Only the un-cached suffix runs through the
  model — as ordinary prefill chunks of the mixed program, typically
  ONE dispatch — so a 1k-token system prompt is prefilled once per
  replica, not once per request.
  ``serving_prefix_cache_hit_total`` /
  ``serving_prefix_saved_prefill_tokens_total`` make the win visible;
  under pool pressure cached pages are evicted (LRU, chain tails
  first) before the degradation ladder touches live requests.

Request lifecycle (robustness layer):
- Every request moves through ``status``: ``pending`` → ``live`` →
  one of ``completed`` / ``deadline_exceeded`` / ``cancelled`` /
  ``requeued`` (evicted under pressure, will retry) / ``paused``
  (pages parked in the host-DRAM KV tier —
  :mod:`paddle_tpu.inference.kv_tier` — resumes without re-prefill) /
  ``evicted`` (retry budget exhausted). Terminal failures carry a
  typed exception in ``req.error`` — never a silently truncated
  output.
- **Deadlines**: ``Request(deadline=...)`` (wall-clock TTL from
  admission) and ``Request(token_budget=...)`` (seconds per generated
  token) are enforced at step/scan boundaries; an expired request's
  pages go back to the :class:`PageAllocator` and the next admission
  can use them.
- **Cancellation**: :meth:`LlamaServingEngine.cancel` is thread-safe
  and idempotent — safe to fire from a client-abandon callback while
  another thread drives ``step()``; page release is deferred past any
  in-flight dispatch so compiled batch shapes are never disturbed.
- **Degradation ladder**: under admission pressure the engine first
  *trims* (truncate a lower-priority request's ``max_new_tokens`` to
  what it already produced, retiring it with partial output), then
  *evicts* (reclaim the lowest-priority request's pages and re-queue
  it against its ``retry_budget``), then *sheds* with a typed
  :class:`AdmissionError` carrying a ``retry_after`` hint.
- **Graceful drain**: :meth:`LlamaServingEngine.drain` stops admission
  and finishes or expires the in-flight set within a grace window;
  :meth:`install_drain_handler` wires that to SIGTERM (the preemption
  notice) for a clean exit — the serving analog of the checkpoint
  manager's preemption handler.
- **Stuck-dispatch watchdog**: a warm decode dispatch exceeding
  ``stuck_factor`` × its observed P99 trips a
  :class:`~paddle_tpu.distributed.watchdog.StepWatchdog`, which dumps
  a flight-recorder post-mortem.
Fault points ``serve.admit`` / ``serve.decode`` / ``serve.drain``
(:mod:`paddle_tpu.testing.faults`) make each path reproducibly
testable.
"""

from __future__ import annotations

import collections
import contextlib
import hashlib
import math
import os
import signal as _signal
import threading
import time

import jax.numpy as jnp
import numpy as np

from ..framework.tensor import Tensor, no_grad, run_op
from ..observability import compile_watch as _cw
from ..observability import flight_recorder as _fr
from ..observability import metrics as _om
from ..observability import tracing as _tracing
from ..observability.trace import record as _record_span
from ..observability.trace import span as _span
from ..testing import faults as _faults
from .kv_tier import KvPageTier, TierError
from .layer_step import (DispatchLayout, PagedKV, PagedLatent, ServingStep,
                         SharedPages, SlotState, _token_gather)
from .paged_cache import PageAllocator
from .sampling import SamplingParams, sampled_next_tokens
from .speculative import NGramDrafter

__all__ = ["LlamaServingEngine", "Request", "AdmissionError",
           "DeadlineExceeded", "UnsupportedServingFeature"]


class AdmissionError(MemoryError):
    """Typed admission rejection carrying queue/pool stats so callers
    can shed load (429, redirect, re-queue) instead of crashing.

    Subclasses :class:`MemoryError` for backward compatibility with
    callers catching the engine's old bare raise; the serving
    ``_fatal_guard`` likewise treats it as a routine rejection, not a
    crash worth a flight-recorder dump.

    ``retry_after`` (seconds, may be None) estimates when capacity
    frees up — derived from the live set's shortest remaining token
    budget and recent per-token latency — so a frontend can answer
    with ``Retry-After`` instead of guessing.
    """

    def __init__(self, reason, live, max_batch, free_pages, num_pages,
                 retries, retry_after=None):
        msg = (f"{reason} (live={live}/{max_batch}, "
               f"free_pages={free_pages}/{num_pages}, "
               f"retries={retries})")
        if retry_after is not None:
            msg += f" — retry after {retry_after:.3f}s"
        super().__init__(msg)
        self.reason = reason
        self.live = live
        self.max_batch = max_batch
        self.free_pages = free_pages
        self.num_pages = num_pages
        self.retries = retries
        self.retry_after = retry_after

    def __reduce__(self):
        # default exception pickling replays type(self)(*args) with
        # args=(formatted msg,) — a TypeError at unpickle time, which
        # would turn a typed shed (retry_after and all) into an opaque
        # rpc failure on the error-reply round trip; rebuild from the
        # typed fields instead (mirrors RpcTimeoutError.__reduce__)
        return (type(self), (self.reason, self.live, self.max_batch,
                             self.free_pages, self.num_pages,
                             self.retries, self.retry_after))


class UnsupportedServingFeature(NotImplementedError):
    """An engine feature was asked for that a layer kind of the model
    cannot serve yet (``str(exc)`` names the feature and the layer)."""


class DeadlineExceeded(TimeoutError):
    """Typed terminal result of a request that ran out of wall-clock
    budget (TTL, per-token budget, or the drain grace window). The
    partial output stays on ``request.output_ids``; this error on
    ``request.error`` says *why* it is partial — never a silent
    truncation."""

    def __init__(self, msg, seq_id=None, elapsed=None, tokens_emitted=0,
                 reason="deadline"):
        super().__init__(msg)
        self.seq_id = seq_id
        self.elapsed = elapsed
        self.tokens_emitted = tokens_emitted
        self.reason = reason

    def __reduce__(self):
        # keep the carried fields (seq_id, tokens_emitted, ...) across a
        # pickle round trip — a subprocess replica reports deadline
        # expiry through the rpc error reply
        return (type(self), (self.args[0] if self.args else "",
                             self.seq_id, self.elapsed,
                             self.tokens_emitted, self.reason))

#: latency buckets tuned for serving (TTFT / per-token): 1ms .. 10s
_LATENCY_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                    0.25, 0.5, 1.0, 2.5, 5.0, 10.0)

#: Cross-ENGINE dispatch serializer. Framework mode state (grad mode,
#: AMP state, trace stacks, the compile watcher) is per-process, so two
#: engine INSTANCES tracing/dispatching from different threads (an
#: in-process multi-replica cluster) would interleave no_grad sections
#: and leak tracers. Each dispatch body takes this lock INSIDE its own
#: per-instance ``_dispatch_lock`` (consistent order: own lock first,
#: global second — no cycle), and it is released between a drain's
#: steps, so one replica draining never starves its peers. Re-entrant
#: because a step's requeue pump may prefill.
_CROSS_ENGINE_LOCK = threading.RLock()


def _serving_metrics():
    """Standard serving metric set on the default registry (no-ops when
    ``PADDLE_TPU_METRICS=0``). Counters aggregate across engines in the
    process; gauges reflect the engine that last updated them."""
    return {
        "admitted": _om.counter(
            "serving_requests_admitted_total",
            "requests admitted into the continuous batch"),
        "completed": _om.counter(
            "serving_requests_completed_total",
            "requests retired (EOS or max_new_tokens)"),
        "evicted": _om.counter(
            "serving_requests_evicted_total",
            "admission rejections (engine full / KV pages exhausted)"),
        "admit_retries": _om.counter(
            "serving_admission_retries_total",
            "admission attempts retried after backoff while waiting "
            "for capacity"),
        "deadline_exceeded": _om.counter(
            "serving_deadline_exceeded_total",
            "requests expired by TTL / token budget / drain grace"),
        "cancelled": _om.counter(
            "serving_cancelled_total",
            "requests cancelled by the client before completion"),
        "degraded": _om.counter(
            "serving_degraded_total",
            "degradation-ladder actions under admission pressure",
            labelnames=("rung",)),
        "paused": _om.counter(
            "serving_paused_total",
            "requests paused into the host-DRAM KV tier under pool "
            "pressure (pages D2H-copied, request parked)"),
        "resumed": _om.counter(
            "serving_resumed_total",
            "paused requests resumed by H2D page restore (no "
            "re-prefill)"),
        "postponed": _om.counter(
            "serving_pressure_postponed_total",
            "decode rows dropped from ONE dispatch because victim "
            "page releases were deferred (cross-thread entry in "
            "flight); no state change — the rows rejoin at the next "
            "boundary"),
        "drain_seconds": _om.gauge(
            "serving_drain_seconds",
            "duration of the last graceful drain"),
        "queue_depth": _om.gauge(
            "serving_queue_depth", "live requests in the engine"),
        "kv_util": _om.gauge(
            "serving_kv_page_utilization",
            "fraction of KV-cache pages in use (0 when idle)"),
        "ttft": _om.histogram(
            "serving_ttft_seconds",
            "admission -> first emitted token", buckets=_LATENCY_BUCKETS),
        "queue_wait": _om.histogram(
            "serving_queue_wait_seconds",
            "submission (the cluster's submit(); the engine's own "
            "admission call without one) -> first admission into the "
            "continuous batch", buckets=_LATENCY_BUCKETS),
        "dispatches": _om.counter(
            "serving_dispatches_total",
            "step programs dispatched, by kind: mixed (prefill chunks "
            "and decode rows), decode (one token a row) or scan",
            labelnames=("kind",)),
        "dispatch_tokens": _om.counter(
            "serving_dispatch_tokens_total",
            "token slots of the dispatched programs, by what filled "
            "them: prefill, decode (speculative drafts among them) or "
            "pad (slots the program's shape had and no row used)",
            labelnames=("kind",)),
        "stale_rows": _om.counter(
            "serving_dispatch_stale_rows_total",
            "rows of dispatched programs dropped at apply because their "
            "sequence had ended (EOS, a stop token, a cancel, a "
            "deadline, an eviction) after the row was launched"),
        "tpot": _om.histogram(
            "serving_token_latency_seconds",
            "per-token decode latency (scan dispatches amortized)",
            buckets=_LATENCY_BUCKETS),
        "prefill_tokens": _om.counter(
            "serving_prefill_tokens_total", "prompt tokens prefilled"),
        "generated": _om.counter(
            "serving_generated_tokens_total", "tokens emitted by decode"),
        "prefix_lookups": _om.counter(
            "serving_prefix_cache_lookup_total",
            "admissions that consulted the shared-prefix cache"),
        "prefix_hits": _om.counter(
            "serving_prefix_cache_hit_total",
            "admissions that reused cached prefix pages"),
        "prefix_saved": _om.counter(
            "serving_prefix_saved_prefill_tokens_total",
            "prompt tokens NOT prefilled because their pages were "
            "served from the shared-prefix cache"),
        "prefix_pages": _om.gauge(
            "serving_prefix_cache_pages",
            "KV pages currently pinned by the shared-prefix cache"),
        "prefill_backlog": _om.gauge(
            "serving_prefill_backlog_tokens",
            "prompt tokens admitted but not yet prefilled (the "
            "chunked-prefill queue; load-routing signal)"),
        "spec_proposed": _om.counter(
            "serving_spec_proposed_tokens_total",
            "draft tokens proposed by the speculative drafter"),
        "spec_accepted": _om.counter(
            "serving_spec_accepted_tokens_total",
            "draft tokens accepted by batched verification"),
        "spec_rate": _om.gauge(
            "serving_spec_accept_rate",
            "cumulative fraction of proposed draft tokens accepted"),
        "spec_tpd": _om.gauge(
            "serving_spec_tokens_per_dispatch",
            "decode tokens emitted per speculative dispatch, averaged "
            "over its decode rows (1.0 = speculation gaining nothing)"),
        "kv_bytes": _om.gauge(
            "kv_page_bytes_per_token",
            "HBM bytes one cached token costs across all layers (K+V "
            "data plus any int8 scale sidecars)"),
        "weight_bytes": _om.gauge(
            "serving_weight_bytes_per_param",
            "bytes per model weight element as served (int8 weights + "
            "f32 scale sidecars land near 1; bf16 weights at 2; f32 "
            "at 4)"),
        "stop_hits": _om.counter(
            "serving_stop_token_hits_total",
            "requests retired by a per-request stop token (the stop "
            "token itself is excluded from the output)"),
        "constraint_truncated": _om.counter(
            "serving_constraint_truncated_total",
            "constraint-hook allowed sets truncated to the engine's "
            "sample_slots width"),
        "constraint_errors": _om.counter(
            "serving_constraint_errors_total",
            "constraint hooks that raised (the step proceeds "
            "unconstrained)"),
        "mixed_hbm": _om.gauge(
            "serving_mixed_hbm_bytes",
            "static cost_analysis bytes accessed of the mixed-program "
            "executable most recently dispatched"),
    }


def _fatal_guard(origin):
    """Decorator: a crash inside an engine entry point dumps a
    flight-recorder post-mortem (when one is installed) before the
    exception reaches the caller — the serving analog of a rank dying
    under the elastic watchdog. Each exception dumps at most once."""
    import functools

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            try:
                return fn(*args, **kwargs)
            except MemoryError:
                # admission control (engine full / KV pages exhausted)
                # raises MemoryError as a ROUTINE rejection — already
                # counted by the evicted metric; it must not burn the
                # recorder's bounded dump budget. A real device OOM
                # surfaces as XlaRuntimeError and still dumps.
                raise
            except Exception as e:
                _fr.on_fatal(origin, e)
                raise
        return wrapper

    return deco


class Request:
    """One generation request (seq_id is assigned by the engine).

    Args:
        prompt_ids: non-empty 1-D sequence of prompt token ids.
        max_new_tokens: generation budget, >= 1.
        eos_token_id: optional early-stop token.
        deadline: wall-clock TTL in seconds, measured from admission.
            Past it the request is expired at the next step/scan
            boundary: its pages are released and ``error`` is set to a
            :class:`DeadlineExceeded` (partial output preserved).
        token_budget: seconds allowed per generated token — an
            alternative deadline of ``token_budget * max_new_tokens``
            from admission; the tighter of the two wins.
        priority: higher values win under pressure — the degradation
            ladder only trims/evicts strictly lower-priority requests.
        retry_budget: how many times the request may be evicted and
            re-queued before it fails permanently (status ``evicted``).
        sampling: :class:`~paddle_tpu.inference.sampling.SamplingParams`
            (None = greedy, bitwise-identical to the pre-sampling
            engine). The params' ``stop`` list merges with ``stop``.
        stop: iterable of token ids checked at the emit boundary —
            generation retires as ``completed`` right before any of
            them would be appended (the stop token is excluded).
        on_token: optional ``fn(request, token)`` fired after each
            appended token (the streaming hook). Runs on the engine's
            dispatch thread — must be fast and must not raise (raises
            are swallowed).
    """

    def __init__(self, prompt_ids, max_new_tokens=16, eos_token_id=None,
                 deadline=None, token_budget=None, priority=0,
                 retry_budget=1, sampling=None, stop=(), on_token=None):
        self.prompt_ids = np.asarray(prompt_ids, np.int64).reshape(-1)
        if self.prompt_ids.size == 0:
            raise ValueError(
                "prompt_ids is empty: a request needs at least one "
                "prompt token")
        if int(max_new_tokens) <= 0:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if deadline is not None and float(deadline) <= 0:
            raise ValueError(f"deadline must be > 0 seconds, "
                             f"got {deadline}")
        if token_budget is not None and float(token_budget) <= 0:
            raise ValueError(f"token_budget must be > 0 seconds/token, "
                             f"got {token_budget}")
        if int(retry_budget) < 0:
            raise ValueError(
                f"retry_budget must be >= 0, got {retry_budget}")
        self.max_new_tokens = int(max_new_tokens)
        self.eos_token_id = eos_token_id
        self.deadline = None if deadline is None else float(deadline)
        self.token_budget = None if token_budget is None \
            else float(token_budget)
        self.priority = int(priority)
        self.retry_budget = int(retry_budget)
        if sampling is not None and not isinstance(sampling,
                                                  SamplingParams):
            raise ValueError(
                f"sampling must be a SamplingParams, got "
                f"{type(sampling).__name__}")
        self.sampling = sampling
        self.stop_set = frozenset(int(t) for t in (stop or ())) \
            | frozenset(sampling.stop if sampling else ())
        self.on_token = on_token
        self._seed = None             # resolved at first admission
        self.output_ids: list[int] = []
        self.seq_id = None
        self.done = False
        self.status = "pending"
        self.error = None             # typed terminal failure, or None
        self.trimmed = False          # budget cut by the ladder
        self._t_admit = None          # set at admission; drives TTFT
        # the life of the request as the `serving.request` span tells it
        # (perf_counter, each stamped once and never shifted)
        self._t_submit = None         # the cluster's submit(), if any
        self._t_first_admit = None
        self._t_first_chunk = None    # first dispatch with a prefill row
        self._t_first_token = None
        self._expires_at = None       # perf_counter stamp, or None
        self._cancel_requested = False  # honored at (re-)admission
        self._cached_tokens = 0       # prefix tokens served from cache
        self._prefilled = 0           # prompt tokens written to pages
        self._tier_key = None         # host-tier handle while paused
        self._tier_tokens = 0         # context length of the parked KV


#: A mixed dispatch the device has been handed and the host has not
#: applied: what :meth:`LlamaServingEngine._finish` needs of it, and what
#: its `serving.dispatch` span will say (``said``).
_Launched = collections.namedtuple(
    "_Launched", "step rows nxt layer_stats flat_start dur cold "
                 "needs_mixed said")


class LlamaServingEngine:
    #: default scanned decode run — one dispatch of the mixed program
    #: scanned over this many ticks serves that many tokens/sequence
    DECODE_TICKS = 16

    def __init__(self, model, max_batch=16, page_size=16, num_pages=None,
                 max_pages_per_seq=None, chunk_budget=None,
                 chunk_block=None, decode_ticks=None, burst=None,
                 admit_retries=0, admit_backoff=0.005, stuck_factor=8.0,
                 stuck_min_timeout=30.0, prefix_cache=None,
                 prefix_cache_pages=None, prewarm=None, kv_dtype=None,
                 spec_k=None, spec_ngram=3, drafter_factory=None,
                 sampling=None, sample_slots=8, weight_dtype=None,
                 weight_block=None, kv_tier=None, kv_tier_bytes=None):
        if num_pages is None:
            num_pages = max_batch * 24 + 8
        self.model = model
        cfg = model.config
        self.max_batch = max_batch
        self.page_size = page_size
        # max_pages_per_seq sizes the block tables (the longest context
        # a sequence may hold). The float program's time does not
        # follow it: its kernel walks the pages a row's kv_len holds,
        # not the table's width. The int8-page program still runs a
        # grid step a table slot, so there narrow tables are faster.
        #
        # Chunked-prefill scheduler knobs:
        # - chunk_budget: token budget per mixed dispatch — the sum of
        #   query tokens (decode rows count 1, prefill chunks their
        #   length) packed into one step. Floored at 2*max_batch so a
        #   full decode batch always leaves prefill headroom.
        # - chunk_block: the ragged kernel's per-row query block — the
        #   largest single prefill chunk. Rounded up so the kernel's
        #   [QB*group] query tile stays sublane-aligned.
        # - decode_ticks: scan length of the all-decode dispatch (the
        #   host-round-trip amortizer). ``burst=`` is accepted as a
        #   legacy alias.
        group = max(1, cfg.num_attention_heads
                    // max(1, cfg.num_key_value_heads))
        align = 8 // math.gcd(group, 8)
        qb = int(chunk_block) if chunk_block else min(
            32, max(8, 2 * page_size))
        self.chunk_block = -(-qb // align) * align
        budget = int(chunk_budget) if chunk_budget \
            else max(64, 4 * max_batch)
        self.chunk_budget = max(budget, 2 * max_batch, self.chunk_block)
        if decode_ticks is None and burst is not None:
            decode_ticks = burst
        self.decode_ticks = int(decode_ticks) if decode_ticks \
            else self.DECODE_TICKS
        # mixed-program row capacity: every live sequence may hold one
        # decode row, and the remaining budget splits into chunk rows
        self.rows_cap = max_batch + -(-self.chunk_budget
                                      // self.chunk_block)
        # admission backpressure: retry this many times (exponential
        # backoff from admit_backoff seconds) before a typed rejection.
        # Default 0 (instant rejection): retries only help when another
        # thread drives step()/scans and can retire a request
        # mid-backoff — opt in for such multithreaded deployments.
        self.admit_retries = int(admit_retries)
        self.admit_backoff = float(admit_backoff)
        # stuck-dispatch watchdog: a WARM dispatch exceeding
        # stuck_factor x the observed P99 (floored at stuck_min_timeout
        # so legitimate recompiles never trip it) dumps a flight
        # recorder post-mortem. stuck_factor=0/None disables it.
        self.stuck_factor = stuck_factor
        self.stuck_min_timeout = float(stuck_min_timeout)
        # every layer states what it keeps (see `.layer_step`): a list
        # of (heads, width), one entry a pool, where all layers of a
        # model keep the same; or, a layer of a model whose layers
        # differ, a `PagedKV` (with or without a window), a
        # `PagedLatent`, a `SlotState`, `SharedPages` of another layer,
        # or None
        specs = [layer.serving_cache() for layer in model.model.layers]
        mixed_caches = any(not isinstance(sp, list) for sp in specs)
        # a row's scan starts from its slot's state and writes it back:
        # two rows of one sequence in one dispatch would both start
        # from the same stored state, so such a model prefills one
        # chunk a sequence a dispatch
        self._single_chunk = any(isinstance(sp, SlotState) for sp in specs)
        #: and at most this many chunks a dispatch (None: no such bound),
        #: which lets a scan advance the decode rows one token and only
        #: the chunk rows further
        self.chunk_rows = self.rows_cap - max_batch \
            if self._single_chunk else None
        #: ``{window: layers that keep it}`` (their rings' counters)
        self._windows = collections.Counter(
            sp.window for sp in specs
            if isinstance(sp, PagedKV) and sp.window)
        #: layers keep a state or a ring a sequence SLOT: every live
        #: sequence holds one of ``max_batch`` slots (slot ``max_batch``
        #: is where rows that are none read and write)
        self._slotted = self._single_chunk or bool(self._windows)
        # page num_pages-1 is the trash page for inactive batch slots
        self.alloc = PageAllocator(num_pages - 1, page_size,
                                   max_pages_per_seq,
                                   slots=max_batch if self._slotted else 0)
        self.width = self.alloc.max_pages_per_seq
        self.trash_page = num_pages - 1
        # shared-prefix KV cache: page-aligned prompt prefixes are
        # prefilled once and later admissions reference the cached
        # pages (refcounted in the allocator; see prefix_cache.py).
        # On by default where every layer's cache is pages of the whole
        # context; asked for where a layer's is not, it is refused below
        unsupported = {what for layer in model.model.layers
                       for what in getattr(layer, "serving_unsupported",
                                           ())}
        asked_prefix = bool(prefix_cache)
        if prefix_cache is None:
            prefix_cache = "prefix_cache" not in unsupported
        from .prefix_cache import PrefixCache
        self.prefix = PrefixCache(self.alloc, page_size,
                                  max_pages=prefix_cache_pages) \
            if prefix_cache else None
        # weight-only int8 serving (ROADMAP item 3, weight side): every
        # decode-side projection stores int8 + per-block f32 scale
        # sidecars and dequantizes in VMEM on use — about half the HBM
        # bytes a decode step streams. PADDLE_TPU_WEIGHT_DTYPE=int8 is
        # the fleet knob; the engine arg wins when given; "bf16" (the
        # default) leaves the model untouched — the old path byte for
        # byte. Quantization is in place: a pre-quantized model (e.g.
        # load_quantized / the QAT bridge) is honored as-is.
        if weight_dtype is None:
            weight_dtype = os.environ.get(
                "PADDLE_TPU_WEIGHT_DTYPE", "") or None
        if weight_dtype == "bf16":
            weight_dtype = None
        if weight_dtype not in (None, "int8"):
            raise ValueError(
                f"weight_dtype must be 'bf16' (model dtype) or 'int8', "
                f"got {weight_dtype!r}")
        from ..quant.format import (is_quantized, model_weight_block,
                                    quantize_model, serving_weight_bytes)
        if weight_dtype == "int8" and "weight_dtype=int8" in unsupported:
            # refused before the model is quantized in place
            raise UnsupportedServingFeature(
                f"{type(model.model.layers[0]).__name__} cannot serve "
                f"weight_dtype=int8 yet")
        if weight_dtype == "int8" and not is_quantized(model):
            quantize_model(model, block=weight_block)
        self.weight_quant = bool(weight_dtype == "int8"
                                 or is_quantized(model))
        self.weight_block = model_weight_block(model) or 0
        wbytes, _, welems = serving_weight_bytes(model)
        self.weight_bytes_per_param = wbytes / max(welems, 1)
        dt = model.parameters()[0].dtype
        # int8 KV pages (ROADMAP item 3b): quantize on write, dequantize
        # inside the ragged kernel's kv loop. Halves (bf16) / quarters
        # (f32) the HBM bytes a cached token costs, so the same pool
        # admits ~2x the batch/context before the degradation ladder
        # ever trims or evicts. PADDLE_TPU_KV_DTYPE=int8 is the fleet
        # knob; the engine arg wins when given.
        if kv_dtype is None:
            kv_dtype = os.environ.get("PADDLE_TPU_KV_DTYPE", "") or None
        if kv_dtype not in (None, "int8"):
            raise ValueError(
                f"kv_dtype must be None (model dtype) or 'int8', "
                f"got {kv_dtype!r}")
        self.kv_quant = kv_dtype == "int8"
        pool_dt = jnp.int8 if self.kv_quant else jnp.dtype(str(dt))
        # pools. Where all layers keep the same list of (heads, width):
        # a pool with heads is head-major [P, Hk, page, D] (the K/V
        # kernels' tiling layout), one without [P, page, W] (a latent
        # row all heads share); the first pool of each layer is held in
        # ``k_pools``, the second, where there is one, in ``v_pools``.
        # Where layers differ, ``k_pools`` holds every pool of every
        # layer, in layer order. ``_layer_pages[i]`` says which entries
        # of ``k_pools + v_pools + k_scales + v_scales`` layer ``i``'s
        # step is handed (and hands back)
        if not mixed_caches and (any(sp != specs[0] for sp in specs)
                                 or len(specs[0]) > 2):
            raise UnsupportedServingFeature(
                "layers that state different lists of pools (or more "
                "than two pools a layer): state a PagedKV, SlotState or "
                "SharedPages a layer instead")

        def pool_shape(heads, width, last=None, pages=num_pages):
            last = width if last is None else last
            return (pages, page_size, last) if heads is None \
                else (pages, heads, page_size, last)

        n_layers = len(specs)
        if mixed_caches:
            self.k_pools, self._layer_pages = [], []
            for sp in specs:
                own = []
                if isinstance(sp, PagedKV):
                    pages = num_pages if not sp.window else \
                        (max_batch + 1) * self.ring_pages(sp.window)
                    own = [jnp.zeros(pool_shape(sp.heads, sp.width,
                                                pages=pages), pool_dt)
                           for _ in range(2)]
                elif isinstance(sp, PagedLatent):
                    own = [jnp.zeros(pool_shape(None, sp.width), pool_dt)]
                elif isinstance(sp, SlotState):
                    own = [jnp.zeros((max_batch + 1,) + shape, d)
                           for shape, d in sp.shapes]
                elif isinstance(sp, SharedPages):
                    if not isinstance(specs[sp.layer], PagedKV) \
                            or specs[sp.layer].window:
                        raise UnsupportedServingFeature(
                            "a layer shares the pages of a layer that "
                            "keeps the whole context")
                elif sp is not None:
                    raise UnsupportedServingFeature(
                        "a model whose layers keep different caches "
                        "states each as a PagedKV, a PagedLatent, a "
                        "SlotState, SharedPages or None")
                at = len(self.k_pools)
                self._layer_pages.append(list(range(at, at + len(own))))
                self.k_pools += [Tensor(a) for a in own]
            for li, sp in enumerate(specs):
                if isinstance(sp, SharedPages):
                    self._layer_pages[li] = self._layer_pages[sp.layer]
            self.v_pools, scales = [], [[], []]
        else:
            pools = [[Tensor(jnp.zeros(pool_shape(*sp), pool_dt))
                      for _ in specs] for sp in specs[0]]
            self.k_pools = pools[0]
            self.v_pools = pools[1] if len(pools) > 1 else []
            # per-head per-slot dequant scales ride sidecar arrays
            # indexed by the SAME page ids, so prefix-shared pages carry
            # their scales for free and a COW page copy copies both
            scales = [[Tensor(jnp.zeros(pool_shape(*sp, last=1),
                                        jnp.float32))
                       for _ in specs] if self.kv_quant else []
                      for sp in specs[0]]
            groups = len(pools) * (2 if self.kv_quant else 1)
            self._layer_pages = [[g * n_layers + li for g in range(groups)]
                                 for li in range(n_layers)]
        self.k_scales = scales[0]
        self.v_scales = scales[1] if len(scales) > 1 else []
        # the most query tokens of a row that the float ragged program
        # computes on its small tile (`small_tile`), at the query heads
        # a kv head of the K/V pools `[P, Hk, page, D]`; None where no
        # layer runs that program (latent rows, int8 pages)
        hk = next((sp.heads for sp in specs if isinstance(sp, PagedKV)),
                  None) if mixed_caches else specs[0][0][0]
        self._tile_tokens = None
        if hk and not self.kv_quant:
            from ..ops.ragged_paged_attention import small_tile
            g = cfg.num_attention_heads // hk
            self._tile_tokens = small_tile(g) // g
        # self-speculative decoding (ROADMAP item 3a): an n-gram /
        # prompt-lookup drafter proposes up to spec_k tokens per live
        # decoder; the scheduler packs each speculating row into the
        # mixed step as a (q_len = k+1) chunk and batched verification
        # accepts the longest exactly-matching prefix — greedy outputs
        # stay token-exact, rejected draft pages roll back via the
        # allocator. spec_k=0 (default) disables.
        if spec_k is None:
            spec_k = int(os.environ.get("PADDLE_TPU_SPEC_K", "0") or 0)
        self.spec_k = max(0, min(int(spec_k), self.chunk_block - 1))
        # per-request sampling (ROADMAP item 4): the mixed program
        # grows a vectorized per-row sample step next to the argmax —
        # every sampler knob is runtime data ([R]-shaped arrays), so
        # compiled shapes never fork per request config and greedy
        # rows stay bitwise-exact. A dispatch in which no row samples
        # takes the argmax and nothing else (the vocab sort sits under
        # a branch the device takes from ``temps``), so sampling=False,
        # which restores the exact pre-sampling program, buys a
        # greedy-only deployment no speed; PADDLE_TPU_SAMPLING=0 is the
        # fleet knob.
        if sampling is None:
            sampling = os.environ.get(
                "PADDLE_TPU_SAMPLING", "1").lower() \
                not in ("0", "false", "off")
        self.sample_enabled = bool(sampling)
        # static width of the per-row logit-bias / constraint slots —
        # part of the compiled signature, hence an ENGINE knob, never a
        # request one
        self.sample_slots = max(1, int(sample_slots))
        # auto-seed LCG for sampled requests that didn't pin a seed
        # (recorded on the request so the draw stays reproducible)
        self._auto_seed = int.from_bytes(os.urandom(4), "little") \
            % (2 ** 31)
        self._drafter_factory = drafter_factory or \
            (lambda: NGramDrafter(n=spec_ngram))
        self._spec_state: dict[int, object] = {}   # seq_id -> drafter
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._spec_idle = 0     # consecutive no-proposal probes
        self._live: dict[int, Request] = {}
        self._m = _serving_metrics()
        # bytes a cached token costs over all layers, as allocated (a
        # latent row's pad lanes included)
        # (of a model whose layers differ: the pools that hold the
        # whole context; a ring and a state do not grow with it)
        def per_token(sp):
            if isinstance(sp, list):
                return sp
            if isinstance(sp, PagedLatent):
                return [(None, sp.width)]
            whole = isinstance(sp, PagedKV) and not sp.window
            return [(sp.heads, sp.width)] * 2 if whole else []

        #: bytes of the states one sequence slot holds, all layers
        self._slot_bytes = sum(
            int(np.prod(shape)) * d.itemsize for sp in specs
            if isinstance(sp, SlotState) for shape, d in sp.shapes)

        tok_bytes = sum(
            (heads or 1) * (width * jnp.dtype(pool_dt).itemsize
                            + (4 if self.kv_quant else 0))
            for sp in specs for heads, width in per_token(sp))
        self.kv_bytes_per_token = tok_bytes
        self._m["kv_bytes"].set(tok_bytes)
        self._m["weight_bytes"].set(self.weight_bytes_per_param)
        # host-DRAM KV page tier (ROADMAP item 5a): under pool pressure
        # the ladder PAUSES victims — pages D2H-copied into a bounded
        # host pool, the request parked ``paused``, resumed by an H2D
        # restore when capacity returns — instead of destroying their
        # work via evict. Opt-in (kv_tier=True / PADDLE_TPU_KV_TIER=1)
        # because pause changes the ladder's observable semantics;
        # kv_tier_bytes bounds the host pool (PADDLE_TPU_KV_TIER_BYTES,
        # default 256 MiB). Cold prefix-cache pages demote into the
        # same pool before being dropped and promote back on a match.
        if kv_tier is None:
            kv_tier = os.environ.get(
                "PADDLE_TPU_KV_TIER", "0").lower() in ("1", "true", "on")
        if kv_tier_bytes is None:
            kv_tier_bytes = int(os.environ.get(
                "PADDLE_TPU_KV_TIER_BYTES", str(256 << 20)))
        self.tier = KvPageTier(max_bytes=kv_tier_bytes) \
            if kv_tier else None
        if self.tier is not None and self.prefix is not None:
            self.prefix.demote = self._demote_prefix_page
        # a layer kind names the engine features its pages do not reach
        # yet: asked for, each is refused here by name, never served by
        # a silent fallback
        asked = {"prefix_cache": asked_prefix,
                 "kv_dtype=int8": self.kv_quant,
                 "kv_tier": self.tier is not None,
                 "spec_k": bool(self.spec_k),
                 "weight_dtype=int8": self.weight_quant,
                 # no option: a layer names it where its one serving
                 # program cannot rotate heads of that width
                 f"head_dim={cfg.head_dim}": True}
        for layer in model.model.layers:
            for what in getattr(layer, "serving_unsupported", ()):
                if asked.get(what):
                    raise UnsupportedServingFeature(
                        f"{type(layer).__name__} cannot serve {what} "
                        f"yet")
        self._next_id = 0
        self._layer_stats = None    # the last dispatch's layer counters
        # ONE traced mixed-program function covers every dispatch; its
        # per-signature cache holds the chunk_budget-token shape and the
        # [max_batch]-token decode-only shape. Scanned multi-tick
        # variants (lax.scan over the same function) key by tick count.
        self._mixed_static = None
        self._layouts: dict[int, DispatchLayout] = {}   # t_cap -> layout
        self._scan_static: dict[int, object] = {}   # ticks -> program
        self._warmed_keys: set = set()  # ("mixed", T) / ("scan", k)
        self._mixed_bytes: dict[int, float] = {}  # t_cap -> hbm bytes
        self._warm_dispatches = 0       # dummy compile-warm dispatches
        # lifecycle state: one re-entrant lock guards _live, the
        # requeue, deferred releases and entry-depth accounting so
        # cancel()/drain handlers may fire from any thread
        self._lock = threading.RLock()
        # dispatch mutex: step()/_decode_scan() bodies are
        # serialized — two driver threads (or a drain racing an
        # external driver loop) must never interleave allocator extends
        # and pool reassignments for the same sequences. Re-entrant so
        # a step's own requeue pump may prefill.
        self._dispatch_lock = threading.RLock()
        self._requeue: collections.deque[Request] = collections.deque()
        self._deferred_release: list[int] = []
        self._in_dispatch = False
        self._entry_depth = 0
        self._entry_threads: dict[object, int] = {}   # thread -> depth
        self._flushing = False
        self._draining = False
        self._drain_active = False
        self._pending_drain = None    # (grace, exit_code, on_drained)
        self._dispatch_count = 0
        # one dispatch ahead: the mixed dispatch the device runs (or has
        # queued) whose tokens the host has not applied yet, and the
        # token array the last mixed program returned, which the next
        # one reads on the device (`_mixed_packed`). ONE shape whichever
        # of the two program shapes wrote it: a row each (a packed token
        # each, of a speculative engine), padded to the longer shape's
        self._inflight = None
        self._carry_len = self.chunk_budget if self.spec_k \
            else self.rows_cap
        self._carry = Tensor(jnp.zeros((self._carry_len,), jnp.int32))
        self._dispatch_times: collections.deque[float] = \
            collections.deque(maxlen=256)
        self._token_times: collections.deque[float] = \
            collections.deque(maxlen=512)
        self._wd = None
        self._closed = False
        # -- warm restart (ROADMAP item 5) -----------------------------
        # persistent XLA compile cache on by default (kill switch:
        # PADDLE_TPU_COMPILE_CACHE=0): a restarted replica re-compiling
        # the same serving programs gets executables from disk in
        # seconds instead of ~19 s of backend compile. The shape
        # registry records which programs THIS engine geometry actually
        # dispatches (mixed token shapes, scan tick counts) so the
        # next process can pre-warm them before traffic arrives.
        self._cache_dir = _cw.enable_persistent_cache()
        self._recorded_shapes: set = set()
        self._shape_key = self._compute_shape_key()
        self.prewarmed = None         # prewarm() summary, or None
        if prewarm is None:
            prewarm = os.environ.get(
                "PADDLE_TPU_SERVING_PREWARM", "0").lower() \
                in ("1", "true", "on", "auto")
        if prewarm:
            self.prewarm()

    def ring_pages(self, window):
        """Pages of the ring a sequence slot holds in the pools of a
        layer that reads the last ``window`` keys: the window, the most
        tokens one dispatch writes of a sequence, and a page (a
        dispatch's first key and last query both lie mid-page)."""
        chunk = self.chunk_block if self._single_chunk \
            else self.chunk_budget
        return -(-(int(window) + chunk) // self.page_size) + 1

    def __state_tensors__(self):
        """State-discovery override for ``to_static``: the KV pools are
        explicit inputs/outputs of every compiled program (donated for
        in-place page writes) and must NOT also be captured as closure state —
        that would donate the same buffers twice. Model params enter via
        ``state=[self.model]``."""
        return []

    # ------------------------------------------------------------------
    # lifecycle plumbing
    # ------------------------------------------------------------------
    @contextlib.contextmanager
    def _entry(self):
        """Depth accounting around public entry points. Two jobs: a
        SIGTERM that lands while an entry is in flight defers its drain
        to the moment the outermost entry returns (state is
        boundary-consistent there), mirroring the checkpoint callback's
        deferred emergency save; and every thread inside an entry is
        recorded so page releases requested while a DIFFERENT thread is
        mid-entry (cancel, a concurrent _admit's eviction) are deferred
        past the whole entry — the in-flight step may still be reading
        the allocator's tables for those sequences."""
        me = threading.current_thread()
        with self._lock:
            self._entry_depth += 1
            self._entry_threads[me] = self._entry_threads.get(me, 0) + 1
        try:
            yield
        finally:
            with self._lock:
                self._entry_depth -= 1
                c = self._entry_threads.get(me, 1) - 1
                if c:
                    self._entry_threads[me] = c
                else:
                    self._entry_threads.pop(me, None)
                at_boundary = self._entry_depth == 0
                if at_boundary:
                    # the flush below releases pages outside the entry
                    # count; this flag keeps the SIGTERM handler
                    # deferring its drain past it (drain -> step ->
                    # alloc.extend would deadlock on the allocator's
                    # non-reentrant lock mid-release)
                    self._flushing = True
            if at_boundary:
                try:
                    self._flush_deferred()
                finally:
                    with self._lock:
                        self._flushing = False
                        pending = None
                        # leave _pending_drain for drain()'s epilogue
                        # when a manual drain is mid-flight — popping
                        # it here would run a second (no-op) drain and
                        # exit mid-grace-window
                        if self._entry_depth == 0 \
                                and not self._drain_active:
                            pending = self._pending_drain
                            if pending is not None:
                                self._pending_drain = None
                    if pending is not None:
                        grace, exit_code, on_drained = pending
                        self._run_drain_and_exit(grace, exit_code,
                                                 on_drained)

    def _release_pages(self, seq_id):
        """Release a sequence's pages — deferred while a dispatch is in
        flight (the program may still be writing K/V into them) and
        while ANOTHER thread is inside an engine entry (its setup/emit
        code may still be reading the allocator for this sequence), so
        a concurrent admission can never be handed dirty pages and the
        driving thread never sees tables vanish mid-step."""
        if seq_id is None:
            return
        me = threading.current_thread()
        with self._lock:
            others_in_entry = any(t is not me for t in self._entry_threads)
            if self._in_dispatch or others_in_entry:
                self._deferred_release.append(seq_id)
            else:
                self.alloc.release(seq_id)

    def _flush_deferred(self):
        with self._lock:
            if self._in_dispatch:
                return      # the dispatch's own epilogue will flush
            pending, self._deferred_release = self._deferred_release, []
        for sid in pending:
            # idempotent: racing a natural completion is a no-op
            self.alloc.release(sid)

    def _retire(self, req, status, error=None):
        """Terminal transition: remove from the live set, free pages,
        stamp status/error. Idempotent under the engine lock."""
        with self._lock:
            if req.done:
                return False
            req.done = True
            req.status = status
            req.error = error
            sid = req.seq_id
            self._spec_state.pop(sid, None)
            if sid in self._live:
                del self._live[sid]
                self._release_pages(sid)
        now = time.perf_counter()
        rctx = getattr(req, "_trace", None)
        _record_span(
            "serving.request",
            now if req._t_submit is None else req._t_submit, now,
            seq_id=sid, t_submit=req._t_submit,
            t_admit=req._t_first_admit, t_first_chunk=req._t_first_chunk,
            t_first_token=req._t_first_token, t_done=now,
            prompt_len=len(req.prompt_ids),
            output_len=len(req.output_ids),
            cached_tokens=int(req._cached_tokens), status=status,
            **({} if rctx is None else {"trace_id": rctx.trace_id}))
        return True

    def _expire(self, req, reason="deadline", now=None):
        now = time.perf_counter() if now is None else now
        elapsed = None if req._t_admit is None else now - req._t_admit
        err = DeadlineExceeded(
            f"request {req.seq_id} exceeded its {reason} after "
            f"{0.0 if elapsed is None else elapsed:.3f}s "
            f"({len(req.output_ids)}/{req.max_new_tokens} tokens "
            f"emitted)", seq_id=req.seq_id, elapsed=elapsed,
            tokens_emitted=len(req.output_ids), reason=reason)
        if self._retire(req, "deadline_exceeded", err):
            self._m["deadline_exceeded"].inc()

    def _expire_deadlines(self):
        """Expire every live request past its deadline — called at
        step/scan boundaries (the granularity that exists once a
        dispatch is on device)."""
        now = time.perf_counter()
        with self._lock:
            expired = [r for r in self._live.values()
                       if not r.done and r._expires_at is not None
                       and now >= r._expires_at]
            # paused requests park on the requeue with their deadline
            # clock still TICKING (their work is preserved, their SLA
            # is not suspended); an expired one frees its host-tier
            # copy too, not just its — already released — pages
            parked = [r for r in self._requeue
                      if not r.done and r._tier_key is not None
                      and r._expires_at is not None
                      and now >= r._expires_at]
            for r in parked:
                self._requeue.remove(r)
        for r in expired:
            self._expire(r, now=now)
        for r in parked:
            self._expire(r, now=now)
            self._tier_discard(r)

    def cancel(self, req):
        """Cancel a live request (by :class:`Request` or seq_id).

        Thread-safe and idempotent — wire it directly to a client-abandon
        callback. The request retires with status ``"cancelled"`` and
        its partial output intact; its pages return to the allocator
        (deferred past any in-flight dispatch, so compiled batch shapes
        are never disturbed mid-flight). Reaches both live requests and
        requests parked on the eviction requeue (an abandoned request
        must not be pumped back in and decoded for nobody). Returns
        True if this call did the cancellation, False if the request
        was already terminal or unknown."""
        with self._entry():
            with self._lock:
                if isinstance(req, Request):
                    r = req
                    if r.done:
                        return False
                    # sticky: even if the request is momentarily
                    # unreachable (popped by the requeue pump, mid
                    # re-admission), the admission path honors this
                    r._cancel_requested = True
                    if r in self._requeue:
                        self._requeue.remove(r)
                        r.done = True
                        r.status = "cancelled"
                        self._m["cancelled"].inc()
                        # a paused request's host copy dies with it
                        self._tier_discard(r)
                        return True
                    if r.seq_id is None \
                            or self._live.get(r.seq_id) is not r:
                        if r.status == "pending":
                            # never admitted: terminal right away, not
                            # a dangling flag the caller must poll
                            r.done = True
                            r.status = "cancelled"
                            self._m["cancelled"].inc()
                        # else: popped by the requeue pump mid
                        # re-admission — the flag is honored there
                        return True
                else:
                    r = self._live.get(req)
                    if r is None or r.done:
                        return False
                if self._retire(r, "cancelled"):
                    self._m["cancelled"].inc()
                    return True
                return False

    # ------------------------------------------------------------------
    # the mixed program: prefill chunks + decode rows, one dispatch
    # ------------------------------------------------------------------
    def _mixed_forward(self, tokens, pos, flat_idx, last_idx, tables,
                       kv_lens, q_starts, q_lens, w_starts, w_flats,
                       w_ends, temps, top_ps, top_ks, seeds, slot_ids,
                       slot_vals, cmodes, k_pools, v_pools, k_scales,
                       v_scales, slots=None, prev_idx=None, prev=None):
        """ONE token-packed model step: embed [1, T] real tokens (a mix
        of prefill-chunk tokens, speculative verify tokens and decode
        tokens, back to back with no inter-row padding), ask every
        layer for its own step over its own pages
        (``layer.serving_step``: the layer writes what it keeps of the
        step's tokens and attends through its pages over the per-row
        ``(q_start, q_len, kv_len)`` metadata; see
        :mod:`.layer_step`), and read the greedy next token:
        a speculative engine (``spec_k > 0``) takes the argmax at
        EVERY packed position — position ``t`` of the [T] return is
        the argmax continuation after token ``t``, what verification
        compares drafts against — while a plain engine gathers each
        row's last valid position first (an [R]-sized lm-head, not a
        [T]-sized one; mixed dispatches with a big ``chunk_budget``
        would otherwise pay T/R times the vocab projection for argmax
        values nobody reads). Pure in its inputs so ``to_static``
        compiles it once per token-count signature; the decode-only
        shape (T == max_batch, QB == 1) and the chunk-budget shape
        share this function.

        With ``sample_enabled`` the argmax generalizes to the
        per-row sample step (:func:`sampled_next_tokens`): temperature
        / top-p / top-k / seed / bias-constraint slots ride as
        ``[R]``-shaped runtime arrays, greedy rows (temperature 0)
        still take the bitwise argmax of the same logits (a dispatch
        with no sampling row computes nothing else), and the
        threefry key folds the request seed with the token's absolute
        position — so the draw at a position never depends on how it
        was dispatched (step, scan tick, or speculative verify row).

        ``w_starts``/``w_flats``/``w_ends`` [R] carry the write-span
        metadata of the layers' attention kernels, which write pages
        themselves (per row: the first position of its sequence this
        dispatch writes, that position's packed index, the sequence's
        final kv_len). The query block QB is the one of the dispatch
        layout that has T tokens.

        ``prev_idx [1, T]`` (None: every token is in ``tokens``) names,
        for a token the host could not write because the dispatch that
        produces it was still running when this one was built, its
        index in ``prev``, that dispatch's returned tokens; -1 for a
        token that is in ``tokens``.

        tokens/pos [1, T]; flat_idx [T];
        last_idx/kv_lens/q_starts/q_lens/w_starts/w_flats/w_ends/
        temps/top_ps/top_ks/seeds/cmodes [R]; slot_ids/slot_vals
        [R, B]; tables [R, W]; ``k_pools`` hold each layer's first
        pool, ``v_pools`` its second (an empty list where a layer
        keeps one), k/v_scales are empty lists for float pools.
        Returns (next token ids — 1-D [T] when speculative, 1-D [R]
        otherwise — new k_pools, new v_pools, new k_scales,
        new v_scales, and the layers' counters: a list, empty or of
        one int32 array ``[layers that count, 2, 1]``)."""
        from ..tensor import search

        m = self.model.model
        t = tokens.shape[1]
        r_rows, qb = tables.shape[0], self._dispatch_layout(t).shape[2]
        if prev_idx is not None:
            # a decode row launched one dispatch ahead: its input token
            # is where the dispatch before left it, on the device
            tokens = run_op(
                "serving_token_feed",
                lambda tok, at, pv: jnp.where(
                    at >= 0, pv[jnp.maximum(at, 0)].astype(tok.dtype),
                    tok),
                (tokens, prev_idx, prev), differentiable=False)
        x = m.embed_tokens(tokens)                       # [1, T, H]
        # the step's metadata, and the tables its layers share (rotary
        # sin/cos are made once a dispatch, not once a layer)
        step = ServingStep(self, qb, pos, flat_idx, tables, kv_lens,
                           q_starts, q_lens, w_starts, w_flats, w_ends,
                           slots=slots)
        # every layer runs its own step over the pools it is handed
        # (``_layer_pages``: of layers that all keep the same, the pools
        # it stated, then their scale sidecars; a layer that reads
        # another's pools is handed those, as that layer left them) and
        # may hand something on to later layers (``step.carry``)
        groups = (k_pools, v_pools, k_scales, v_scales)
        flat = [p for g in groups for p in g]
        stats = []
        for layer, idx in zip(m.layers, self._layer_pages):
            x, pages, st, *more = layer.serving_step(
                x, step, [flat[i] for i in idx])
            for i, page in zip(idx, pages):
                flat[i] = page
            if st is not None:
                stats.append(st)
            for handed in more:
                step.carry.update(handed)
        it = iter(flat)
        new_k, new_v, new_ks, new_vs = ([next(it) for _ in g]
                                        for g in groups)
        if len(stats) > 1:
            # one array, so the host reads the layers' counters in one
            # copy beside the tokens
            stats = [run_op("serving_layer_stats",
                            lambda *a: jnp.concatenate(a), tuple(stats),
                            differentiable=False)]
        x = m.norm(x)
        # returned 1-D ([T] or [R]): XLA aliases an output into a
        # donated input of the same aval, and a host-built input is
        # zero-copy-backed by the caller's numpy array, so such an
        # alias is a use-after-free. The mixed program's one host-built
        # input is the packed [size] buffer, longer than T; the scan's
        # token input is 2-D [B, 1]. These shapes always get a fresh
        # buffer. (The token array one mixed program hands the next,
        # `_mixed_packed`'s ``prev``, HAS the returned tokens' aval: it
        # is kept out of the donation, `keep_args`.)
        if self.spec_k:
            logits = self.model._logits(x)               # [1, T, V]
            if self.sample_enabled:
                # sample at EVERY packed position: row params gather
                # token-wise through flat_idx (token t belongs to row
                # flat_idx[t] // qb), the fold position is the sampled
                # token's absolute position (input pos + 1)
                def fn(lg, tp, pp, kp_, sd, ps, sid, sva, cm, fi):
                    vv = lg.shape[-1]
                    row = jnp.clip(fi.astype(jnp.int32) // qb, 0,
                                   tp.shape[0] - 1)
                    return sampled_next_tokens(
                        lg.reshape(t, vv), tp[row], pp[row], kp_[row],
                        sd[row],
                        ps.reshape(t).astype(jnp.int32) + 1,
                        sid[row], sva[row], cm[row])

                nxt = run_op("serving_sample", fn,
                             (logits, temps, top_ps, top_ks, seeds,
                              pos, slot_ids, slot_vals, cmodes,
                              flat_idx), differentiable=False) \
                    .reshape([t])
            else:
                nxt = search.argmax(logits, axis=-1).astype("int64") \
                    .reshape([t])
        else:
            h_last = _token_gather(x.reshape([t, x.shape[-1]]),
                                   last_idx)
            logits = self.model._logits(
                h_last.reshape([r_rows, 1, h_last.shape[-1]]))
            if self.sample_enabled:
                def fn(lg, tp, pp, kp_, sd, ps, sid, sva, cm, li):
                    vv = lg.shape[-1]
                    p = ps.reshape(-1)[li.astype(jnp.int32)] \
                        .astype(jnp.int32) + 1
                    return sampled_next_tokens(
                        lg.reshape(r_rows, vv), tp, pp, kp_, sd, p,
                        sid, sva, cm)

                nxt = run_op("serving_sample", fn,
                             (logits, temps, top_ps, top_ks, seeds,
                              pos, slot_ids, slot_vals, cmodes,
                              last_idx), differentiable=False) \
                    .reshape([r_rows])
            else:
                nxt = search.argmax(logits, axis=-1).astype("int64") \
                    .reshape([r_rows])
        return nxt, new_k, new_v, new_ks, new_vs, stats

    def _dispatch_layout(self, t_cap):
        """The layout of the host buffer a dispatch of ``t_cap`` packed
        tokens hands its program, or None for a token count that is
        neither of this engine's two program shapes: the chunk-budget
        shape ``(chunk_budget, rows_cap, chunk_block)`` and the
        decode-only shape ``(max_batch, max_batch, 1)``."""
        lay = self._layouts.get(t_cap)
        if lay is None:
            if t_cap == self.chunk_budget:
                r_cap, qb = self.rows_cap, self.chunk_block
            elif t_cap == self.max_batch:
                r_cap, qb = self.max_batch, 1
            else:
                return None
            lay = self._layouts[t_cap] = DispatchLayout(
                t_cap, r_cap, qb, self.width, self.sample_slots,
                self.trash_page,
                trash_slot=self.max_batch if self._slotted else None)
        return lay

    def _mixed_packed(self, packed, prev, k_pools, v_pools, k_scales,
                      v_scales):
        """The compiled entry of the mixed program: ``packed`` is one
        dispatch's metadata as :class:`DispatchLayout` lays it out
        (int32 ``[size]``); it is taken apart at static offsets into the
        18 tensors :meth:`_mixed_forward` takes and ``prev_idx``. The
        buffer's length names the program shape: the chunk-budget layout
        is always the longer (``chunk_budget >= 2 * max_batch``,
        ``rows_cap > max_batch``). ``prev`` int32 ``[_carry_len]`` is
        the token array the mixed program before this one returned (it
        is not donated: the host reads it after this call is enqueued);
        the tokens returned are padded to that one length, so that both
        program shapes read and write the same array and an engine
        compiles two programs, not one a pair of shapes."""
        layout = self._dispatch_layout(self.chunk_budget)
        if packed.shape[0] != layout.size:
            layout = self._dispatch_layout(self.max_batch)
        fields = [Tensor(a) for a in layout.unpack(packed._data)]
        # a model that keeps a state or a ring a slot has a 20th field
        slots = fields.pop() if self._slotted else None
        prev_idx = fields.pop()
        nxt, *rest = self._mixed_forward(
            *fields, k_pools, v_pools, k_scales, v_scales, slots=slots,
            prev_idx=prev_idx, prev=prev)
        pad = self._carry_len - nxt.shape[0]
        nxt = run_op("serving_token_carry",
                     lambda a: jnp.pad(a.astype(jnp.int32), (0, pad)),
                     (nxt,), differentiable=False)
        return (nxt, *rest)

    def _run_mixed(self, buf):
        """Hand the mixed program one host buffer (its only transfer)
        and the tokens the program before it returned, and adopt the
        donated pools it returns. Returns ``(next tokens, the layers'
        counters)``, both still on the device."""
        sf = self._ensure_mixed_compiled()
        nxt, new_k, new_v, new_ks, new_vs, stats = sf(
            Tensor(jnp.asarray(buf)), self._carry, self.k_pools,
            self.v_pools, self.k_scales, self.v_scales)
        self.k_pools, self.v_pools = list(new_k), list(new_v)
        if self.kv_quant:
            self.k_scales, self.v_scales = list(new_ks), list(new_vs)
        return nxt, stats

    def _ensure_mixed_compiled(self):
        if self._mixed_static is None:
            from ..jit import StaticFunction

            # no lazy state (params exist, no optimizer): skip the eager
            # warmup and compile directly; donate pools for in-place
            # page writes. donate=False: serving state is read-only
            # pass-through (weights are never updated), so donating it
            # saves nothing — and with many same-aval state slots (e.g.
            # int8 weights + per-block scale sidecars) XLA's aval-based
            # alias assignment scrambles the pass-through outputs across
            # the donated buffers, corrupting the model in place.
            self._mixed_static = StaticFunction(
                self._mixed_packed, state=[self.model], warmup="once",
                donate=False, donate_inputs=True, keep_args=(1,),
                name="serving.mixed_step")
            self._mixed_static._warmed_any = True
        return self._mixed_static

    def _note_mixed_bytes(self, t_cap):
        """Refresh the ``serving_mixed_hbm_bytes`` gauge with the
        static cost_analysis bytes of the mixed program just
        dispatched. The analysis runs ONCE per token shape (cached);
        every later dispatch is a dict lookup + gauge set. Under
        PADDLE_TPU_METRICS=0 the AOT executables don't exist and this
        is a no-op — the zero-cost mandate holds."""
        if not _om.enabled():
            return
        nbytes = self._mixed_bytes.get(t_cap)
        if nbytes is None:
            sf = self._mixed_static
            if sf is None:
                return
            compiled = None
            # match the executable by its signature: the FIRST leaf of
            # a mixed-program signature is the packed host buffer, whose
            # length identifies the dispatch's t_cap exactly. A
            # signature whose AOT slot is None (aot unsupported /
            # AOT_MISMATCH demotion) is skipped — misattributing some
            # OTHER shape's bytes here would poison the exact
            # fused-vs-unfused comparison the gauge exists for.
            for sig, c in sf._aot.items():
                if c is None:
                    continue
                shapes = sig[0]
                if shapes and shapes[0][0] == (
                        self._dispatch_layout(t_cap).size,):
                    compiled = c
                    break
            if compiled is None:
                return
            _, nbytes, _ = _cw.CompileWatch._analyze(compiled)
            if nbytes is None:
                return
            self._mixed_bytes[t_cap] = nbytes
        self._m["mixed_hbm"].set(nbytes)

    def _prefix_insert(self, reqs, sids):
        """Pin freshly written full prompt pages in the prefix cache
        (one allocator reference each) so they outlive the requests."""
        with self._lock:
            for r, sid in zip(reqs, sids):
                if r.done or r.seq_id != sid:
                    continue
                table = self.alloc._tables.get(sid)
                if table:
                    self.prefix.insert(r.prompt_ids, table)
            self._m["prefix_pages"].set(self.prefix.pages)

    def _copy_page(self, old, new):
        """Device-copy one page's K/V across every layer — the payload
        of a :meth:`PageAllocator.ensure_writable` copy-on-write. Int8
        pools copy the scale sidecars WITH the page: a copied page that
        kept stale scales would dequantize to garbage for its new
        owner."""
        for pools in (self.k_pools, self.v_pools, self.k_scales,
                      self.v_scales):
            for li, pool in enumerate(pools):
                pools[li] = Tensor(pool._data.at[new].set(pool._data[old]))

    # ------------------------------------------------------------------
    # chunked-prefill scheduler: rows -> one mixed dispatch
    # ------------------------------------------------------------------
    def _draft(self, r, kcap):
        """Draft up to ``kcap`` speculative tokens for a live decoder
        from its per-sequence drafter (created lazily; synced to the
        committed prompt + output only — never to rejected drafts).
        Out-of-vocab proposals from a custom drafter are dropped at the
        first offender. Constrained requests never draft: the
        constraint hook is host code evaluated once per scheduled
        position, so mid-dispatch draft positions can't consult it."""
        if r.sampling is not None and r.sampling.constraint is not None:
            return ()
        st = self._spec_state.get(r.seq_id)
        if st is None:
            st = self._spec_state[r.seq_id] = self._drafter_factory()
        st.sync(r.prompt_ids, r.output_ids)
        v = self.model.config.vocab_size
        out = []
        for t in st.propose(kcap):
            t = int(t)
            if not 0 <= t < v:
                break
            out.append(t)
        return tuple(out[:int(kcap)])

    def _spec_worth(self, live):
        """Probe (caller holds the engine lock): does any live decoder
        have at least one draft to verify? Proposals are pure (sync
        folds only committed tokens), so probing costs a dict lookup
        per row and never skews the drafter. When nothing proposes, a
        mixed spec step would be a plain one-token step paying the
        chunk-shaped program — the scan is strictly better, so
        :meth:`decode_many` falls back to it until the history gives
        the drafter something to say."""
        for r in live:
            if r.max_new_tokens - len(r.output_ids) <= 1:
                continue
            if self._draft(r, 1):
                return True
        return False

    def spec_stats(self):
        """Cumulative speculative-decoding counters: proposed/accepted
        draft tokens and the acceptance rate (also exported as
        ``serving_spec_accept_rate``)."""
        with self._lock:
            p, a = self._spec_proposed, self._spec_accepted
        return {"k": self.spec_k, "proposed": p, "accepted": a,
                "accept_rate": a / p if p else 0.0}

    @staticmethod
    def _landing(prev):
        """What the dispatch in flight ``prev`` (or None) will have done
        to its sequences whatever its tokens' values: ``{seq_id:
        (prompt positions prefilled once it lands, index of the row
        whose token the sequence will emit, or None)}``."""
        landing: dict[int, tuple] = {}
        for i, (r, sid, start, n, _, is_dec) in enumerate(
                prev.rows if prev is not None else ()):
            if r.done or r.seq_id != sid:
                continue
            if is_dec:
                landing[sid] = (r._prefilled, i)
            else:
                # rows of one sequence are consecutive: the last wins
                end = start + n
                landing[sid] = (end,
                                i if end >= len(r.prompt_ids) else None)
        return landing

    def _schedule_rows(self, prev=None):
        """Build one mixed step's row list (caller holds the engine
        lock): every fully-prefilled live sequence gets a decode row
        (one guaranteed token plus up to ``spec_k`` speculative draft
        tokens when the drafter has proposals and pages/budget allow —
        the row becomes a (q_len = 1+k) verify chunk over pages the
        drafts are tentatively written to), then the remaining
        ``chunk_budget`` fills with prefill chunks of at most
        ``chunk_block`` tokens each, FIFO by admission — a long prompt
        may take several chunk rows of ONE dispatch when the budget
        allows (one, of a model whose layers keep a state a sequence:
        a row's scan starts from the stored state), and what doesn't
        fit waits for the next step, so a
        10k-token prompt never stalls a live decode for more than one
        budget. Returns (rows, cow) where each row is
        ``(req, sid, start, n, toks, is_decode)``.

        ``prev`` is the dispatch in flight, whose tokens the host has
        not seen (None: there is none). What it does to a sequence that
        does not depend on their VALUES is counted as done: its chunk
        rows' positions as prefilled, the token each of its decode rows
        and final chunks will emit as emitted, so a request whose last
        token is in flight gets no row. A decode row whose input token
        is that token names it by where the device holds it: ``toks``
        is ``(~i,)``, ``i`` the index of the producing row in
        ``prev``'s returned tokens (`DispatchLayout`'s ``prev_idx``).
        If the sequence turns out to have ended with that token (EOS, a
        stop token; or a cancel, a deadline, an eviction lands first),
        the row is stale: :meth:`_apply_rows` drops it."""
        landing = self._landing(prev)
        decode, prefill = [], []
        for r in self._live.values():
            if r.done:
                continue
            done_to, src = landing.get(r.seq_id, (r._prefilled, None))
            if done_to < len(r.prompt_ids):
                prefill.append(r)
            elif src is None or len(r.output_ids) + 1 < r.max_new_tokens:
                decode.append(r)
        decode = self._relieve_pressure(decode, 1)
        rows, cow = [], []
        budget = self.chunk_budget
        page = self.page_size
        # speculative page headroom: _relieve_pressure proved ONE token
        # per decode row fits; drafts may only spend what is left after
        # that guarantee, so speculation can never evict or shed
        spare = 0
        if self.spec_k:
            reserved = sum(
                max(0, -(-(self.alloc._lens[r.seq_id] + 1) // page)
                    - len(self.alloc._tables[r.seq_id]))
                for r in decode)
            spare = self.alloc.free_pages - reserved
        n_dec = len(decode)
        # drafts must never starve pending prefill: with prompts
        # waiting, a chunk_block of budget is reserved for them, so
        # the chunked-prefill invariant (concurrent TTFT bounded by
        # one budget) survives sustained high acceptance — speculation
        # throttles while prompts chunk in, not the other way around
        reserve = self.chunk_block if prefill else 0
        for i, r in enumerate(decode):
            sid = r.seq_id
            drafts = ()
            if self.spec_k:
                # leave one budget token for every remaining decode row
                # and never draft past the request's own budget
                kcap = min(self.spec_k, self.chunk_block - 1,
                           budget - reserve - (n_dec - i),
                           r.max_new_tokens - len(r.output_ids) - 1)
                if kcap > 0:
                    drafts = self._draft(r, kcap)
                if drafts:
                    ln = self.alloc._lens[sid]
                    cur = len(self.alloc._tables[sid])
                    base = max(0, -(-(ln + 1) // page) - cur)
                    while drafts:
                        need = max(0, -(-(ln + 1 + len(drafts)) // page)
                                   - cur)
                        if need - base <= spare and cur + need \
                                <= self.alloc.max_pages_per_seq:
                            spare -= need - base
                            break
                        drafts = drafts[:-1]
            n = 1 + len(drafts)
            at = self.alloc.extend(sid, n)
            # copy-on-write backstop: the write position must never
            # land in a page shared with the prefix cache (positions
            # past ``at`` sit in the same now-private page or in
            # pages the extend just allocated)
            cp = self.alloc.ensure_writable(sid, at)
            if cp is not None:
                cow.append(cp)
            src = landing.get(sid, (0, None))[1]
            if src is not None:
                tok = ~src
            else:
                tok = r.output_ids[-1] if r.output_ids \
                    else int(r.prompt_ids[-1])
            rows.append((r, sid, at, n, (tok,) + drafts, True))
            budget -= n
        for r in prefill:
            if budget <= 0 or len(rows) >= self.rows_cap \
                    or len(rows) - n_dec == self.chunk_rows:
                break
            off = int(landing.get(r.seq_id, (r._prefilled,))[0])
            n_total = len(r.prompt_ids)
            # defensive copy-on-write for the chunk's first position:
            # page-aligned prefix matches always continue into pages
            # this sequence owns, but a shared page must stay immutable
            # regardless
            cp = self.alloc.ensure_writable(r.seq_id, off)
            if cp is not None:
                cow.append(cp)
            while off < n_total and budget > 0 \
                    and len(rows) < self.rows_cap:
                n = min(self.chunk_block, n_total - off, budget)
                toks = tuple(int(x) for x in r.prompt_ids[off:off + n])
                rows.append((r, r.seq_id, off, n, toks, False))
                off += n
                budget -= n
                if self._single_chunk:
                    break
        return rows, cow

    def _sample_arrays(self, reqs, r_cap, into=None):
        """Host-built per-row sampler metadata for one dispatch:
        ``reqs`` is a <= r_cap list of requests (None entries and the
        padding tail stay inert greedy rows). Constraint hooks run
        HERE, once per scheduled dispatch — a raising hook degrades to
        unconstrained (counted), an oversized allowed set truncates to
        the engine's static ``sample_slots`` width (counted). The seven
        arrays are written in place where ``into`` (the views of a
        dispatch's buffer, at their fill values) holds them, made here
        for the scan's callers."""
        b = self.sample_slots
        if into is None:
            into = {"temps": np.zeros((r_cap,), np.float32),
                    "top_ps": np.ones((r_cap,), np.float32),
                    "top_ks": np.zeros((r_cap,), np.int32),
                    "seeds": np.zeros((r_cap,), np.int32),
                    "slot_ids": np.full((r_cap, b), -1, np.int32),
                    "slot_vals": np.zeros((r_cap, b), np.float32),
                    "cmodes": np.zeros((r_cap,), np.int32)}
        temps, top_ps, top_ks, seeds, slot_ids, slot_vals, cmodes = (
            into[k] for k in ("temps", "top_ps", "top_ks", "seeds",
                              "slot_ids", "slot_vals", "cmodes"))
        if not self.sample_enabled:
            return (temps, top_ps, top_ks, seeds, slot_ids, slot_vals,
                    cmodes)
        for i, r in enumerate(reqs):
            sp = r.sampling if r is not None else None
            if sp is None:
                continue
            temps[i] = sp.temperature
            top_ps[i] = sp.top_p
            top_ks[i] = sp.top_k
            seeds[i] = r._seed or 0
            bias = sp.logit_bias or {}
            allowed = None
            if sp.constraint is not None:
                try:
                    allowed = sp.constraint(r.prompt_ids,
                                            tuple(r.output_ids))
                except Exception:
                    self._m["constraint_errors"].inc()
                    allowed = None
            if allowed is not None:
                ids = [int(tk) for tk in allowed]
                if not ids:
                    # an empty allowed set has no valid continuation;
                    # degrade to unconstrained rather than emit the
                    # arbitrary all-masked argmax
                    self._m["constraint_errors"].inc()
                elif len(ids) > b:
                    self._m["constraint_truncated"].inc()
                    ids = ids[:b]
                if ids:
                    cmodes[i] = 1
                    for j, tk in enumerate(ids):
                        slot_ids[i, j] = tk
                        slot_vals[i, j] = bias.get(tk, 0.0)
                    continue
            if bias:
                for j, (tk, v) in enumerate(list(bias.items())[:b]):
                    slot_ids[i, j] = int(tk)
                    slot_vals[i, j] = v
        return temps, top_ps, top_ks, seeds, slot_ids, slot_vals, cmodes

    def _dispatch_rows(self, rows, cow):
        """Build and enqueue ONE mixed program over an already-scheduled
        row list (caller holds the dispatch locks): copy-on-write, the
        host-built metadata, its one transfer, the enqueue. A token the
        plan named by its place in the last program's output (``~i``,
        see :meth:`_schedule_rows`) goes into ``prev_idx``. Returns
        what :meth:`_apply_rows` needs and what the spans say: ``(next
        tokens still on the device, each row's first index in the T
        axis, enqueue seconds, cold, needs_mixed, t_cap, bytes handed
        to the device, rows that sample, rows on the attention kernel's
        small tile)``."""
        # speculative verify rows are multi-token decode rows: they
        # need the chunk-shaped program exactly like prefill chunks do
        needs_mixed = any(n > 1 or not is_dec
                          for _, _, _, n, _, is_dec in rows)
        t_cap = self.chunk_budget if needs_mixed else self.max_batch
        layout = self._dispatch_layout(t_cap)
        _, r_cap, qb, _, _ = layout.shape
        for old, new in cow:
            self._copy_page(old, new)
        key = ("mixed", t_cap)
        cold = key not in self._warmed_keys
        if cold and self._m["ttft"] is not _om.NULL:
            # compile this token shape OUTSIDE the TTFT window: a dummy
            # dispatch (all page writes land in the trash page, emitted
            # tokens discarded) triggers the one-time trace + compile,
            # and the affected clocks shift past it so TTFT keeps one
            # honest sample per request without the multi-second
            # compile skewing the histogram's +Inf bucket forever.
            # Under PADDLE_TPU_METRICS=0 this is skipped (zero-cost
            # mandate) and the cold dispatch just skips tpot.
            t_w = time.perf_counter()
            self._warm_mixed(t_cap)
            warm_dur = time.perf_counter() - t_w
            with self._lock:
                for r in {row[0] for row in rows}:
                    if r._t_admit is not None:
                        r._t_admit += warm_dur
                    if r._expires_at is not None:
                        # the deadline clock starts at admission;
                        # compile warmup is engine overhead, not
                        # request time
                        r._expires_at += warm_dur
            cold = False
        now = time.perf_counter()
        for r, _, _, _, _, is_dec in rows:
            if not is_dec and r._t_first_chunk is None:
                r._t_first_chunk = now
        # host-built metadata, written into ONE fresh buffer the
        # program takes apart again (a buffer kept for the next dispatch
        # could change under a transfer still reading it): reads of the
        # allocator's tables are safe here — cross-thread releases
        # defer past the whole _entry
        buf = layout.new()
        f = layout.views(buf)
        tokens, pos = f["tokens"], f["pos"]
        flat_idx, last_idx = f["flat_idx"], f["last_idx"]
        tables, kv_lens, q_starts, q_lens = (f["tables"], f["kv_lens"],
                                             f["q_starts"], f["q_lens"])
        # fused-write metadata: per row, the first position of its
        # sequence written by THIS dispatch, that position's packed
        # index, and the sequence's final kv_len (rows of one sequence
        # are consecutive, so one forward pass collects all three)
        w_starts, w_flats, w_ends = (f["w_starts"], f["w_flats"],
                                     f["w_ends"])
        slots = f.get("slots")
        seq_first: dict[int, tuple] = {}     # sid -> (w_start, w_flat)
        seq_last: dict[int, int] = {}        # sid -> w_end
        t = 0
        flat_start = []         # each row's first index in the T axis
        for i, (r, sid, start, n, toks, is_dec) in enumerate(rows):
            tb = self.alloc._tables[sid]
            tables[i, :len(tb)] = tb
            kv_lens[i] = start + n
            q_starts[i] = start
            q_lens[i] = n
            if slots is not None:
                slots[i] = self.alloc.slot_of(sid)
            tokens[0, t:t + n] = toks
            pos[0, t:t + n] = start + np.arange(n)
            flat_idx[t:t + n] = i * qb + np.arange(n)
            flat_start.append(t)
            if sid not in seq_first:
                seq_first[sid] = (start, t)
            seq_last[sid] = start + n
            t += n
            last_idx[i] = t - 1
        for i, (r, sid, start, n, toks, is_dec) in enumerate(rows):
            w_starts[i], w_flats[i] = seq_first[sid]
            w_ends[i] = seq_last[sid]
        fed = tokens < 0
        f["prev_idx"][fed] = ~tokens[fed]
        tokens[fed] = 0
        self._sample_arrays([row[0] for row in rows], r_cap, into=f)
        # 0 is the false side of the sample step's branch: the program
        # takes the argmax and nothing else
        sampled = int(np.count_nonzero(f["temps"] > 0))
        # the rows the attention kernel computes on its small tile
        tile_rows = None if self._tile_tokens is None else int(
            np.count_nonzero((q_lens > 0) & (q_lens <= self._tile_tokens)))
        self._record_shape("mixed", t_cap)
        self._arm_watchdog(cold)
        with self._lock:
            self._in_dispatch = True
        t0 = time.perf_counter()
        try:
            with no_grad(), _span("serving.mixed_step", rows=len(rows),
                                  tokens=int(t), prefill=needs_mixed):
                nxt, stats = self._run_mixed(buf)
        finally:
            with self._lock:
                self._in_dispatch = False
            dur = time.perf_counter() - t0
            self._disarm_watchdog(dur, cold=cold)
            self._warmed_keys.add(key)
        self._note_mixed_bytes(t_cap)
        self._flush_deferred()
        self._carry = nxt       # what the next program's ``prev`` is
        self._layer_stats = stats[0] if stats else None
        return (nxt, flat_start, dur, cold, needs_mixed, t_cap,
                buf.nbytes, sampled, tile_rows)

    def _apply_rows(self, rows, out, flat_start, dur, cold, needs_mixed):
        """Apply one mixed dispatch's next tokens ``out`` (``[t_cap]``,
        on the host): prefill progress, prefix-cache pins, speculative
        verification (accept the longest exactly-matching draft prefix,
        roll back rejected draft pages), emitted tokens. Returns
        ``(tokens emitted, stale rows)``.

        A row is STALE when its sequence ended after the row was
        launched: the request is done (EOS or a stop token in the
        dispatch before, a cancel, a deadline) or lives on under another
        ``seq_id`` (evicted, paused). Nothing of it is applied. What the
        device did for it is harmless: it wrote one token's K/V (or a
        slot's state, a ring's page) into pages and a slot the sequence
        still held when the row was planned, and they went back to the
        allocator with the sequence's release, the row's one planned
        ``extend`` among them. The device runs programs in the order
        they were enqueued and a freed page or slot is handed out only
        by a plan that comes after the release, so a new owner's writes
        all land after the stale one; and a new owner reads only the
        positions it wrote itself (``kv_len``), its slot's states and
        rings restarting where its ``starts == 0``."""
        if not cold and not needs_mixed:
            # a pure-decode dispatch is one token per live row: honest
            # per-token latency. Mixed dispatches carry prefill work
            # and would skew the histogram.
            self._m["tpot"].observe(dur)
            self._token_times.append(dur)
        finished, fin_sids = [], []
        with self._lock:
            for (r, sid, start, n, toks, is_dec) in rows:
                if is_dec or r.done or r.seq_id != sid:
                    continue
                # the seq_id check drops rows whose request was evicted
                # and requeued mid-dispatch — its reset progress must
                # not be advanced by this stale chunk
                self._m["prefill_tokens"].inc(n)
                r._prefilled = max(r._prefilled, start + n)
                if r._prefilled >= len(r.prompt_ids) \
                        and r not in finished:
                    finished.append(r)
                    fin_sids.append(sid)
        # pin finished prompts' pages in the prefix cache BEFORE
        # emitting: a max_new_tokens=1 request retires (and releases)
        # at emit, and its prefix must still make it into the cache
        if finished and self.prefix is not None:
            self._prefix_insert(finished, fin_sids)
        # speculative verification BEFORE any emission: out[t] is the
        # target continuation after packed token t — argmax for greedy
        # rows, the position-keyed SAMPLE for sampled rows — so a
        # verify row's window out[f .. f+n-1] holds exactly the token
        # the sequential engine would emit after the pending token and
        # after each draft. Accepting the longest matching prefix IS
        # rejection sampling for our point-mass drafter (accept w.p.
        # p(draft), reject resamples the residual — see sampling.py),
        # and keeps sampled outputs seed-stable with speculation on or
        # off. Accept the longest prefix where draft i+1 equals out i;
        # rejected drafts' pages roll back NOW, while the sequence is
        # still live (an emission below may retire it and release
        # everything — rollback after that would touch a freed table)
        accepted: dict[int, int] = {}
        if any(is_dec and n > 1 for *_, n, _, is_dec in rows):
            with self._lock:
                for i, (r, sid, start, n, toks, is_dec) \
                        in enumerate(rows):
                    if not is_dec or n <= 1:
                        continue
                    f = flat_start[i]
                    acc = 0
                    while acc < n - 1 \
                            and int(toks[1 + acc]) == int(out[f + acc]):
                        acc += 1
                    accepted[i] = acc
                    self._spec_proposed += n - 1
                    self._spec_accepted += acc
                    self._m["spec_proposed"].inc(n - 1)
                    if acc:
                        self._m["spec_accepted"].inc(acc)
                    rejected = (n - 1) - acc
                    if rejected and not r.done and r.seq_id == sid:
                        # deadline/cancel/evict mid-speculation: a row
                        # whose request turned terminal (or was
                        # requeued under a fresh seq_id) mid-dispatch
                        # skips rollback — release/re-admission owns
                        # its pages wholesale
                        self.alloc.rollback(sid, rejected)
                if self._spec_proposed:
                    self._m["spec_rate"].set(
                        self._spec_accepted / self._spec_proposed)
        emitted = stale = 0
        dec_rows = dec_tokens = 0
        # spec engines index `out` by flat token position ([T] argmax);
        # plain engines by row ([R] last-position argmax)
        by_pos = bool(self.spec_k)
        for i, (r, sid, start, n, toks, is_dec) in enumerate(rows):
            if r.done or r.seq_id != sid:
                stale += 1
                continue
            f = flat_start[i]
            if is_dec:
                # the guaranteed decode token plus every accepted draft
                # (greedy-exact by construction); _emit retires at EOS
                # or max_new_tokens, discarding the accepted tail
                dec_rows += 1
                for j in range(accepted.get(i, 0) + 1):
                    if r.done:
                        break
                    self._emit(r, int(out[f + j] if by_pos else out[i]))
                    emitted += 1
                    dec_tokens += 1
            elif (start + n) >= len(r.prompt_ids):
                # FINAL prompt chunks emit their last position; a mid-
                # prompt chunk's argmax is meaningless and discarded
                self._emit(r, int(out[f + n - 1] if by_pos else out[i]))
                emitted += 1
        if accepted and dec_rows:
            self._m["spec_tpd"].set(dec_tokens / dec_rows)
        if not cold and needs_mixed and dec_tokens \
                and all(is_dec for *_, is_dec in rows):
            # a pure decode+verify dispatch (no prefill rows): the
            # per-token latency is the dispatch amortized over what it
            # committed — same accounting as the decode scan — so tpot
            # and _retry_after() stay live while speculation runs
            per = dur / dec_tokens
            self._token_times.append(per)
            for _ in range(dec_tokens):
                self._m["tpot"].observe(per)
        if stale:
            self._m["stale_rows"].inc(stale)
        return emitted, stale

    # ------------------------------------------------------------------
    # stuck-dispatch watchdog
    # ------------------------------------------------------------------
    def _arm_watchdog(self, cold):
        """Arm the shared StepWatchdog for one dispatch: timeout =
        max(stuck_min_timeout, stuck_factor x P99 of warm dispatches).
        Cold dispatches (trace + compile, legitimately multi-second)
        never arm; with < 8 samples there is no P99 worth trusting."""
        if cold or not self.stuck_factor or self._closed:
            return
        times = self._dispatch_times
        if len(times) < 8:
            return
        s = sorted(times)
        p99 = s[min(len(s) - 1, int(math.ceil(0.99 * len(s))) - 1)]
        if self._wd is None:
            from ..distributed.watchdog import StepWatchdog
            self._wd = StepWatchdog(timeout=float("inf"),
                                    name="serving.decode").start()
        self._wd.arm(max(self.stuck_min_timeout, self.stuck_factor * p99))

    def _disarm_watchdog(self, duration=None, cold=False):
        if duration is not None and not cold:
            self._dispatch_times.append(duration)
        if self._wd is not None:
            self._wd.disarm()

    def close(self):
        """Release engine-owned background resources (the stuck-dispatch
        watchdog thread). Idempotent; the engine stays usable but
        unwatched — later dispatches will NOT respawn the watchdog."""
        self._closed = True
        # nothing stays in flight: its tokens reach their requests. The
        # wait is bounded, so that a turn hanging in another thread (what
        # the watchdog is there to report) cannot hang the closing one
        if self._dispatch_lock.acquire(timeout=2.0):
            try:
                with contextlib.suppress(Exception):
                    self._settle()
            finally:
                self._dispatch_lock.release()
        if self._wd is not None:
            self._wd.stop()
            self._wd = None
        if self.tier is not None:
            self.tier.close()

    # ------------------------------------------------------------------
    # warm restart: shape registry + prewarm (ROADMAP item 5)
    # ------------------------------------------------------------------
    def _compute_shape_key(self):
        """Stable identity of this engine's compile surface: every
        dimension that shapes a serving program (model dims + batch
        geometry + pool layout + dtype). Two engines with the same key
        compile byte-identical programs, so one's recorded shape buckets
        are the other's valid warm-up recipe."""
        cfg = self.model.config
        dt = str(self.model.parameters()[0].dtype)
        parts = (cfg.vocab_size, cfg.hidden_size, cfg.intermediate_size,
                 cfg.num_hidden_layers, cfg.num_attention_heads,
                 cfg.num_key_value_heads, cfg.head_dim,
                 # MoE dims shape the FFN programs (router + stacked
                 # expert weights + grouped-GEMM grids): an MoE engine
                 # and a dense engine of otherwise equal geometry must
                 # not share prewarm recipes
                 getattr(cfg, "moe_num_experts", 0),
                 getattr(cfg, "moe_top_k", 0),
                 getattr(cfg, "moe_intermediate_size", None) or 0,
                 float(cfg.rope_theta), self.max_batch, self.page_size,
                 self.width, self.chunk_budget, self.chunk_block,
                 len(self.k_pools) and
                 tuple(self.k_pools[0]._data.shape), dt,
                 # the pool dtype shapes every serving program (int8
                 # pages add scale-sidecar inputs) and speculation
                 # changes the mixed program's lm-head ([T] vs [R]
                 # argmax) AND which scan lengths get dispatched — two
                 # engines that differ in either must not share
                 # warm-up recipes
                 str(self.k_pools[0]._data.dtype)
                 if self.k_pools else dt, bool(self.spec_k),
                 # the sample step adds inputs + a branch that holds a
                 # vocab sort to every serving program, and the slot
                 # width shapes the bias arrays — both fork the compiled
                 # surface
                 bool(self.sample_enabled), self.sample_slots,
                 # weight-only int8 forks every serving program: the
                 # projections trade one bf16 weight input for an int8
                 # weight + scale-sidecar pair (and the block size
                 # shapes the sidecars), so a prewarm recipe recorded
                 # by a bf16 engine must never drive an int8 one (or
                 # vice versa, or across block sizes)
                 bool(self.weight_quant), int(self.weight_block))
        return "llama:" + hashlib.sha1(
            repr(parts).encode()).hexdigest()[:16]

    def _record_shape(self, kind, value):
        """Record one dispatched shape bucket in the persistent
        signature registry (one file write per distinct value per
        process; a no-op when the compile cache is disabled — without
        the cache a prewarm would re-PAY every compile, not skip it)."""
        if self._cache_dir is None:
            return
        k = (kind, value)
        if k in self._recorded_shapes:
            return
        self._recorded_shapes.add(k)
        try:
            _cw.shape_registry().record(self._shape_key, kind, value)
        except Exception:
            pass            # registry IO must never fail a dispatch

    def _warm_mixed(self, t_cap):
        """Compile one mixed-program token shape via a dummy dispatch:
        every row is inactive (kv_len 0 — the ragged kernel emits
        zeros), every page write lands in the trash page and the
        emitted tokens are discarded, so no request state is touched.
        The program donates its pool inputs — the returned pools must
        replace ours. Returns False for a token count that doesn't
        match this engine's geometry (a stale registry entry)."""
        t_cap = int(t_cap)
        layout = self._dispatch_layout(t_cap)
        if layout is None:
            return False
        with no_grad():
            self._run_mixed(layout.new())
        self._warmed_keys.add(("mixed", t_cap))
        self._warm_dispatches += 1
        self._record_shape("mixed", t_cap)
        self._note_mixed_bytes(t_cap)
        return True

    def _warm_scan(self, n):
        """Compile the n-tick decode-scan program via a dummy dispatch
        (trash tables, lens 1). The scan donates its pool inputs —
        reassign from the outputs."""
        b = self.max_batch
        sf = self._ensure_scan_compiled(int(n))
        samp = self._sample_arrays([], b)
        with no_grad():
            out = sf(Tensor(jnp.asarray(np.zeros((b, 1), np.int64))),
                     Tensor(jnp.asarray(np.full(
                         (b, self.width), self.trash_page, np.int32))),
                     Tensor(jnp.asarray(np.ones((b,), np.int32))),
                     *[Tensor(jnp.asarray(a)) for a in samp],
                     self.k_pools, self.v_pools,
                     self.k_scales, self.v_scales)
        self._adopt_scan_pools(out)
        self._warmed_keys.add(("scan", int(n)))
        self._warm_dispatches += 1

    def _adopt_scan_pools(self, out):
        """Reassign the donated pool (and scale-sidecar) arrays a scan
        dispatch returned after its token block."""
        at = 1
        for name in ("k_pools", "v_pools", "k_scales", "v_scales"):
            n = len(getattr(self, name))
            setattr(self, name, list(out[at:at + n]))
            at += n

    def prewarm(self, mixed=None, scans=None):
        """Compile this engine's serving programs BEFORE traffic
        arrives, so a replacement replica's first request pays
        milliseconds, not the full compile bill. With no arguments the
        recipe comes from the persistent shape registry — the
        mixed-program token shapes and decode-scan tick counts an
        engine of identical geometry actually dispatched (recorded as
        they compiled). Combined with the persistent compilation cache
        these compiles are disk hits on a warm host
        (``compile_cache_hit_total``), which is what turns an ~19 s
        restart into seconds.

        Returns ``{"mixed": [...], "scan": [...]}`` — what was warmed
        (also kept on ``self.prewarmed``)."""
        if mixed is None and scans is None:
            recipe = {}
            try:
                recipe = _cw.shape_registry().lookup(self._shape_key) \
                    if self._cache_dir is not None else {}
            except Exception:
                recipe = {}
            mixed = recipe.get("mixed", ())
            scans = recipe.get("scan", ())
        done = {"mixed": [], "scan": []}
        with self._dispatch_lock, _CROSS_ENGINE_LOCK, \
                _span("serving.prewarm", mixed=len(mixed or ()),
                      scan=len(scans or ())):
            for t_cap in sorted(set(mixed or ())):
                if self._warm_mixed(int(t_cap)):
                    done["mixed"].append(int(t_cap))
            for n in sorted(set(scans or ())):
                self._warm_scan(int(n))
                done["scan"].append(int(n))
        self.prewarmed = done
        return done

    # ------------------------------------------------------------------
    # scheduling
    # ------------------------------------------------------------------
    def prefill_backlog(self):
        """Prompt tokens admitted but not yet written to pages — the
        chunked scheduler's pending prefill work. A routing signal for
        the cluster's load-aware router: a replica chewing through a
        long prompt is busier than its live count suggests."""
        with self._lock:
            return sum(max(0, len(r.prompt_ids) - r._prefilled)
                       for r in self._live.values() if not r.done)

    def _set_pool_gauges(self):
        self._m["queue_depth"].set(len(self._live))
        self._m["prefill_backlog"].set(self.prefill_backlog())
        self._m["kv_util"].set(
            1.0 - self.alloc.free_pages / self.alloc.num_pages)
        if _om.enabled():
            # per-dispatch device-memory accounting (host metadata walks
            # only, no sync), throttled so the live-array enumeration
            # never rides the per-token decode path, + a rate-limited
            # flight-recorder snapshot
            _cw.sample_device_memory(min_interval=1.0)
            _fr.periodic_snapshot()

    def _validate(self, req):
        cap_pages = min(self.alloc.max_pages_per_seq, self.alloc.num_pages)
        max_prompt = cap_pages * self.page_size
        n = len(req.prompt_ids)
        if n > max_prompt:
            raise ValueError(
                f"prompt of {n} tokens exceeds this engine's KV capacity "
                f"of {max_prompt} tokens ({cap_pages} pages x "
                f"{self.page_size} slots); split the prompt or size the "
                f"pool up (num_pages/max_pages_per_seq)")
        sp = req.sampling
        if sp is not None:
            if not sp.is_greedy and not self.sample_enabled:
                raise ValueError(
                    "request asks for sampled decoding but this engine "
                    "was built with sampling=False; rebuild with "
                    "sampling=True (or unset PADDLE_TPU_SAMPLING=0)")
            if sp.logit_bias and len(sp.logit_bias) > self.sample_slots:
                raise ValueError(
                    f"logit_bias has {len(sp.logit_bias)} entries but "
                    f"this engine packs sample_slots={self.sample_slots}"
                    f" per row; raise sample_slots or trim the bias")

    def _retry_after(self):
        """Seconds until capacity plausibly frees: the live set's
        shortest remaining token budget x recent median per-token
        latency. Falls back to one backoff quantum without history."""
        with self._lock:
            live = [r for r in self._live.values() if not r.done]
            times = sorted(self._token_times)
        if not live or not times:
            return max(self.admit_backoff, 0.005)
        remaining = min(max(1, r.max_new_tokens - len(r.output_ids))
                        for r in live)
        return round(remaining * times[len(times) // 2], 4)

    def _try_reserve(self, req):
        """One admission attempt: capacity check, page reservation and
        live-set insertion are ONE atomic transition under the engine
        lock, so two admitting threads can never push the live set past
        ``max_batch`` between a check and an insert. Returns a failure
        reason or None."""
        try:
            # outside the lock: a hang/sleep fault must not wedge the
            # engine lock, and an injected MemoryError rides the same
            # pool-exhausted path the real allocator raises
            _faults.fire("serve.admit", step=self._dispatch_count)
        except MemoryError:
            return "KV page pool exhausted"
        with self._lock:
            if self._draining:
                return "draining"
            if len(self._live) >= self.max_batch:
                return "engine full"
            n = len(req.prompt_ids)
            if self.tier is not None and self.prefix is not None:
                # demoted prefix pages promote back BEFORE the match,
                # so a system prompt that rode out pressure in host
                # DRAM is a cache hit, not a re-prefill
                self._promote_prefix(req.prompt_ids, n)
            cached = 0
            val_retries = 0
            evicted_cache = False
            recorded = False
            while True:
                shared, cached = ([], 0)
                if self.prefix is not None:
                    # stats recorded once per admission, not per retry
                    shared, cached = self.prefix.match(
                        req.prompt_ids, record=not recorded)
                    recorded = True
                try:
                    self.alloc.admit(req.seq_id, n, shared_pages=shared)
                    break
                except ValueError:
                    # a concurrent prefix.clear()/eviction freed the
                    # matched pages between match and admit: re-match
                    # and retry (a ValueError with NO shared pages is
                    # a genuine validation error and propagates)
                    if shared and val_retries < 2:
                        val_retries += 1
                        continue
                    raise
                except MemoryError:
                    # cached prefixes are an optimization, never a
                    # reason to shed load: give cold cache pages back
                    # to the pool and retry once (the retry re-matches
                    # — eviction may have taken this prompt's chain)
                    if evicted_cache or self.prefix is None:
                        return "KV page pool exhausted"
                    evicted_cache = True
                    need = max(1, math.ceil(n / self.page_size))
                    while self.alloc.free_pages < need \
                            and self.prefix.pages:
                        self.prefix.evict_pages(need
                                                - self.alloc.free_pages)
                    if self.alloc.free_pages < need:
                        return "KV page pool exhausted"
            req._cached_tokens = cached
            # stamp the prefill cursor BEFORE the request becomes
            # visible in _live: a concurrent dispatch thread must never
            # see a warm request at _prefilled 0 and schedule chunks
            # over its still-shared cached-prefix pages
            req._prefilled = cached
            self._live[req.seq_id] = req
            req.status = "live"
            if self.prefix is not None:
                self._m["prefix_lookups"].inc()
                if cached:
                    self._m["prefix_hits"].inc()
                    self._m["prefix_saved"].inc(cached)
                self._m["prefix_pages"].set(self.prefix.pages)
        return None

    def _degrade_trim(self, req, tried):
        """Ladder rung 1: truncate the lowest-priority victim's
        ``max_new_tokens`` to what it already produced, retiring it NOW
        with partial output (status ``completed``, ``trimmed=True``) —
        frees its batch slot and pages without discarding work."""
        with self._lock:
            victims = [r for r in self._live.values()
                       if not r.done and r.priority < req.priority
                       and r.output_ids and r.seq_id not in tried]
            if not victims:
                return False
            v = min(victims, key=lambda r: (r.priority, len(r.output_ids)))
            tried.add(v.seq_id)
            self._trim(v)
        return True

    def _trim(self, v):
        """Shared trim bookkeeping: truncate the victim's budget to what
        it already produced and retire it NOW (partial output kept,
        ``trimmed=True``). Caller holds the engine lock."""
        v.max_new_tokens = max(1, len(v.output_ids))
        v.trimmed = True
        if self._retire(v, "completed"):
            self._m["completed"].inc()
            self._m["degraded"].labels("trim").inc()

    def _evict(self, v):
        """Shared eviction bookkeeping: reclaim the victim's pages and
        re-queue it against its ``retry_budget`` (a re-admission
        restarts generation from scratch — its KV is gone) or fail it
        typed when the budget is spent. A victim that turned terminal
        (or was already requeued) since selection is left alone."""
        with self._lock:
            if v.done or v.seq_id is None:
                return
            if v.seq_id in self._live:
                del self._live[v.seq_id]
            self._spec_state.pop(v.seq_id, None)
            self._release_pages(v.seq_id)
            self._requeue_or_fail(v)

    def _requeue_or_fail(self, v):
        """Shared evict epilogue (the ladder's evict rung AND the host
        tier's failed-restore fallback): park the victim for a
        from-scratch retry against its ``retry_budget``, or fail it
        typed when the budget is spent. Caller holds the engine lock
        and has already released/returned the victim's pages."""
        if v.retry_budget > 0:
            v.retry_budget -= 1
            v.output_ids = []
            v.status = "requeued"
            v._t_admit = None
            v._expires_at = None
            v._cached_tokens = 0    # re-matched at re-admission
            v._prefilled = 0        # KV is gone; prefill restarts
            # a fresh seq_id on re-admission: the old id may still
            # have a deferred page release in flight
            v.seq_id = None
            self._requeue.append(v)
        else:
            v.done = True
            v.status = "evicted"
            v.error = AdmissionError(
                "evicted under pressure; retry budget exhausted",
                live=len(self._live), max_batch=self.max_batch,
                free_pages=self.alloc.free_pages,
                num_pages=self.alloc.num_pages, retries=0)
        self._m["degraded"].labels("evict").inc()

    def _degrade_evict(self, req):
        """Ladder rung 2: evict the lowest-priority victim — pages
        reclaimed; the victim restarts from scratch via the requeue
        (``retry_budget`` permitting) or fails typed."""
        with self._lock:
            victims = [r for r in self._live.values()
                       if not r.done and r.priority < req.priority]
            if not victims:
                return False
            v = min(victims, key=lambda r: (r.priority, len(r.output_ids)))
            self._evict(v)
        return True

    # ------------------------------------------------------------------
    # host-DRAM KV page tier: the pause rung (ROADMAP item 5a)
    # ------------------------------------------------------------------
    def _pause(self, v):
        """The ladder's pause rung: D2H-export the victim's pages into
        the host tier, release the HBM pages, and park the request
        ``paused`` on the requeue — the evict rung minus the destroyed
        work (output, prefill progress, seed and retry budget all
        survive; the deadline clock keeps ticking while parked). Any
        tier failure is typed and degrades to :meth:`_evict` — never a
        wedge, never a leak. Caller holds the engine lock."""
        if self.tier is None:
            self._evict(v)
            return
        with self._lock:
            if v.done or v.seq_id is None:
                return
            try:
                table, n_tokens = self.alloc.export_table(v.seq_id)
            except KeyError:
                self._evict(v)
                return
            try:
                key = self.tier.export_seq(
                    self.k_pools, self.v_pools, self.k_scales,
                    self.v_scales, table, n_tokens,
                    step=self._dispatch_count)
            except TierError:
                self._evict(v)
                return
            if v.seq_id in self._live:
                del self._live[v.seq_id]
            self._spec_state.pop(v.seq_id, None)
            self._release_pages(v.seq_id)
            v._tier_key = key
            v._tier_tokens = n_tokens
            v.status = "paused"
            # a fresh seq_id at resume: the old id may still have a
            # deferred page release in flight (same rule as _evict)
            v.seq_id = None
            self._requeue.append(v)
            self._m["paused"].inc()
            self._m["degraded"].labels("pause").inc()

    def _degrade_pause(self, req):
        """Ladder rung between cache-reclaim and trim (requires the
        host tier): pause the lowest-priority victim — frees its batch
        slot and pages WITHOUT destroying its work. Returns True when
        a victim left the live set (even if its export failed and the
        pause degraded to an evict: capacity was freed either way)."""
        if self.tier is None:
            return False
        with self._lock:
            victims = [r for r in self._live.values()
                       if not r.done and r.priority < req.priority]
            if not victims:
                return False
            v = min(victims,
                    key=lambda r: (r.priority, len(r.output_ids)))
            self._pause(v)
        return True

    def _tier_discard(self, req):
        """Free a parked request's host-tier copy (a cancel, deadline
        expiry, or drain ended its pause). Idempotent — racing a
        resume that already consumed the entry is a no-op."""
        key = req._tier_key
        if key is None or self.tier is None:
            return
        req._tier_key = None
        req._tier_tokens = 0
        self.tier.free(key)

    def _try_resume(self, req):
        """Resume one paused request at a boundary: fresh exclusively
        owned pages via :meth:`PageAllocator.import_table`, H2D
        restore (CRC-verified per page) into them, rejoin the live set
        with output/prefill progress intact — the remaining tokens are
        bitwise what an uninterrupted run produces. Returns False when
        capacity is short: the request is re-parked at the FRONT and
        the pump stops for this boundary. A failed or torn restore
        falls back to the evict→requeue path (host copy freed,
        from-scratch retry against the retry budget) — typed, never
        wedged, never leaked."""
        with self._lock:
            if req._cancel_requested and not req.done:
                req.done = True
                req.status = "cancelled"
                self._m["cancelled"].inc()
            if req.done:
                self._tier_discard(req)
                return True
            expired = (req._expires_at is not None
                       and time.perf_counter() >= req._expires_at)
        if expired:
            self._expire(req)
            self._tier_discard(req)
            return True
        with self._lock:
            if len(self._live) >= self.max_batch:
                self._requeue.appendleft(req)
                return False
            sid = self._next_id
            try:
                self.alloc.import_table(sid, req._tier_tokens)
            except MemoryError:
                self._requeue.appendleft(req)
                return False
            self._next_id += 1
            table = list(self.alloc._tables[sid])
            try:
                (self.k_pools, self.v_pools, self.k_scales,
                 self.v_scales) = self.tier.restore_seq(
                    req._tier_key, self.k_pools, self.v_pools,
                    self.k_scales, self.v_scales, table,
                    step=self._dispatch_count)
            except TierError:
                # the pre-tier behavior: fresh pages back to the pool,
                # from-scratch retry (or a typed terminal failure)
                self._release_pages(sid)
                req._tier_key = None
                req._tier_tokens = 0
                self._requeue_or_fail(req)
                return True
            req._tier_key = None
            req._tier_tokens = 0
            req.seq_id = sid
            req.status = "live"
            self._live[sid] = req
            self._m["resumed"].inc()
        return True

    def _demote_prefix_page(self, key, parent, page):
        """Prefix-cache evict hook: D2H-copy ONE cold cached page into
        the host tier before its last reference drops, so a hot system
        prompt survives pool pressure without re-prefill. Raises
        :class:`TierError` on a failed copy — the cache swallows it
        (demotion is best-effort; the old behavior IS dropping the
        page)."""
        self.tier.put_prefix(
            key.hex(), parent.hex() if parent is not None else None,
            self.k_pools, self.v_pools, self.k_scales, self.v_scales,
            page, step=self._dispatch_count)

    def _promote_prefix(self, prompt_ids, n_tokens):
        """Host-tier prefix promotion: extend this prompt's in-HBM
        cached chain with demoted pages the host tier still holds.
        Best-effort — promotion only spends SURPLUS pages (the
        admission's own page need plus one stays untouched) and any
        tier failure just leaves the cold path (the chain re-prefills).
        Caller holds the engine lock."""
        tier = self.tier
        if tier is None or self.prefix is None:
            return
        from .prefix_cache import chain_keys
        keys = chain_keys(prompt_ids, self.page_size)
        if not keys:
            return
        cached_pages, _ = self.prefix.match(prompt_ids, record=False)
        j = len(cached_pages)
        need = max(1, math.ceil(n_tokens / self.page_size))
        while j < len(keys):
            key = keys[j]
            if not tier.has_prefix(key.hex()):
                break
            if self.alloc.free_pages <= need + 1:
                break
            try:
                page = self.alloc.take_pages(1)[0]
            except MemoryError:
                break
            try:
                (self.k_pools, self.v_pools, self.k_scales,
                 self.v_scales) = tier.restore_prefix(
                    key.hex(), self.k_pools, self.v_pools,
                    self.k_scales, self.v_scales, page,
                    step=self._dispatch_count)
            except TierError:
                self.alloc.decref(page)
                break
            if not self.prefix.pin(key, page, parent=keys[j - 1]
                                   if j > 0 else None, depth=j):
                # someone re-cached this link meanwhile: give the
                # promoted page back (the cached one wins)
                self.alloc.decref(page)
            j += 1

    def _relieve_pressure(self, live, n):
        """Decode-boundary rung of the degradation ladder: when the
        pool cannot hold the next ``n`` tokens for every live sequence,
        pause (host tier on) or evict the lowest-priority (then
        least-progressed) victim until the rest fit — shed or degrade,
        never crash mid-step with a torn allocator. Returns the
        surviving live list. Caller holds the engine lock."""
        page = self.page_size
        live = list(live)
        # a sequence about to cross its per-seq table cap can NEVER
        # take this step, and a retry would deterministically hit the
        # same wall — trim it (retire with the output it produced,
        # ``trimmed=True``) rather than burn its retry budget on full
        # regenerations or let alloc.extend raise mid-loop
        for r in list(live):
            need_pages = -(-(self.alloc._lens[r.seq_id] + n) // page)
            if need_pages > self.alloc.max_pages_per_seq:
                live.remove(r)
                self._trim(r)
        # while another thread is mid-entry, victim releases would be
        # DEFERRED — evicting could not free a single page, so victims
        # are merely POSTPONED from this dispatch (no state change;
        # they rejoin at the next boundary, after the flush)
        me = threading.current_thread()
        deferrals_blocked = self._in_dispatch \
            or any(t is not me for t in self._entry_threads)
        while live:
            need = sum(
                max(0, -(-(self.alloc._lens[r.seq_id] + n) // page)
                    - len(self.alloc._tables[r.seq_id]))
                for r in live)
            if need <= self.alloc.free_pages:
                break
            # cold prefix-cache pages go back to the pool BEFORE any
            # live request is destroyed — same contract as admission
            if self.prefix is not None and self.prefix.pages \
                    and self.prefix.evict_pages(
                        need - self.alloc.free_pages):
                continue
            v = min(live, key=lambda r: (r.priority, len(r.output_ids)))
            live.remove(v)
            if not deferrals_blocked:
                if self.tier is not None:
                    self._pause(v)
                else:
                    self._evict(v)
            else:
                # POSTPONE: no state change — the row sits this
                # dispatch out and rejoins at the next boundary
                self._m["postponed"].inc()
        return live

    def _pump_requeue(self):
        """Continuous-batching re-admission at step boundaries:
        requests the ladder parked on the requeue rejoin the batch as
        capacity allows, so plain ``add_request()`` + ``step()``
        drivers (no :meth:`generate` loop) never strand an evicted
        request in limbo. Re-admitted prompts prefill as ordinary
        chunks of the very next mixed dispatch — no separate wave."""
        while True:
            with self._lock:
                if self._draining or not self._requeue \
                        or len(self._live) >= self.max_batch:
                    break
                nxt = self._requeue.popleft()
            if nxt.done:
                self._tier_discard(nxt)
                continue
            if nxt._tier_key is not None:
                # paused: resume is an H2D restore into fresh pages,
                # not a re-admission — no prefill, no ladder walk
                if not self._try_resume(nxt):
                    break
                continue
            try:
                # quiet probe: no backoff sleeps inside the dispatch
                # lock (the pump retries at the next boundary anyway)
                # and a re-park is not a shed for the metrics
                self._admit_locked(nxt, quiet_retry=True)
            except AdmissionError:
                with self._lock:
                    self._requeue.appendleft(nxt)
                break
        # hint the tier at the NEXT resume candidate so its CRC verify
        # + device put overlap the coming decode dispatches
        if self.tier is not None:
            with self._lock:
                head = next((r for r in self._requeue
                             if not r.done and r._tier_key is not None),
                            None)
            if head is not None:
                self.tier.stage(head._tier_key)

    def _admit(self, req):
        """Admit one request, walking the degradation ladder under
        pressure: trim -> evict -> (bounded backoff) -> shed with a
        ``retry_after`` hint. Raises :class:`ValueError` for requests
        that can NEVER fit (prompt longer than the pool) and
        :class:`AdmissionError` for transient pressure."""
        with self._entry():
            return self._admit_locked(req)

    def _admit_locked(self, req, quiet_retry=False):
        with self._lock:
            if req._cancel_requested and not req.done:
                # a client abandon raced an eviction/re-admission:
                # honor it here instead of decoding for nobody
                req.done = True
                req.status = "cancelled"
                self._m["cancelled"].inc()
                return req.seq_id
        if req.done:
            return req.seq_id
        self._validate(req)
        self._expire_deadlines()      # expired requests free capacity
        with self._lock:
            if req.seq_id is None:
                req.seq_id = self._next_id
                self._next_id += 1
            if req._seed is None:
                sp = req.sampling
                if sp is not None and sp.seed is not None:
                    req._seed = sp.seed
                else:
                    # auto-seed once per request (stable across ladder
                    # evictions/re-admissions so a regenerated request
                    # redraws the same sequence) and record it for
                    # after-the-fact reproducibility
                    self._auto_seed = (self._auto_seed * 1103515245
                                       + 12345) % (2 ** 31)
                    req._seed = self._auto_seed
        attempt = 0
        trim_tried: set[int] = set()
        while True:
            reason = self._try_reserve(req)
            if reason is None:
                break
            # while a dispatch is in flight — or any other thread is
            # mid-entry — victim page releases are DEFERRED, so
            # trimming/evicting cannot free pages yet and destroying
            # lower-priority work would gain nothing; fall through to
            # backoff (which can observe the post-entry flush) or shed
            me = threading.current_thread()
            with self._lock:
                pages_blocked = (
                    reason == "KV page pool exhausted"
                    and (self._in_dispatch
                         or any(t is not me
                                for t in self._entry_threads)))
            if reason != "draining" and not pages_blocked:
                # rung order: cache-reclaim (inside _try_reserve) →
                # pause → trim → evict → backoff → shed
                if self._degrade_pause(req):
                    continue
                if self._degrade_trim(req, trim_tried):
                    continue
                if self._degrade_evict(req):
                    continue
            if reason != "draining":
                if not quiet_retry and attempt < self.admit_retries:
                    # bounded backoff: a concurrent step()/scan may
                    # retire a request and release its pages before the
                    # retry
                    attempt += 1
                    self._m["admit_retries"].inc()
                    time.sleep(self.admit_backoff * (2 ** (attempt - 1)))
                    continue
                if not quiet_retry:
                    # drain gating and the requeue pump's boundary
                    # probes are not capacity pressure: only real
                    # pressure rejections feed the evicted/shed metrics
                    self._m["evicted"].inc()
                    self._m["degraded"].labels("shed").inc()
            raise AdmissionError(
                reason, live=len(self._live),
                max_batch=self.max_batch,
                free_pages=self.alloc.free_pages,
                num_pages=self.alloc.num_pages, retries=attempt,
                retry_after=self._retry_after())
        # _try_reserve already made the request live; stamp the clocks
        now = time.perf_counter()
        with self._lock:
            req._t_admit = now
            if req._t_submit is None:
                req._t_submit = now
            if req._t_first_admit is None:
                req._t_first_admit = now
                self._m["queue_wait"].observe(now - req._t_submit)
            ttl = None
            if req.deadline is not None:
                ttl = req.deadline
            if req.token_budget is not None:
                budget = req.token_budget * req.max_new_tokens
                ttl = budget if ttl is None else min(ttl, budget)
            req._expires_at = None if ttl is None else now + ttl
        self._m["admitted"].inc()
        # prefill_tokens counts per APPLIED chunk in _dispatch_rows —
        # under chunked prefill, admission no longer implies the work
        self._set_pool_gauges()
        return req.seq_id

    def add_request(self, req):
        """Admit a request and drive the chunked prefill through to its
        first emitted token (the admission-prefills-immediately
        contract; live decodes ride along in the same mixed dispatches,
        chunk by chunk). Returns its seq_id."""
        sid = self._admit(req)
        while not req.done and req._prefilled < len(req.prompt_ids):
            if self.step() == 0:
                break       # nothing dispatchable (drained/expired)
        return sid

    def _emit(self, req, token):
        first = not req.output_ids
        if first and req._t_admit is not None:
            now = time.perf_counter()
            ttft = now - req._t_admit
            self._m["ttft"].observe(ttft)
            if req._t_first_token is None:
                req._t_first_token = now
                # a zero-width marker: where the first token landed, on
                # which pid, after which waits (a request that has not
                # retired yet has no `serving.request`); a node of the
                # request's distributed trace where it has one
                with _tracing.activate(getattr(req, "_trace", None)), \
                        _span("serving.first_token",
                              ttft_seconds=round(ttft, 6),
                              seq_id=req.seq_id, t_submit=req._t_submit,
                              t_admit=req._t_first_admit,
                              t_first_chunk=req._t_first_chunk,
                              t_first_token=now):
                    pass
        # stop tokens are checked BEFORE the append: the request
        # retires ``completed`` with the stop token excluded from its
        # output (the chat-endpoint contract; eos keeps its legacy
        # include-then-stop behavior)
        if req.stop_set and token in req.stop_set:
            if self._retire(req, "completed"):
                self._m["completed"].inc()
                self._m["stop_hits"].inc()
            return
        req.output_ids.append(token)
        self._m["generated"].inc()
        cb = req.on_token
        if cb is not None:
            try:
                cb(req, token)
            except Exception:
                pass        # streaming hooks must never kill a dispatch
        if (req.eos_token_id is not None and token == req.eos_token_id) \
                or len(req.output_ids) >= req.max_new_tokens:
            if self._retire(req, "completed"):
                self._m["completed"].inc()
        # pool gauges are refreshed once per dispatch by the
        # caller, not per emitted token — only the post-loop value is
        # observable anyway

    def step(self):
        """Advance the engine by ONE mixed dispatch, launched and
        finished: every live fully-prefilled sequence decodes one token
        and pending prompt chunks pack into the remaining
        ``chunk_budget``; when it returns, the dispatch's tokens are on
        the requests and nothing is in flight (a dispatch
        :meth:`step_ahead` left in flight is finished first). Returns
        the number of rows dispatched (0 = nothing live)."""
        return self._mixed_step()[0]

    def step_ahead(self):
        """One turn of a continuous loop, ONE DISPATCH AHEAD: plan,
        build and enqueue the next dispatch, THEN wait for the tokens
        of the one that was in flight and apply them. The device finds
        its next program queued when it ends the last, and the host's
        turn runs beside the device's step and not between two of them.
        At most one dispatch is ever in flight: its successor is planned
        from what it will do whatever its tokens turn out to be
        (:meth:`_schedule_rows`), and takes them on the device
        (`DispatchLayout`'s ``prev_idx``).

        Where the next plan needs the host's view of the last token the
        turn is :meth:`step`'s (:meth:`_runs_ahead`). Returns the rows
        it launched plus the rows it finished: 0 says that nothing is
        live and nothing in flight. Callers that stop turning while the
        return is not 0 leave a dispatch in flight; :meth:`step`,
        :meth:`decode_many`, :meth:`drain` and :meth:`close` finish
        it."""
        return self._mixed_step(ahead=True)[0]

    def _runs_ahead(self):
        """May the next dispatch be launched before the last one's
        tokens are on the host (caller holds the dispatch locks)? Not
        where planning or building it reads them: a speculative engine
        drafts from ``output_ids``, a constraint hook is handed them; a
        host-tier engine pauses and resumes sequences by their applied
        lengths; and a program shape's first dispatch compiles, which
        the tokens in flight should not wait for."""
        if self.spec_k or self.tier is not None:
            return False
        with self._lock:
            live = [r for r in self._live.values() if not r.done]
        if any(r.sampling is not None and r.sampling.constraint is not None
               for r in live):
            return False
        # the shape the plan will take: chunk rows where a prompt has
        # tokens left once the dispatch in flight lands
        landing = self._landing(self._inflight)
        mixed = any(landing.get(r.seq_id, (r._prefilled,))[0]
                    < len(r.prompt_ids) for r in live)
        return ("mixed", self.chunk_budget if mixed else self.max_batch) \
            in self._warmed_keys

    @_fatal_guard("serving.step")
    def _mixed_step(self, ahead=False):
        """One mixed dispatch launched (see :meth:`_turn`); with
        ``ahead`` false also finished, the one in flight before it.
        Returns (rows launched and rows finished, tokens emitted) — a
        dispatch that only advanced mid-prompt chunks reports rows > 0
        with emitted == 0."""
        rows = emitted = 0
        try:
            again = True
            while again:
                n, e, again = self._turn(ahead)
                rows, emitted = rows + n, emitted + e
        except BaseException:
            # nothing stays in flight behind a fault: the tokens it
            # already computed reach their requests (the plan that
            # raised fired before it touched the allocator)
            with contextlib.suppress(Exception):
                self._settle()
            raise
        return rows, emitted

    def _settle(self):
        """Finish the dispatch in flight, if there is one."""
        while self._inflight is not None:
            self._turn(ahead=False, launch=False)

    def _turn(self, ahead, launch=True):
        """One turn of the engine under the dispatch locks: plan, build
        and enqueue a dispatch (``launch``), and finish one (wait for
        its tokens, apply them): the dispatch that was in flight, or,
        with none in flight and ``ahead`` false, the one just launched.
        With one in flight and no leave to run ahead of it
        (:meth:`_runs_ahead`) the turn finishes it and launches nothing.
        Returns ``(rows launched plus rows finished, tokens emitted,
        whether the caller's launch is still to come)``.

        One `serving.dispatch` span a turn that finishes a dispatch,
        which says everything of THAT dispatch (``step``, its rows and
        tokens as launched, ``ahead``: 1 where it was enqueued while its
        predecessor was unapplied, ``dev_tokens``: input tokens it took
        from the predecessor's output on the device, ``stale_rows``:
        rows dropped at apply); the four phases carry the ``step`` of
        the dispatch they work for, so a turn ahead holds the
        ``serving.schedule`` and ``serving.build`` of dispatch n+1 and
        then the ``serving.wait`` and ``serving.apply`` of dispatch n. A
        turn that finishes nothing (an idle one, the first of a run
        ahead) records no `serving.dispatch`."""
        with _span("serving.dispatch") as disp, \
                contextlib.ExitStack() as locks:
            rows, launched = [], None
            with _span("serving.schedule") as sched:
                step = self._enter_dispatch(locks, sched)
                prev = self._inflight
                ahead = ahead and self._runs_ahead()
                # synchronous with one in flight: that one first
                finish_first = prev is not None and not ahead
                if launch and not finish_first:
                    rows, cow = self._plan_rows(prev)
                if not rows:
                    sched.cancel()
            if rows:
                launched = self._inflight = self._launch(
                    step, rows, cow, ahead=prev is not None)
            if prev is not None:
                fin, self._inflight = prev, launched
            elif launched is not None and not ahead:
                fin, self._inflight = launched, None
            else:
                # an idle turn, or the first of a run ahead
                disp.cancel()
                return len(rows), 0, False
            emitted = self._finish(fin, disp)
            return (len(rows) + (len(prev.rows) if prev is not None else 0),
                    emitted, launch and finish_first)

    def _launch(self, step, rows, cow, ahead):
        """Build and enqueue the planned ``rows`` as dispatch ``step``
        under its `serving.build` span, start the copy of its outputs
        to the host, and say what was launched (:class:`_Launched`)."""
        with _span("serving.build", step=step) as build:
            try:
                (nxt, flat_start, dur, cold, needs_mixed, t_cap, nbytes,
                 sampled, tile_rows) = self._dispatch_rows(rows, cow)
            except BaseException:
                # as if the rows had never been planned: a decode row's
                # extend is the plan's one change to the allocator
                with self._lock:
                    for r, sid, _, n, _, is_dec in rows:
                        if is_dec and sid in self.alloc._lens:
                            self.alloc.rollback(sid, n)
                raise
            # what the host handed the program: one staged buffer
            build.set(h2d_arrays=1, h2d_bytes=nbytes)
        layer_stats = self._layer_stats
        # the tokens (and the expert layers' counters beside them) set
        # out for the host as soon as the program has them
        for a in (nxt, layer_stats):
            if a is not None:
                a._data.copy_to_host_async()
        tokens = sum(row[3] for row in rows)
        prefill = sum(row[3] for row in rows if not row[5])
        # what the attention kernel walks (the pages the rows'
        # contexts hold) against the slots of the tables it is given
        r_cap = self.rows_cap if needs_mixed else self.max_batch
        said = dict(rows=len(rows),
                    decode_rows=sum(1 for row in rows if row[5]),
                    prefill_tokens=prefill, tokens=tokens, t_cap=t_cap,
                    kind="mixed" if needs_mixed else "decode",
                    sampled_rows=sampled, ahead=int(ahead),
                    dev_tokens=sum(1 for row in rows if row[4][0] < 0),
                    kv_pages=self._kv_pages(row[2] + row[3]
                                            for row in rows),
                    table_slots=r_cap * self.width)
        if tile_rows is not None:
            said["tile_rows"] = tile_rows
        if self._slotted:
            said.update(self._slot_counters(rows))
        return _Launched(step, rows, nxt, layer_stats, flat_start, dur,
                         cold, needs_mixed, said)

    def _finish(self, fin, disp):
        """Wait for the tokens of the launched dispatch ``fin`` and
        apply them, under its `serving.wait` and `serving.apply` spans;
        ``disp``, the turn's `serving.dispatch`, says what ``fin`` was.
        Returns tokens emitted."""
        with _span("serving.wait", step=fin.step):
            out = np.asarray(fin.nxt._data).reshape(-1)  # [_carry_len]
            # the expert layers' counters came with the tokens
            layer_stats = None if fin.layer_stats is None \
                else np.asarray(fin.layer_stats._data)
        with _span("serving.apply", step=fin.step) as applied:
            emitted, stale = self._apply_rows(
                fin.rows, out, fin.flat_start, fin.dur, fin.cold,
                fin.needs_mixed)
            self._expire_deadlines()
            self._set_pool_gauges()
            applied.set(emitted=emitted)
        said = fin.said
        disp.set(step=fin.step, stale_rows=stale, **said)
        if layer_stats is not None:
            # per expert layer [experts that got a row, rows of the
            # largest group]: the medians over the layers; and the
            # latent rows the dispatch's contexts hold
            med = np.median(layer_stats.reshape(-1, 2), axis=0)
            disp.set(experts_touched=float(med[0]),
                     expert_rows_max=float(med[1]),
                     latent_rows=sum(row[2] + row[3] for row in fin.rows))
        self._count_dispatch(said["kind"], said["prefill_tokens"],
                             said["tokens"] - said["prefill_tokens"],
                             said["t_cap"] - said["tokens"])
        return emitted

    def _enter_dispatch(self, locks, sched):
        """Take the dispatch locks (held until ``locks`` closes) under
        the open ``serving.schedule`` span and give it the ``step`` of
        the dispatch a plan made now would be, which it returns."""
        t_lock = time.perf_counter()
        locks.enter_context(self._entry())
        locks.enter_context(self._dispatch_lock)
        locks.enter_context(_CROSS_ENGINE_LOCK)
        step = self._dispatch_count
        sched.set(step=step, lock_wait_s=time.perf_counter() - t_lock)
        return step

    def _plan_rows(self, prev=None):
        """The scheduling half of a mixed step (dispatch locks held):
        expire, pump the requeue, count the dispatch, schedule its rows
        (behind ``prev``, the dispatch in flight, where there is one).
        Returns ``(rows, cow)``; no rows means nothing to dispatch."""
        self._expire_deadlines()
        self._pump_requeue()
        with self._lock:
            if not any(not r.done for r in self._live.values()):
                return [], []
        # before any allocator mutation: an injected raise aborts
        # the dispatch cleanly instead of leaving lens advanced
        # with no K/V written
        _faults.fire("serve.decode", step=self._dispatch_count)
        self._dispatch_count += 1
        with self._lock:
            # rows are snapshotted under the lock: a concurrent
            # cancel/evict may null seq_id or swap output_ids
            # mid-setup, but this dispatch keeps reading its own
            # consistent view (the pages stay reserved —
            # cross-thread releases defer past _entry); the decode
            # extends happen while still holding the lock, so a
            # concurrent admission can't consume the pages between
            # _relieve_pressure's proof and the extend
            return self._schedule_rows(prev)

    def _slot_counters(self, rows):
        """What a model that keeps states and windows holds after a
        dispatch of ``rows``: sequences with a slot, pages of the whole-
        context pools, ring pages all window layers hold live keys in
        (a context of ``n`` tokens and a window ``w``: the pages of
        positions ``max(n - w, 0) .. n - 1``), those that fell behind a
        window in this dispatch (free to be overwritten), and the bytes
        of slot states the dispatch's rows moved (in and out)."""
        page = self.page_size
        with self._lock:
            lens = [n for n in self.alloc._lens.values() if n > 0]
        held = freed = 0
        for w, layers in self._windows.items():
            held += layers * sum((n - 1) // page - max(n - w, 0) // page
                                 + 1 for n in lens)
            freed += layers * sum(max(start + n - w, 0) // page
                                  - max(start - w, 0) // page
                                  for _, _, start, n, _, _ in rows)
        # (no prefix cache holds pages of such a model)
        shared = self.alloc.num_pages - self.alloc.free_pages
        # every row reads its slot's states and writes them back
        return dict(state_slots=self.alloc.slots_held,
                    shared_kv_pages=shared, window_pages=held,
                    window_pages_freed=freed,
                    state_bytes=2 * len(rows) * self._slot_bytes)

    def _kv_pages(self, kv_lens):
        """Pages that hold contexts of these lengths, summed."""
        return sum(-(-n // self.page_size) for n in kv_lens)

    def _count_dispatch(self, kind, prefill, decode, pad):
        """Count one dispatched program and what filled its slots."""
        self._m["dispatches"].labels(kind).inc()
        for what, n in (("prefill", prefill), ("decode", decode),
                        ("pad", pad)):
            if n:
                self._m["dispatch_tokens"].labels(what).inc(n)

    # ------------------------------------------------------------------
    # decode scan: n all-decode ticks = ONE compiled program (lax.scan)
    # ------------------------------------------------------------------
    def _decode_scan_fn(self, n):
        """Build the n-tick decode scan: ``lax.scan`` whose body is the
        SAME Tensor-level :meth:`_mixed_forward` specialized to the
        decode-only shape (T == R == max_batch, QB == 1) — parity with
        the per-step program is by construction, and the dispatch path
        stays singular. The carry is (tokens, lens, pools); tables are
        scan-invariant because pages for the whole run are reserved
        before launch; per-tick write positions derive from the length
        carry on device."""
        import jax

        def fn(tokens, tables, lens, temps, top_ps, top_ks, seeds,
               slot_ids, slot_vals, cmodes, k_pools, v_pools, k_scales,
               v_scales):
            tab = tables._data
            b = tab.shape[0]
            kp = [x._data for x in k_pools]
            vp = [x._data for x in v_pools]
            ksp = [x._data for x in k_scales]
            vsp = [x._data for x in v_scales]
            rows = jnp.arange(b, dtype=jnp.int32)
            ones = jnp.ones((b,), jnp.int32)
            # sampler params are scan-invariant per row; the fold
            # position advances with the length carry, so tick i of a
            # scan draws the SAME randomness the per-step path would
            samp = (temps, top_ps, top_ks, seeds, slot_ids, slot_vals,
                    cmodes)

            def body(carry, _):
                tok, lc, kc, vc, ksc, vsc = carry
                start = (lc - 1).astype(jnp.int32)
                nxt, nk, nv, nks, nvs, _ = self._mixed_forward(
                    Tensor(tok.reshape(1, b)),
                    Tensor(start.reshape(1, b)),
                    Tensor(rows), Tensor(rows), Tensor(tab),
                    Tensor(lc.astype(jnp.int32)), Tensor(start),
                    Tensor(ones),
                    # fused-write metadata for a decode tick: each row
                    # writes exactly its own one token, so the write
                    # span starts at the token's position, its packed
                    # index is the row index, and every row is its
                    # sequence's last (w_end == kv_len)
                    Tensor(start), Tensor(rows),
                    Tensor(lc.astype(jnp.int32)), *samp,
                    [Tensor(a) for a in kc], [Tensor(a) for a in vc],
                    [Tensor(a) for a in ksc], [Tensor(a) for a in vsc])
                nxt_arr = nxt._data.reshape(tok.shape).astype(tok.dtype)
                return ((nxt_arr, lc + 1,
                         [x._data for x in nk], [x._data for x in nv],
                         [x._data for x in nks], [x._data for x in nvs]),
                        nxt_arr[:, 0])

            (_, _, kf, vf, ksf, vsf), toks = jax.lax.scan(
                body, (tokens._data, lens._data, kp, vp, ksp, vsp),
                None, length=n)
            return (jnp.swapaxes(toks, 0, 1), *kf, *vf, *ksf, *vsf)

        return fn

    def _ensure_scan_compiled(self, n):
        if self._slotted:
            raise UnsupportedServingFeature(
                "the decode scan does not carry sequence slots: a model "
                "whose layers keep a state or a window decodes by "
                "single steps")
        sf = self._scan_static.get(n)
        if sf is None:
            from ..jit import StaticFunction

            # donate=False for the same reason as the mixed step: model
            # state is pass-through here, and donating same-aval weight
            # slots lets XLA alias them across each other
            sf = StaticFunction(self._decode_scan_fn(n),
                                state=[self.model], warmup="once",
                                donate=False, donate_inputs=True,
                                name=f"serving.mixed_scan[{n}]")
            # no lazy state to materialize (params exist; no optimizer):
            # skip the eager warmup — n scanned steps of per-op dispatch
            # would cost more than the compile it avoids
            sf._warmed_any = True
            self._scan_static[n] = sf
            self._record_shape("scan", n)
        return sf

    @_fatal_guard("serving.decode_scan")
    def _decode_scan(self, n):
        """Decode ``n`` tokens for every live (fully-prefilled) request
        in one dispatch. Pages for all n tokens are reserved up front;
        requests that retire mid-scan (EOS / max_new_tokens / expired
        deadline) have their tail tokens discarded at emit time —
        bounded waste, no correctness impact."""
        with self._dispatch_lock:
            # a scan starts from the tokens the host holds: the mixed
            # dispatch in flight is finished first, and none is
            # launched in between
            self._settle()
            return self._scan(n)

    def _scan(self, n):
        # the same spans as a mixed step, kind "scan"
        with _span("serving.dispatch", kind="scan") as disp, \
                contextlib.ExitStack() as locks:
            with _span("serving.schedule") as sched:
                step = self._enter_dispatch(locks, sched)
                disp.set(step=step)
                live, sids, last_tok, start_lens, cow = self._plan_scan(n)
                if not live:
                    sched.cancel()
                    disp.cancel()
                    return 0
            with _span("serving.build", step=step) as build:
                out, dur, cold, h2d, sampled = self._dispatch_scan(
                    n, live, sids, last_tok, start_lens, cow)
                # the scan still takes its arrays one by one
                build.set(h2d_arrays=len(h2d),
                          h2d_bytes=sum(a.nbytes for a in h2d))
            with _span("serving.wait", step=step):
                all_tokens = np.asarray(out[0]._data)        # one D2H
            with _span("serving.apply", step=step) as applied:
                # one scan tick serves every live row: per-token latency
                # is the dispatch wall time amortized over the n ticks
                if not cold:
                    tick = dur / n
                    self._token_times.append(tick)
                    for _ in range(n):
                        self._m["tpot"].observe(tick)
                served = 0
                for i, r in enumerate(live):
                    for t in range(n):
                        # done: retired mid-scan (EOS / budget); seq_id
                        # mismatch: evicted + requeued mid-dispatch — the
                        # stale tail must not land in its cleared output
                        if r.done or r.seq_id != sids[i]:
                            break
                        self._emit(r, int(all_tokens[i, t]))
                        served += 1
                self._expire_deadlines()
                self._set_pool_gauges()
                applied.set(emitted=served)
            tokens, t_cap = len(live) * n, self.max_batch * n
            # a scan's contexts grow a token a tick: its first tick's
            disp.set(rows=len(live), decode_rows=len(live),
                     prefill_tokens=0, tokens=tokens, t_cap=t_cap,
                     sampled_rows=sampled,
                     kv_pages=self._kv_pages(start_lens[sid] + 1
                                             for sid in sids),
                     table_slots=self.max_batch * self.width)
            self._count_dispatch("scan", 0, tokens, t_cap - tokens)
            return served

    def _plan_scan(self, n):
        """The scheduling half of a decode scan (dispatch locks held).
        Returns ``(live, sids, last_tok, start_lens, cow)``; no live
        rows means nothing to dispatch."""
        nothing = [], [], [], {}, []
        self._expire_deadlines()
        self._pump_requeue()
        with self._lock:
            if n <= 0 or not any(not r.done
                                 for r in self._live.values()):
                return nothing
        # as in step(): fire before any allocator mutation
        _faults.fire("serve.decode", step=self._dispatch_count)
        self._dispatch_count += 1
        with self._lock:
            live = [r for r in self._live.values() if not r.done
                    and r._prefilled >= len(r.prompt_ids)]
            live = self._relieve_pressure(live, n)
            sids = [r.seq_id for r in live]
            last_tok = [r.output_ids[-1] if r.output_ids
                        else int(r.prompt_ids[-1]) for r in live]
            # reserve the whole scan under the lock (see step())
            start_lens = {sid: self.alloc._lens[sid] for sid in sids}
            cow = []
            for sid in sids:
                self.alloc.extend(sid, n)
                # only the scan's FIRST write position can sit in
                # a pre-existing (possibly shared) page; the rest
                # land in pages this extend just allocated
                cp = self.alloc.ensure_writable(sid, start_lens[sid])
                if cp is not None:
                    cow.append(cp)
        return live, sids, last_tok, start_lens, cow

    def _dispatch_scan(self, n, live, sids, last_tok, start_lens, cow):
        """Build and enqueue the ``n``-tick scan over the planned rows.
        Returns ``(the program's outputs, enqueue seconds, cold, the
        arrays handed to the device, rows that sample)``."""
        for old, new in cow:
            self._copy_page(old, new)
        # as in step(): each new scan length compiles on its first
        # call — don't let that land n inflated samples in tpot
        key = ("scan", n)
        cold = key not in self._warmed_keys
        t0 = time.perf_counter()
        b = self.max_batch
        tables = np.full((b, self.width), self.trash_page, np.int32)
        lens = np.ones((b,), np.int32)
        tokens = np.zeros((b, 1), np.int64)
        for i, sid in enumerate(sids):
            t = self.alloc._tables[sid]
            tables[i, :len(t)] = t
            lens[i] = start_lens[sid] + 1       # first new token incl.
            tokens[i, 0] = last_tok[i]
        samp = self._sample_arrays(live, b)
        sampled = int(np.count_nonzero(samp[0] > 0))        # temps
        h2d = [jnp.asarray(a) for a in (tokens, tables, lens, *samp)]
        sf = self._ensure_scan_compiled(n)
        self._arm_watchdog(cold)
        with self._lock:
            self._in_dispatch = True
        try:
            with no_grad(), _span("serving.decode_scan",
                                  live=len(live), ticks=n):
                out = sf(*[Tensor(a) for a in h2d],
                         self.k_pools, self.v_pools,
                    self.k_scales, self.v_scales)
        finally:
            with self._lock:
                self._in_dispatch = False
            dur = time.perf_counter() - t0
            self._disarm_watchdog(dur, cold=cold)
            self._warmed_keys.add(key)
        self._flush_deferred()
        self._adopt_scan_pools(out)
        return out, dur, cold, h2d, sampled

    def _scan_fits(self, live, n):
        """Largest scan <= n whose page reservations fit the pool and
        no sequence's per-seq table cap."""
        page = self.page_size
        for r in live:
            headroom = self.alloc.max_pages_per_seq * page \
                - self.alloc._lens[r.seq_id]
            if headroom < n:
                # shrink to the tightest per-seq headroom; a fully
                # capped sequence (headroom <= 0) is trimmed at the
                # next step boundary by _relieve_pressure
                n = max(1, headroom)
        while n > 1:
            need = sum(
                max(0, -(-(self.alloc._lens[r.seq_id] + n) // page)
                    - len(self.alloc._tables[r.seq_id]))
                for r in live)
            if need <= self.alloc.free_pages:
                break
            n //= 2
        return n

    def decode_many(self, n, exact=True):
        """``n`` decode steps for the current live set. While any live
        prompt still has unprefilled chunks the engine takes single
        mixed steps (chunks + decodes together); once the batch is all
        decode it switches to compiled scans — full
        :attr:`decode_ticks` runs, then ticks/4 runs, then single
        steps. With ``exact=False`` the tail may overshoot by up to
        ticks/4 - 1 — callers use this when every live request retires
        by step ``n`` (the overshot ticks are discarded at emit time),
        trading a few idle ticks for never paying the per-step dispatch
        round trip. Returns tokens served."""
        served = 0
        small = max(self.decode_ticks // 4, 2)
        while n > 0:
            with self._lock:
                # _scan_fits reads the allocator's per-seq state: hold
                # the lock so a concurrent evict can't null a seq_id
                # between the snapshot and the fit computation
                live = [r for r in self._live.values() if not r.done]
                if not live and not self._requeue:
                    break
                prefilling = any(r._prefilled < len(r.prompt_ids)
                                 for r in live)
                # constraint hooks are per-step host work: a scan's n
                # on-device ticks can't re-consult them, so constrained
                # traffic pins the engine to single mixed steps (static
                # logit_bias is scan-invariant and scans fine)
                constrained = any(
                    r.sampling is not None
                    and r.sampling.constraint is not None for r in live)
                spec_now = False
                if self.spec_k and not prefilling and live:
                    spec_now = self._spec_worth(live)
                    # the probe result paces scan escalation below: a
                    # drafter with nothing to say should not hold the
                    # engine at short scans forever
                    self._spec_idle = 0 if spec_now \
                        else self._spec_idle + 1
                if not live:
                    chunk = 1       # pump parked requests via a step
                elif prefilling or constrained or self._slotted:
                    # (the scan does not carry the rows' slots: a model
                    # that keeps a state or a ring decodes step by step)
                    chunk = 1
                elif spec_now:
                    # speculation rides the mixed step: one dispatch
                    # verifies k+1 tokens per row, which is the scan's
                    # amortization and more — the fixed-tick scan would
                    # force every row back to one token per tick. When
                    # the drafter has NOTHING (cold history, no
                    # repetition), fall through to scans and re-probe
                    # at their boundaries: speculation must never cost
                    # more than not speculating.
                    chunk = 1
                elif n >= self.decode_ticks and (not self.spec_k
                                                 or self._spec_idle >= 2):
                    # a speculative engine starts with SHORT scans so a
                    # repetition onset is caught within ticks/4 tokens,
                    # but repeated empty probes escalate to full scans
                    # — non-draftable traffic converges to the plain
                    # engine's dispatch amortization (probes still run
                    # at every scan boundary, so speculation resumes at
                    # most one scan after the history turns repetitive)
                    chunk = self._scan_fits(live, self.decode_ticks)
                elif n >= small or not exact:
                    chunk = self._scan_fits(live, small)
                else:
                    chunk = 1
            if chunk > 1:
                served += self._decode_scan(chunk)
                n -= chunk
            else:
                rows, emitted = self._mixed_step()
                if rows == 0:
                    break
                served += emitted
                n -= 1
        return served

    @_fatal_guard("serving.generate")
    def generate(self, prompts, max_new_tokens=16, eos_token_id=None):
        """Convenience batch API: admit all prompts (continuous batching
        handles ragged finish times), run to completion, return output id
        lists in order. Every pending request that fits is admitted and
        its prompt chunks pack into the shared mixed dispatches.
        Requests the ladder re-queued are re-admitted ahead of new ones."""
        reqs = [Request(p, max_new_tokens, eos_token_id) for p in prompts]
        pending = list(reqs)
        while pending or any(not r.done for r in reqs):
            while True:
                with self._lock:
                    if len(self._live) >= self.max_batch:
                        break
                    # requeue pops race _pump_requeue in a second driver
                    # thread: decide AND pop under the lock
                    from_requeue = bool(self._requeue)
                    nxt = self._requeue.popleft() if from_requeue \
                        else (pending.pop(0) if pending else None)
                if nxt is None:
                    break
                if nxt.done:
                    continue
                try:
                    self._admit(nxt)
                except AdmissionError:
                    if from_requeue:
                        # still under pressure: park it again (keeps the
                        # typed-terminal contract — never strand a
                        # popped request in non-terminal 'requeued')
                        with self._lock:
                            self._requeue.appendleft(nxt)
                        break
                    raise
            with self._lock:
                live = [r for r in self._live.values() if not r.done]
                prefilling = any(r._prefilled < len(r.prompt_ids)
                                 for r in live)
            if live:
                if prefilling:
                    # mixed steps until every admitted prompt is in:
                    # prefill chunks and live decodes share dispatches,
                    # each launched while the one before it runs
                    self.step_ahead()
                    continue
                # scan until the earliest possible retirement; with EOS
                # or pending admissions cap at decode_ticks so a
                # retirement (and the admission it unblocks) is never
                # far away. The tail may overshoot (exact=False): every
                # live request retires by then, so overshot ticks are
                # discarded, never mis-emitted.
                run = min(r.max_new_tokens - len(r.output_ids)
                          for r in live)
                if pending or eos_token_id is not None:
                    run = min(run, self.decode_ticks)
                self.decode_many(max(1, run), exact=False)
                continue
            if not pending and all(r.done for r in reqs):
                break
        return [r.output_ids for r in reqs]

    # ------------------------------------------------------------------
    # graceful drain
    # ------------------------------------------------------------------
    @_fatal_guard("serving.drain")
    def drain(self, timeout=30.0):
        """Stop admission and retire the in-flight set: decode until
        every live request completes (EOS / max_new_tokens) or the
        grace ``timeout`` elapses, then expire the stragglers with a
        :class:`DeadlineExceeded` and release their pages. Admission
        stays closed afterwards (:class:`AdmissionError` reason
        ``"draining"``); call :meth:`resume_admission` to reopen.

        Returns ``{"seconds", "completed", "expired"}`` — requests that
        finished during the drain vs. those cut off at the window.
        """
        with self._lock:
            self._draining = True
            already = self._drain_active
            if not already:
                self._drain_active = True
        if already:
            # another thread's drain is mid-flight: wait it out within
            # our own budget rather than returning a misleading no-op
            # (a preemption exit riding on this return must not cut the
            # active drain's grace window short)
            t0 = time.perf_counter()
            while self._drain_active \
                    and time.perf_counter() - t0 < timeout:
                time.sleep(0.01)
            return {"seconds": time.perf_counter() - t0,
                    "completed": 0, "expired": 0}
        t0 = time.perf_counter()
        try:
            _faults.fire("serve.drain")
            with self._lock:
                start = [r for r in self._live.values() if not r.done]
            while True:
                self._expire_deadlines()
                with self._lock:
                    live = [r for r in self._live.values() if not r.done]
                if not live:
                    break
                if time.perf_counter() - t0 >= timeout:
                    for r in live:
                        self._expire(r, reason="drain grace window")
                    break
                self.step_ahead()
            # what the grace window cut off may have left a dispatch
            # (of stale rows) in flight
            self._settle()
            # admission is closed, so requests parked on the requeue
            # (evicted under decode-boundary pressure) can never run
            # again — expire them typed rather than stranding them
            with self._lock:
                requeued = list(self._requeue)
                self._requeue.clear()
            for r in requeued:
                if not r.done:
                    self._expire(r, reason="drain grace window")
                # paused requests drain typed AND leak-free: the host
                # copy goes with them
                self._tier_discard(r)
            # everything that was live at entry is terminal now
            dur = time.perf_counter() - t0
            self._m["drain_seconds"].set(dur)
            self._set_pool_gauges()
            completed = sum(1 for r in start if r.status == "completed")
            expired = sum(1 for r in start
                          if r.status == "deadline_exceeded")
            return {"seconds": dur, "completed": completed,
                    "expired": expired}
        finally:
            with self._lock:
                self._drain_active = False
                pending = self._pending_drain
                self._pending_drain = None
            if pending is not None:
                # a preemption signal arrived while this drain ran: the
                # work is done, exit now
                self._run_drain_and_exit(*pending)

    def resume_admission(self):
        """Reopen admission after a :meth:`drain` (test/maintenance
        hook; a preemption-driven drain exits the process instead)."""
        with self._lock:
            self._draining = False

    def is_ready(self):
        """Readiness (distinct from liveness): False while draining or
        closed, so a load balancer stops sending BEFORE :meth:`drain`
        finishes. Wire it to the ``ready=`` probe of
        :func:`paddle_tpu.observability.export.start_http_server` to
        expose it as ``/readyz``."""
        with self._lock:
            return not (self._draining or self._closed)

    def _run_drain_and_exit(self, grace, exit_code, on_drained):
        stats = self.drain(grace)
        if on_drained is not None:
            try:
                on_drained(stats)
            except Exception:
                pass        # exiting anyway; the drain itself succeeded
        os._exit(exit_code)

    def install_drain_handler(self, grace=30.0,
                              signals=(_signal.SIGTERM,), exit_code=0,
                              on_drained=None):
        """Hook preemption signals (default SIGTERM) for a graceful
        drain: admission stops immediately; in-flight requests finish
        or expire within ``grace`` seconds; then the process exits with
        ``exit_code`` (default 0 — a drained exit is a clean exit). A
        signal landing while a dispatch is in flight defers the drain
        to the next step/scan boundary, so engine state is never
        torn mid-update — mirroring the checkpoint callback's deferred
        emergency save. ``on_drained(stats)`` runs just before exit
        (e.g. to flush metrics).

        Must be called from the main thread (CPython signal rule).
        Returns ``{signum: previous_handler}`` so callers can restore.
        """
        prev = {}

        def _handler(signum, frame):
            with self._lock:
                self._draining = True
                if self._drain_active or self._entry_depth > 0 \
                        or self._flushing:
                    # a manual drain is running or an entry is in
                    # flight: record the exit request — drain's
                    # epilogue / the entry boundary executes it
                    self._pending_drain = (grace, exit_code, on_drained)
                    return
            self._run_drain_and_exit(grace, exit_code, on_drained)

        for s in signals:
            prev[s] = _signal.signal(s, _handler)
        return prev
