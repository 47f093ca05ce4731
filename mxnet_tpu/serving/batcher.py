"""The serving scheduler: concurrent generation requests -> fixed-shape
engine dispatches.

``ContinuousBatcher`` is the one scheduler: Orca-style ITERATION-LEVEL
scheduling (Yu et al., OSDI 2022) over a PAGED KV cache (Kwon et al.,
SOSP 2023). The decode batch is a static menu of ``slots``; each
iteration dispatches one jitted ``InferStep.decode_iter`` burst, then —
between dispatches — retires rows that hit EOS / their
``max_new_tokens`` / their deadline, frees their pages back to the pool,
and admits queued requests into the vacated slots through a jitted
prefill-into-pages dispatch. Slot count, page-table shape and pool shape
never change, so occupancy is dynamic while the program menu stays
exactly two entries per prompt bucket. Tokens stream per iteration
(``GenerationResult.tokens_iter``), and admission control rejects with
``Backpressure`` when the queue or the free-page watermark says the pool
can't absorb more work.

Telemetry (``infer/`` family): ``queue_wait_ms``/``ttft_ms`` per request,
``batch_occupancy``/``pages_in_use``/``page_fragmentation``/
``admitted_per_iter`` per iteration, ``prefill_ms``/
``decode_ms_per_token``/``tokens_per_sec`` per dispatch,
``requests``/``tokens``/``rejected_backpressure``/``preempted`` counters.

A request carries its own timeline (``GenerationResult``: instants on
``time.perf_counter``, the clock ``telemetry.phase`` reads, each stamped
where the thing happens), and everything said of a request is computed
from it in one place: ``GenerationResult.phases``, the ``infer/``
histograms above, the ``trace.*`` spans (at retire) and the window
histograms ``stats["h_*"]`` (``telemetry.metrics.BucketBlock``), which
hold the parts of a first token's latency and the scheduler's own passes
at a resolution a 95th percentile can be read from.
"""

from __future__ import annotations

import collections
import os
import queue
import threading
import time
from typing import Optional, Sequence

import numpy as _np

from ..base import MXNetError
from .. import telemetry as _tel
from ..telemetry import metrics as _metrics
from . import faults as _faults
from . import pages as _pages
from . import prefix as _prefix
from . import tracing as _tracing


# the parts of a first token's latency that follow one another (what
# ``GenerationResult.phases`` gives under ``<part>_ms``), and the window
# histograms in ``stats``: the four parts and their sum as the caller saw
# it, a working pass, a decode burst (dispatch to tokens on the host) and
# a prompt's chunk (dispatch to its token)
TTFT_PARTS = ("queue", "seat", "service", "deliver")
_PART_KEYS = tuple((f"h_{part}_ms", f"{part}_ms") for part in TTFT_PARTS)
_REQUEST_HISTS = tuple(h for h, _ in _PART_KEYS) + ("h_ttft_ms",
                                                     "h_chunk_ms")
_PASS_HISTS = ("h_pass_ms", "h_burst_ms")
HIST_KEYS = _REQUEST_HISTS + _PASS_HISTS
# entries of ``phases`` that lie INSIDE others (``seat_ms`` and
# ``service_ms`` split ``prefill_ms``, ``deliver_ms`` runs while the slot
# decodes): whoever adds phases up to an end-to-end latency leaves them out
PHASE_DETAIL = ("seat_ms", "service_ms", "deliver_ms")

__all__ = ["ContinuousBatcher", "GenerationResult", "PHASE_DETAIL",
           "DeadlineExceeded", "Backpressure", "batcher_slots",
           "iter_tokens_default", "spec_k_default", "spec_draft_enabled",
           "make_batcher"]


class DeadlineExceeded(MXNetError):
    """A request's deadline passed while it was still queued (or before
    the router could place it) — it is FAILED, never dispatched late."""


class Backpressure(MXNetError):
    """Admission control rejected the request at submit: the queue or the
    free-page watermark breached its threshold (``MXTPU_ADMIT_*``).
    Retriable — the router resubmits to a less-loaded replica."""


def batcher_slots(default: int = 8) -> int:
    """``MXTPU_BATCHER_SLOTS``: batch rows per dispatch."""
    v = os.environ.get("MXTPU_BATCHER_SLOTS", "").strip()
    try:
        return int(v) if v else default
    except ValueError:
        return default


def iter_tokens_default(default: int = 4) -> int:
    """``MXTPU_ITER_TOKENS``: decode tokens per scheduler iteration
    (dispatch granularity). 1 = pure per-token Orca scheduling (finest
    retirement/streaming granularity); larger bursts amortize dispatch
    overhead at the cost of up to ``iter_tokens - 1`` wasted steps per
    retiring row."""
    v = os.environ.get("MXTPU_ITER_TOKENS", "").strip()
    try:
        return max(int(v), 1) if v else default
    except ValueError:
        return default


def spec_k_default(default: int = 0) -> int:
    """``MXTPU_SPEC_K``: draft tokens proposed per speculative-decoding
    round. 0 (the default) disables speculation; a positive k makes the
    scheduler draft k tokens per live slot and verify them in ONE target
    dispatch (greedy output stays bit-identical to non-speculative)."""
    v = os.environ.get("MXTPU_SPEC_K", "").strip()
    try:
        return max(int(v), 0) if v else default
    except ValueError:
        return default


def spec_draft_enabled(default: bool = True) -> bool:
    """``MXTPU_SPEC_DRAFT``: master enable for the speculative-decoding
    draft path — ``0``/``false``/``off`` force-disables speculation even
    when a draft model is attached and ``MXTPU_SPEC_K`` is positive (the
    operator kill switch)."""
    v = os.environ.get("MXTPU_SPEC_DRAFT", "").strip().lower()
    if not v:
        return default
    return v not in ("0", "false", "off")


def _one_request(rows):
    """Args of an admission prefill's span: the request's identifier
    where the dispatch carries one request (``rows``: tuples whose second
    entry is the request), so that its prefill can be found on the
    profiler's timeline; None for a batch."""
    return {"request_id": rows[0][1].future.request_id} \
        if len(rows) == 1 else None


def make_batcher(engine, bucket_keys, **kwargs):
    """Build the serving scheduler over ``engine``: a
    ``ContinuousBatcher`` with every keyword argument handed on as it
    is."""
    return ContinuousBatcher(engine, bucket_keys, **kwargs)


class GenerationResult:
    """Future for one submitted request.

    ``result(timeout)`` blocks until the request finished and returns the
    full generated token list (trimmed at EOS); ``exception()`` surfaces
    a failure. ``tokens_iter(timeout)`` STREAMS instead: it yields token
    chunks as the scheduler emits them (one per decode iteration) and
    ends when the request resolves. ``weights_version`` tags the param
    set that served the request (hot weight swap: the version of its
    final iteration) and ``replica`` which
    engine replica ran it (router). ``drafts``, for a net that drafts
    the token after next itself, lists ``(j, token)``: what its module
    proposed for generated token ``j`` (kept only where it WAS token
    ``j``; the tokens are the model's own either way).

    **The timeline**: ``time.perf_counter`` instants, each stamped once,
    where the thing happens. ``enqueued_at`` (``submit``),
    ``admitted_at`` (the scheduler gave it a slot), ``first_chunk_at``
    (its first prefill dispatch: the first chunk of a prompt that enters
    its pages in chunks, else the admission prefill, where it equals
    ``admitted_at``), ``active_at`` (its first token is on the host),
    ``first_token_at`` (the first token is in the stream: TTFT is
    ``first_token_at - enqueued_at``), ``first_read_at`` (the CALLER's
    thread took the first chunk from ``tokens_iter()``, or ``result()``
    returned), ``finished_at`` (retired). ``phases`` is computed from
    them: ``queue_ms``, ``seat_ms`` (slot taken to first dispatch: the
    wait for the chunk seat), ``service_ms`` (first dispatch to first
    token: its chunks and the bursts between them), ``deliver_ms`` (first
    token to the caller's read) add up to ``first_read_at - enqueued_at``;
    ``prefill_ms`` is slot to first token (``seat_ms + service_ms``) and
    ``decode_ms`` first token to retirement. After a preemption the
    instants and parts are the LAST admission's (``first_token_at``
    alone keeps the first stream's), counted from ``requeued_at``, and
    ``preempt_ms`` is the time before it."""

    __slots__ = ("_event", "_tokens", "_error", "enqueued_at",
                 "queue_wait_ms", "weights_version", "replica",
                 "_cond", "_stream", "first_token_at",
                 "request_id", "phases", "drafts", "admitted_at",
                 "first_chunk_at", "active_at", "first_read_at",
                 "finished_at", "requeued_at")

    def __init__(self):
        self._event = threading.Event()
        self._tokens = None
        self._error = None
        self.enqueued_at = time.perf_counter()
        self.queue_wait_ms = None
        self.weights_version = None
        self.replica = None
        self._cond = threading.Condition()
        self._stream = []
        self.first_token_at = None
        self.admitted_at = self.first_chunk_at = self.active_at = None
        self.first_read_at = self.finished_at = self.requeued_at = None
        # fleet tracing/SLO attribution: the request id minted at the
        # router (or adopted from the RPC trace context) and the
        # per-phase latency breakdown — every ``*_ms`` entry names a
        # phase; the router adds ``other_ms`` so that those which follow
        # one another (all but ``PHASE_DETAIL``) sum to the observed
        # end-to-end latency exactly
        self.request_id = None
        self.phases = None
        self.drafts = None

    def _stream_tokens(self, tokens, at=None):
        """Append newly emitted tokens to the live stream (scheduler
        thread). First call stamps ``first_token_at`` (TTFT): ``at`` where
        the scheduler has just read the clock for ``active_at``."""
        if not tokens:
            return
        with self._cond:
            if self.first_token_at is None:
                self.first_token_at = at if at is not None \
                    else time.perf_counter()
            self._stream.extend(tokens)
            self._cond.notify_all()

    def _requeue(self, now):
        """Preemption (pool exhaustion): the request restarts from its
        prompt, so the stream restarts too, and the timeline from
        ``requeued_at``. ``result()`` is unaffected — only live
        ``tokens_iter`` consumers observe the re-emission."""
        with self._cond:
            self._stream = []
            self.requeued_at = now
            self.admitted_at = self.first_chunk_at = None
            self.active_at = self.first_read_at = None
            self._cond.notify_all()

    def parts_ms(self) -> dict:
        """``phases`` as far as the timeline's stamps give them (the
        class's docstring): the one place a request's intervals are
        taken."""
        since = self.enqueued_at if self.requeued_at is None \
            else self.requeued_at
        out = {"queue_ms": (self.admitted_at - since) * 1e3,
               "seat_ms": (self.first_chunk_at - self.admitted_at) * 1e3,
               "service_ms": (self.active_at - self.first_chunk_at) * 1e3,
               "prefill_ms": (self.active_at - self.admitted_at) * 1e3}
        if self.requeued_at is not None:
            out["preempt_ms"] = (self.requeued_at - self.enqueued_at) * 1e3
        if self.first_read_at is not None:
            out["deliver_ms"] = (self.first_read_at - self.active_at) * 1e3
        if self.finished_at is not None:
            out["decode_ms"] = (self.finished_at - self.active_at) * 1e3
        return out

    def _set_phases(self, adopted=False):
        """``phases`` anew from the timeline; a request whose prefill ran
        elsewhere (a handoff) stays marked ``adopted``."""
        parts = self.parts_ms()
        if adopted or (self.phases and "adopted" in self.phases):
            parts["adopted"] = True
        self.phases = parts
        return parts

    def _note_read(self):
        """The caller's thread takes its first chunk (under ``_cond``).
        A request that was retired before its caller read (a caller of
        ``result()`` alone) gets its ``deliver_ms`` here."""
        if self.first_read_at is None:
            self.first_read_at = time.perf_counter()
            if self.finished_at is not None:
                self._set_phases()

    def _resolve(self, tokens):
        """Resolve the future. Where the scheduler's retire has stamped
        ``finished_at``, ``phases`` is completed from the timeline in the
        same hold of ``_cond`` under which a caller stamps its first
        read, so one of the two writes ``deliver_ms``, whichever comes
        second. Returns ``phases`` as this call left them."""
        with self._cond:
            if self.finished_at is not None and self.active_at is not None:
                self._set_phases()
            phases = self.phases
            self._tokens = tokens
            if not self._stream and tokens:
                if self.first_token_at is None:
                    self.first_token_at = time.perf_counter()
                self._stream = list(tokens)
            self._event.set()
            self._cond.notify_all()
        return phases

    def _fail(self, err):
        with self._cond:
            self._error = err
            self._event.set()
            self._cond.notify_all()

    def done(self) -> bool:
        return self._event.is_set()

    def exception(self):
        return self._error

    def result(self, timeout: Optional[float] = None):
        if not self._event.wait(timeout):
            raise TimeoutError("generation result not ready")
        if self._error is not None:
            raise self._error
        if self.first_read_at is None:
            with self._cond:
                self._note_read()
        return self._tokens

    def tokens_iter(self, timeout: Optional[float] = None):
        """Yield generated-token chunks (lists) as they stream in; ends
        when the request resolves (raising its error if it failed).
        ``timeout`` bounds each wait for the NEXT chunk. After a pool
        preemption the stream restarts from the first token."""
        i = 0
        while True:
            with self._cond:
                if i > len(self._stream):
                    i = 0  # stream was reset by a preemption
                while len(self._stream) <= i and not self._event.is_set():
                    if not self._cond.wait(timeout):
                        raise TimeoutError("no token within timeout")
                chunk = list(self._stream[i:])
                done = self._event.is_set()
                if chunk and self.first_read_at is None:
                    self._note_read()
            if chunk:
                i += len(chunk)
                yield chunk
            if done and i >= len(self._stream):
                if self._error is not None:
                    raise self._error
                return


class _Request:
    __slots__ = ("prompt", "max_new", "future", "deadline", "frames",
                 "prefix")

    def __init__(self, prompt, max_new, future, deadline=None,
                 frames=None, prefix=None):
        self.prompt = prompt
        self.max_new = max_new
        self.future = future
        self.deadline = deadline  # absolute perf_counter instant or None
        # disaggregated serving: prefilled KV frames shipped by a
        # prefill-role worker (serving.disagg); None = prefill locally
        self.frames = frames
        # prefix caching: target-side conversation history the client
        # re-sends (multi-turn); forced verbatim before new tokens, and
        # the part already in the prefix trie is adopted instead of
        # recomputed. None/empty = fresh conversation.
        self.prefix = prefix


class _Slot:
    """Host-side record of one OCCUPIED decode slot."""

    __slots__ = ("req", "carry", "length", "emitted", "finished",
                 "admitted_seq", "version", "base", "entered", "drafts")

    def __init__(self, req, admitted_seq):
        self.req = req
        self.carry = None        # last sampled token, not yet KV-cached
        self.length = 0          # KV entries cached in this slot's pages
        self.emitted = []        # generated tokens streamed so far
        self.finished = False
        self.admitted_seq = admitted_seq
        self.version = None
        # cached positions before the first generated token: the prime
        # and the forced prefix (encoder-decoder), or the prompt itself
        # (a net with no encoder, whose prompt lives in the pages)
        self.base = 1 + (0 if req.prefix is None
                         else int(req.prefix.shape[0]))
        # prompt tokens written into the pages so far; None where the
        # prompt is encoder memory. The slot decodes once all are in
        self.entered = None
        self.drafts = []         # (index of the generated token, draft)

    @property
    def decoding(self) -> bool:
        return not self.finished and self.carry is not None


class _Burst:
    """A decode burst dispatched and not yet read: the rows it ran for AS
    THEY STOOD at the dispatch (slot index and slot record), the token
    block on the device, the weights' version it ran on, the lengths it
    was given (the host's array, or the device's for a burst dispatched
    ahead), the instant of the dispatch, and a speculative round's draft
    milliseconds."""

    __slots__ = ("rows", "buf", "version", "lengths", "t0", "draft_ms")

    def __init__(self, rows, buf, version, lengths, t0, draft_ms=None):
        self.rows = rows
        self.buf = buf
        self.version = version
        self.lengths = lengths
        self.t0 = t0
        self.draft_ms = draft_ms


class ContinuousBatcher:
    """Iteration-level scheduler over a paged KV cache.

    Between every decode iteration the scheduler retires finished rows
    (EOS, per-request ``max_new_tokens``, deadline), returns their pages
    to the pool, and admits queued requests into the vacated slots via a
    jitted prefill-into-pages dispatch — the decode batch stays full
    under load without a single retrace.

    While EVERY slot decodes the scheduler keeps one burst queued behind
    the burst in flight: the next burst takes its tokens and lengths from
    the device (``InferStep.next_carry``) and is dispatched BEFORE the last
    one is read, so reading, streaming, retiring and taking in run under
    device work. Only where nothing could be seated whatever arrives
    (``_may_run_ahead``): no slot free or entering, no row within a burst
    of its ``max_new_tokens``, the pages of a second burst granted without
    a fight, speculation off. Wherever that does not hold a pass is
    dispatch, then read, as ever, and a first token waits behind no burst
    it would not have waited behind.

    Parameters
    ----------
    engine : paged-protocol ``InferStep`` (``supports_paged``).
    bucket_keys : ascending prompt-length menu; the LARGEST key is also
        the static cross-attention memory width every slot carries.
    slots : decode-batch rows (``MXTPU_BATCHER_SLOTS``).
    max_new_tokens : per-request generation cap (requests may ask less).
    page_size / num_pages : KV pool geometry (``MXTPU_PAGE_SIZE`` /
        ``MXTPU_PAGES``; default pool fully provisions every slot).
    iter_tokens : decode steps per iteration (``MXTPU_ITER_TOKENS``);
        1 = pure Orca-style per-token scheduling. A step yields one token
        a row, or up to ``InferStep.slot_state["step_tokens"]`` for a net
        that drafts the next one itself: the count a row rides the token
        block's read-back, and lengths, pages and ``max_new_tokens``
        follow it.
    admit_free_pages / admit_max_queue / admit_max_wait_ms : backpressure
        thresholds (``MXTPU_ADMIT_*``): keep N pages free, bound the
        queue depth, reject while rolling queue-wait p50 breaches.
    max_prefix_tokens : forced target-prefix budget per request (re-sent
        multi-turn history, ``submit(prefix_ids=...)``); each slot is
        provisioned for ``1 + max_prefix_tokens + max_new_tokens``
        cached positions. 0 (default) rejects prefix requests.
    prefix_cache : enable the copy-on-write prefix trie over the page
        pool (``MXTPU_PREFIX_CACHE`` when None): retiring slots donate
        their page chains; admission adopts matched prefixes read-only
        and replays only the uncached suffix.
    spec_k : draft tokens per speculative round (``MXTPU_SPEC_K`` when
        None; 0 disables). Speculation engages only when the engine has
        an attached draft (``InferStep.attach_draft``), sampling is
        greedy, and ``MXTPU_SPEC_DRAFT`` isn't force-off — greedy output
        stays BIT-IDENTICAL to the non-speculative scheduler; only the
        tokens-per-dispatch ratio changes.
    spec_wide : verify drafts with the one-pass windowed target program
        (the shape the paged flash kernel accelerates) instead of the
        bit-exact sequential verifier.
    suffix_wide : replay prefix-cache suffixes through the one-pass
        q_offset-aware window program instead of the sequential stream.
    prefill_chunk : for a net whose slots keep no encoder memory
        (``InferStep.slot_state``): tokens a dispatch of the chunk
        program takes. The prompt lives in the slot's pages, which cover
        ``largest bucket + max_new_tokens``; it enters in chunks of this
        length, one chunk a pass, and between two chunks the decoding
        slots take their burst. Default: the largest bucket (one chunk).
        No ``cross`` buffers, ``mem_vl`` or cross-frame store are built
        for such a net; the prefix cache, forced prefixes, speculation
        and handoff frames are refused for it by name. What a slot keeps
        is one of three kinds, all declared by the net and read in
        ``InferStep.slot_state``: pages (they grow with the context),
        encoder memory (the largest bucket's width), and arrays indexed
        by slot of a fixed size whatever the context (a state-space
        layer's recurrent state). All of it is allocated here, once, for
        every slot: ``state_bytes`` reports pages (every plane of a pool
        that keeps a K/V plane for each pass of a looped stack), slot
        arrays and encoder memory; nothing is allocated a request. Slot
        arrays need no host call at admission or preemption: the chunk
        program starts them from zero where a prompt's first chunk
        enters.
    warmup : compile the admission-prefill program per bucket plus the
        decode-iteration program at construction (inert rows — the pools
        only ever see trash-page writes).
    sampling : ``method``/``top_k``/``temperature`` shared by every
        iteration. NOTE the key schedule is per-iteration, so sampled
        runs are reproducible per batcher, not vs ``decode_n``.
    name : tag for telemetry and fault matching (``serving.faults``);
        the router names each replica's batcher after the replica.
    watchdog : optional ``telemetry.Watchdog`` notified after every
        iteration — its ``heartbeat.json`` is the router's liveness
        signal for this replica (a hung dispatch stops the notifications
        and the heartbeat goes stale).
    """

    def __init__(self, engine, bucket_keys: Sequence[int],
                 slots: Optional[int] = None, max_new_tokens: int = 32,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 iter_tokens: Optional[int] = None,
                 sampling: Optional[dict] = None,
                 pad_id: Optional[int] = None,
                 admit_free_pages: Optional[int] = None,
                 admit_max_queue: Optional[int] = None,
                 admit_max_wait_ms: Optional[float] = None,
                 max_prefix_tokens: int = 0,
                 prefix_cache: Optional[bool] = None,
                 spec_k: Optional[int] = None, spec_wide: bool = False,
                 suffix_wide: bool = False,
                 prefill_chunk: Optional[int] = None,
                 warmup: bool = False, start: bool = True,
                 name: Optional[str] = None, watchdog=None):
        if not getattr(engine, "supports_paged", False):
            raise MXNetError(
                "ContinuousBatcher needs an InferStep whose net speaks the "
                "paged protocol (init_paged_state and decode_step_paged, "
                "with prefill_paged where a slot keeps encoder memory); "
                f"{type(getattr(engine, '_net', engine)).__name__} does not")
        self._engine = engine
        self.bucket_keys = sorted(int(k) for k in bucket_keys)
        if not self.bucket_keys:
            raise MXNetError("bucket_keys must be non-empty")
        self.slots = int(slots) if slots is not None else batcher_slots()
        self.max_new = int(max_new_tokens)
        self._sampling = dict(sampling or {})
        self._sampling.pop("seed", None)  # per-iteration key schedule
        self._pad = int(pad_id) if pad_id is not None else engine._pad
        self.name = name
        self._watchdog = watchdog
        self._queue: "queue.Queue[_Request]" = queue.Queue()
        # set by ``submit`` and ``stop``: what the idle scheduler waits
        # for. It takes nothing off the queue while it waits, so a request
        # changes hands (queue, waiting line, slot) inside a pass alone
        self._arrival = threading.Event()
        # odd while a pass runs (written by the scheduler thread alone):
        # ``_drained`` reads it before and after the rest
        self._pass_seq = 0
        self._init_rolling()
        self._stop = threading.Event()
        self._thread = None
        self.page_size = int(page_size) if page_size is not None \
            else _pages.page_size_default()
        self.max_prefix = int(max_prefix_tokens)
        # speculative decoding resolves BEFORE pool geometry: a spec
        # round writes up to k target entries past a row's emitted
        # length, and ACCEPTED entries must land in real pages (a
        # trash-page overflow would silently lose cached KV), so every
        # slot is provisioned k positions deeper
        self.spec_k = int(spec_k) if spec_k is not None \
            else spec_k_default()
        self._spec_on = (self.spec_k > 0
                         and getattr(engine, "has_draft", False)
                         and spec_draft_enabled()
                         and self._sampling.get("method",
                                                "greedy") == "greedy")
        self.spec_wide = bool(spec_wide)
        self.suffix_wide = bool(suffix_wide)
        # the one seam: what a slot keeps is the net's to declare, and
        # how many tokens a step of its decode program may yield a row
        self._enc_mem = bool(engine.slot_state["encoder_memory"])
        self._step_tokens = int(engine.slot_state["step_tokens"])
        # columns of the token block a step: the token, or [token, second
        # token, count, draft]
        self._step_cols = 1 if self._step_tokens == 1 else 4
        if self._enc_mem:
            self.chunk = None
            cached = 1 + self.max_prefix + self.max_new \
                + (self.spec_k if self._spec_on else 0)
        else:
            for what, on in (("the prefix cache", prefix_cache),
                             ("a forced prefix (max_prefix_tokens)",
                              self.max_prefix),
                             ("speculative decoding", self._spec_on)):
                if on:
                    raise MXNetError(
                        f"{what} is not built for a net whose slots keep "
                        "no encoder memory: sharing or replaying pages "
                        "that hold a PROMPT is a later issue")
            prefix_cache = False
            self.chunk = int(prefill_chunk) if prefill_chunk is not None \
                else self.bucket_keys[-1]
            cached = self.bucket_keys[-1] + self.max_new
        self.pages_per_slot = _pages.pages_for(cached, self.page_size)
        self.num_pages = int(num_pages) if num_pages is not None \
            else _pages.num_pages_default(self.slots, self.pages_per_slot)
        if self.pages_per_slot > self.num_pages:
            raise MXNetError(
                f"one request needs {self.pages_per_slot} pages for "
                f"max_new_tokens={self.max_new} but the pool has only "
                f"{self.num_pages} (MXTPU_PAGES / MXTPU_PAGE_SIZE)")
        self.iter_tokens = int(iter_tokens) if iter_tokens is not None \
            else iter_tokens_default()
        self.mem_len = self.bucket_keys[-1]
        self._admit_free_pages = admit_free_pages \
            if admit_free_pages is not None else _pages.admit_free_pages()
        self._admit_max_queue = admit_max_queue \
            if admit_max_queue is not None else _pages.admit_max_queue()
        self._admit_max_wait_ms = admit_max_wait_ms \
            if admit_max_wait_ms is not None else _pages.admit_max_wait_ms()
        self.pool = _pages.PagePool(self.num_pages, self.page_size,
                                    self.slots, self.pages_per_slot)
        self._state = engine.init_paged_state(
            self.slots, self.num_pages, self.page_size, self.mem_len)
        # what was provisioned, by kind of slot state (bytes on the
        # device): the pool's pages (whole arrays, so a pool's plane axis
        # counts), slots x the fixed-size arrays, and the encoder memory
        decl = engine.slot_state
        self.state_bytes = {
            kind: sum(int(a.nbytes) for n in names for a in self._state[n])
            for kind, names in (
                ("pages", decl["pools"]),
                ("slot_arrays", decl["slot_arrays"]),
                ("encoder_memory",
                 ("cross_k", "cross_v") if self._enc_mem else ()))}
        for kind, n in self.state_bytes.items():
            _tel.registry().gauge("infer/state_bytes_" + kind).set(n)
        # the draft model decodes against its OWN pools but the SAME
        # page table — one allocator, two KV caches
        self._dstate = engine.init_draft_state(
            self.slots, self.num_pages, self.page_size,
            self.mem_len) if self._spec_on else None
        from ..ops import paged as _paged
        _tel.registry().gauge("infer/flash_kernel").set(
            1.0 if _paged.kernels_on() else 0.0)
        # prefix trie over this pool: retired slots donate their page
        # chains (refcounted, read-only) and admission adopts matched
        # prefixes instead of recomputing them
        self.cache = _prefix.PrefixCache(
            self.pool, self.page_size, enabled=prefix_cache)
        self._cache_tag = getattr(engine, "weights_version", None)
        # device-resident frames of the trie's roots, and the two
        # compiled programs that fill and read them (traced once by
        # warmup): slot -> row at retire, row -> slot at a hit
        self._store = self._new_store()
        self._store_fn = None
        self._hits_fn = None
        # suffix-length bucket menu for the forced-prefix replay program
        # (same powers-of-2 discipline as the admission-row menu)
        self._suffix_menu = []
        if self.max_prefix > 0:
            s = 1
            while s < self.max_prefix:
                self._suffix_menu.append(s)
                s *= 2
            self._suffix_menu.append(self.max_prefix)
        self._slots = [None] * self.slots
        self._pending = collections.deque()
        self._seq = 0
        self._iter = 0
        # the burst dispatched ahead and not yet read (``_Burst``), or
        # None: written by the scheduler thread alone, between two passes
        # it is the one thing the device still runs for this batcher
        self._flight = None
        # the end of the last read-back: a burst dispatched ahead began on
        # the device no earlier than this
        self._read_at = 0.0
        self._all_active = _np.ones((self.slots,), bool)
        # stats + the rolling-wait window are written by the scheduler
        # thread AND by submit-side admission control (caller threads);
        # every touch goes through this lock — an unsynchronized
        # sorted() over the deque while the scheduler appends raises
        # "deque mutated during iteration" (mxlint lock-order pass)
        self._stats_lock = threading.Lock()
        self.stats = {"iterations": 0, "occupancy_sum": 0.0,
                      "admitted": 0, "retired": 0, "preempted": 0,
                      "rejected": 0, "tokens": 0,
                      # bursts dispatched before the burst before them
                      # was read (over ``iterations``: the share of passes
                      # whose host turn ran under device work)
                      "bursts_ahead": 0,
                      # disaggregated serving: KV handoffs adopted into
                      # this pool / handoffs that fell back to a local
                      # re-prefill (serving.disagg)
                      "adopted": 0, "re_prefills": 0,
                      # prefix caching: trie lookups that matched, KV
                      # tokens served from cache instead of recomputed,
                      # and copy-on-write page copies
                      "prefix_hits": 0, "prefix_lookups": 0,
                      "prefix_tokens_saved": 0, "cow_copies": 0,
                      # store programs dispatched and store rows written:
                      # ``prefix_rows_stored`` over ``retired`` is the
                      # share of requests that brought a new root
                      "prefix_store_dispatches": 0, "prefix_rows_stored": 0,
                      # cumulative seconds of the scheduler's phases
                      # (``telemetry.phase`` spans ``mxtpu.sched.*``):
                      # ``step_s`` is a whole working pass; intake,
                      # retire, admit, capacity, dispatch, readback and
                      # collect lie side by side inside it;
                      # register_prefix (with its device step, the
                      # store dispatch, under the read-back's name) lies
                      # inside retire, prefill inside admit
                      "step_s": 0.0, "intake_s": 0.0, "retire_s": 0.0,
                      "register_prefix_s": 0.0,
                      "register_readback_s": 0.0, "admit_s": 0.0,
                      "prefill_s": 0.0, "capacity_s": 0.0,
                      "dispatch_s": 0.0, "readback_s": 0.0,
                      "collect_s": 0.0,
                      # a prompt that lives in the pages: chunk
                      # dispatches (span ``sched.admit.prefill_chunk``,
                      # dispatch to its token on the host) and the
                      # prompt tokens they wrote
                      "prefill_chunk_s": 0.0, "prompt_chunks": 0,
                      "prompt_tokens": 0}
        # window histograms (``HIST_KEYS``): int64 counts over fixed
        # log-spaced edges (``telemetry.metrics.BucketBlock``), observed
        # by the scheduler thread alone. What is observed of requests
        # (``_observe((key, ms))``, an append) becomes a new block right
        # after ``_pass_once`` has dispatched the burst, where the thread
        # would only wait for the device; a pass's own two observations
        # have a small block to themselves, made at its end. A pass
        # publishes the rows of NEW blocks (``_rows``), so a
        # ``dict(stats)`` is a sound snapshot and the difference of two is
        # the histogram of what was observed between them
        self._hist = _metrics.BucketBlock(_REQUEST_HISTS)
        self._hist_pass = _metrics.BucketBlock(_PASS_HISTS)
        self._observe = self._hist.observe
        self._rows = {}
        for block in (self._hist, self._hist_pass):
            self.stats.update(block.rows())
        # device-side counts that rode the tokens' read-backs
        # (``InferStep._take_counts``), under the names the net declares
        # (``slot_state["counts"]``: an expert layer's tokens an expert,
        # a sparse attention's keys seen and selected, a state-space
        # layer's scanned tokens...), by the program they came from
        self._count_fields = tuple(
            (str(k), int(n)) for k, n in engine.slot_state["counts"])
        for k in ("prefill_", "decode_"):
            self.stats.update({
                k + name: _np.zeros((n,), _np.int64) if n > 1 else 0
                for name, n in self._count_fields})
        # what one pass adds to ``stats`` (phase seconds, and the
        # iteration's counts): summed here by the scheduler thread alone
        # and published in ONE ``_stats_lock`` hold at the pass's end
        self._pass = collections.Counter()
        if warmup:
            self._warmup()
        if start:
            self.start()

    # --------------------------------------------------- SLO telemetry
    def _init_rolling(self):
        """Rolling SLO windows (queue wait / TTFT) feeding the worker's
        health report and the router's predicted-wait placement; written
        by the scheduler thread, read by caller threads — every touch
        holds ``_roll_lock`` (and nothing blocking runs under it)."""
        self._roll_lock = threading.Lock()
        self._recent_waits = collections.deque(maxlen=64)
        self._recent_ttft = collections.deque(maxlen=64)

    def _note_wait(self, ms: float):
        with self._roll_lock:
            self._recent_waits.append(ms)

    def _note_ttft(self, ms: float):
        with self._roll_lock:
            self._recent_ttft.append(ms)

    def rolling_wait_ms(self, min_samples: int = 8) -> Optional[float]:
        """Rolling queue-wait p50 (ms) over recent completions, or None
        below ``min_samples`` — the worker-reported signal behind both
        admission control and SLO-aware router placement."""
        with self._roll_lock:
            waits = sorted(self._recent_waits)
        if len(waits) < min_samples:
            return None
        return waits[len(waits) // 2]

    def rolling_ttft_ms(self, min_samples: int = 4) -> Optional[float]:
        """Rolling time-to-first-token p50 (ms), or None below
        ``min_samples``."""
        with self._roll_lock:
            ttft = sorted(self._recent_ttft)
        if len(ttft) < min_samples:
            return None
        return ttft[len(ttft) // 2]

    def _label(self) -> str:
        return f"{type(self).__name__}" + (f" {self.name!r}"
                                           if self.name else "")

    # ------------------------------------------------------------ lifecycle
    def start(self):
        if self._thread is not None and self._thread.is_alive():
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, name="mxtpu-batcher", daemon=True)
        self._thread.start()

    def stop(self, drain: bool = True, timeout: float = 30.0):
        """Stop the scheduler; with ``drain`` (default) outstanding
        requests are served first. Whatever is still queued, waiting or
        in a slot once the thread is down is FAILED (a stopped batcher
        must never hold an unresolvable future)."""
        if drain and self.healthy:
            deadline = time.perf_counter() + timeout
            while not self._drained() and time.perf_counter() < deadline:
                time.sleep(0.005)
        self._stop.set()
        self._arrival.set()
        if self._thread is not None:
            self._thread.join(timeout=timeout)
            self._thread = None
        self.cancel_pending()
        self._fail_inflight(RuntimeError(
            f"{self._label()} stopped with this request in flight"))

    def _drained(self) -> bool:
        """Nothing queued, waiting or in a slot, no burst dispatched
        ahead and unread, and nothing on its way from one to the next. A
        request changes hands inside a pass alone (an admission prefill
        holds it in neither ``_pending`` nor ``_slots``), so while a pass
        runs, or where one began or ended during this read, the answer is
        no."""
        seq = self._pass_seq
        return not seq & 1 and self._queue.empty() and not self._pending \
            and not any(self._slots) and self._flight is None \
            and self._pass_seq == seq

    @property
    def healthy(self) -> bool:
        """True while the dispatcher thread is alive and accepting — the
        router's per-replica liveness poll. Goes false on ``stop()`` and
        when the thread died (a crash outside the dispatch try)."""
        t = self._thread
        return t is not None and t.is_alive() and not self._stop.is_set()

    def cancel_pending(self, error: Optional[BaseException] = None) -> int:
        """Drain the queue, failing every undispatched request's future
        (default error: RuntimeError naming the batcher). The router uses
        this when evicting an unhealthy replica — the failed futures are
        its signal to resubmit those requests elsewhere. Returns how many
        requests were cancelled."""
        n = 0
        while True:
            try:
                r = self._queue.get_nowait()
            except queue.Empty:
                return n
            r.future._fail(error if error is not None else RuntimeError(
                f"{self._label()} stopped with this request still queued"))
            n += 1

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    # ------------------------------------------------------------- requests
    def _admission_check(self, fut) -> bool:
        """Reject-with-backpressure at submit: queue depth beyond
        ``MXTPU_ADMIT_MAX_QUEUE``, or rolling queue-wait p50 beyond
        ``MXTPU_ADMIT_MAX_WAIT_MS``, or free pages below the watermark
        with nothing about to retire — the caller (router) reroutes."""
        reason = None
        if self._queue.qsize() + len(self._pending) >= self._admit_max_queue:
            reason = (f"queue depth {self._queue.qsize()} >= "
                      f"{self._admit_max_queue} (MXTPU_ADMIT_MAX_QUEUE)")
        elif self._admit_max_wait_ms > 0:
            p50 = self.rolling_wait_ms()
            if p50 is not None and p50 > self._admit_max_wait_ms:
                reason = (f"queue wait p50 {p50:.0f} ms > "
                          f"{self._admit_max_wait_ms:.0f} ms "
                          "(MXTPU_ADMIT_MAX_WAIT_MS)")
        if reason is not None:
            with self._stats_lock:
                self.stats["rejected"] += 1
            _tel.registry().counter("infer/rejected_backpressure").inc()
            fut._fail(Backpressure(
                f"{self._label()} rejected the request: {reason}"))
            return False
        return True

    def submit(self, prompt_ids, max_new_tokens: Optional[int] = None,
               deadline_ms: Optional[float] = None,
               frames: Optional[dict] = None,
               prefix_ids=None,
               request_id: Optional[str] = None) -> GenerationResult:
        """Enqueue one prompt (1-D int sequence). Returns a future whose
        ``result()`` is the generated token list, trimmed at EOS and at
        the request's ``max_new_tokens`` (<= the batcher's).

        ``deadline_ms`` bounds the request's total latency from NOW: a
        request still queued or still decoding when its deadline passes
        is failed with ``DeadlineExceeded`` instead of being served late.

        ``frames`` carries prefilled KV from a prefill-role worker
        (``serving.disagg``): admission adopts them into the pool instead
        of re-running the prefill; any adoption failure re-prefills from
        the prompt — the request is served either way.

        ``prefix_ids`` is target-side conversation history (tokens the
        model already produced in earlier turns, re-sent by the client):
        they are forced verbatim before new tokens are sampled, and any
        part already in the prefix trie is served straight from cached KV
        pages. Only new tokens are returned. Requires a batcher built
        with ``max_prefix_tokens > 0``. Neither ``frames`` nor
        ``prefix_ids`` is built for a net whose slots keep no encoder
        memory.

        ``request_id`` tags the future (and its spans/phase breakdown)
        with the fleet-wide trace id minted at the router; None is fine
        for direct callers — phases still stamp, spans are just
        unlinked.

        Submitting to a stopped (or crashed) batcher fails the future
        immediately with a RuntimeError — a request must never enqueue
        behind a dispatcher that will not run again."""
        if not self._enc_mem and (frames is not None
                                  or prefix_ids is not None):
            raise MXNetError(
                "handoff frames and forced prefixes are not built for a "
                "net whose slots keep no encoder memory: both are written "
                "against per-slot cross buffers (serving.disagg "
                "pack_frames, ContinuousBatcher._adopt)")
        prompt = _np.asarray(prompt_ids, dtype=_np.int32).reshape(-1)
        if prompt.shape[0] > self.bucket_keys[-1]:
            raise MXNetError(
                f"prompt length {prompt.shape[0]} exceeds the largest "
                f"bucket key {self.bucket_keys[-1]}")
        max_new = self.max_new if max_new_tokens is None \
            else int(max_new_tokens)
        if max_new > self.max_new:
            raise MXNetError(
                f"request max_new_tokens {max_new} > batcher "
                f"max_new_tokens {self.max_new}")
        prefix = None
        if prefix_ids is not None:
            prefix = _np.asarray(prefix_ids, dtype=_np.int32).reshape(-1)
            if prefix.shape[0] == 0:
                prefix = None
            elif prefix.shape[0] > self.max_prefix:
                raise MXNetError(
                    f"prefix length {prefix.shape[0]} > batcher "
                    f"max_prefix_tokens {self.max_prefix}")
        fut = GenerationResult()
        fut.request_id = request_id
        if not self.healthy:
            fut._fail(RuntimeError(
                f"{self._label()} is not accepting requests (stopped, or "
                "its dispatcher thread died) — the request would never "
                "resolve"))
            return fut
        if not self._admission_check(fut):
            return fut
        deadline = None if deadline_ms is None \
            else time.perf_counter() + float(deadline_ms) / 1e3
        self._queue.put(_Request(prompt, max_new, fut, deadline,
                                 frames=frames, prefix=prefix))
        self._arrival.set()
        return fut

    def _expire(self, reqs):
        """Fail (never dispatch) requests whose deadline passed while
        they were queued. Runs BEFORE admission, so expired rows don't
        occupy slots and the occupancy/queue-wait telemetry of the
        dispatched batch is unaffected."""
        now = time.perf_counter()
        live = []
        for r in reqs:
            if r.deadline is not None and now > r.deadline:
                _tel.registry().counter("serve/deadline_exceeded").inc()
                r.future._fail(DeadlineExceeded(
                    f"request deadline passed after "
                    f"{(now - r.future.enqueued_at) * 1e3:.0f} ms in "
                    "queue — not dispatched"))
            else:
                live.append(r)
        return live

    def _bucket_for(self, max_len):
        for k in self.bucket_keys:
            if max_len <= k:
                return k
        raise MXNetError(
            f"prompt length {max_len} > largest bucket key "
            f"{self.bucket_keys[-1]}")

    # --------------------------------------------------------------- warmup
    def _warmup(self):
        """Compile every program the scheduler can dispatch — one
        admission prefill per bucket + the decode-iteration program —
        with fully inert rows (no slot ids, trash pages only), then mark
        the guard steady."""
        import jax

        eng = self._engine
        reg = _tel.registry()
        before = eng.compile_guard.signatures
        if not self._enc_mem:
            # the chunk program (one row, inert) and the decode burst
            tok, self._state = eng.prefill_suffix_paged(
                self._state, _np.zeros((1, self.chunk), _np.int32),
                _np.ones((1,), _np.int32), _np.zeros((1,), _np.int32),
                _np.zeros((1, self.pages_per_slot), _np.int32),
                _np.full((1,), self.slots, _np.int32),
                _np.zeros((1,), bool), wide=True, **self._sampling)
            zeros = _np.zeros((self.slots,), _np.int32)
            buf, self._state = eng.decode_iter(
                self._state, self.pool.table, zeros, zeros,
                _np.zeros((self.slots,), bool), steps=self.iter_tokens,
                **self._sampling)
            # (and the program that carries one burst into the next)
            jax.block_until_ready((tok.data, buf.data, eng.next_carry(
                buf, zeros, steps=self.iter_tokens)))
            reg.counter("compile/warmup_compiles").inc(
                eng.compile_guard.signatures - before)
            eng.compile_guard.mark_steady()
            return
        rows_menu = []
        rows = 1
        while rows < self.slots:
            rows_menu.append(rows)
            rows *= 2
        rows_menu.append(self.slots)
        for bucket in self.bucket_keys:
            for rows in rows_menu:
                src = _np.zeros((rows, bucket), _np.int32)
                vl = _np.full((rows,), bucket, _np.int32)
                inert = _np.full((rows,), self.slots, _np.int32)  # OOB
                tok0, self._state = eng.prefill_paged(
                    self._state, src, vl, inert,
                    _np.zeros((rows,), _np.int32),
                    _np.zeros((rows,), bool), **self._sampling)
                jax.block_until_ready(tok0.data)
                if self._spec_on:
                    # draft admission shares every shape bucket with the
                    # target so cold admits never trace mid-serving
                    tokD, self._dstate = eng.draft.prefill_paged(
                        self._dstate, src, vl, inert,
                        _np.zeros((rows,), _np.int32),
                        _np.zeros((rows,), bool), **self._sampling)
                    jax.block_until_ready(tokD.data)
        zeros = _np.zeros((self.slots,), _np.int32)
        buf, self._state = eng.decode_iter(
            self._state, self.pool.table, zeros, zeros,
            _np.zeros((self.slots,), bool), steps=self.iter_tokens,
            **self._sampling)
        jax.block_until_ready(buf.data)
        if not self._spec_on:
            # the program that carries one burst into the next; a batcher
            # that speculates dispatches nothing ahead and has none
            jax.block_until_ready(eng.next_carry(
                buf, zeros, steps=self.iter_tokens))
        if self._spec_on:
            # one inert speculative round compiles BOTH spec programs
            # (draft k-token proposal + target k+1 verification)
            inactive = _np.zeros((self.slots,), bool)
            pair = eng.spec_pair()
            dbuf, self._dstate = eng.spec_draft(
                self._dstate, self.pool.table, zeros, zeros, inactive,
                k=self.spec_k, pair=pair)
            vbuf, self._state = eng.spec_verify(
                self._state, self.pool.table, dbuf, zeros, zeros,
                inactive, pair=pair, wide=self.spec_wide)
            jax.block_until_ready(vbuf.data)
        # forced-prefix replay menu (rows x suffix-length buckets): the
        # teacher-forced suffix program serves both cache hits and cold
        # prefix replays, so it must be steady before the first one
        for srows in rows_menu:
            for s_len in self._suffix_menu:
                toks = _np.zeros((srows, s_len), _np.int32)
                ones = _np.ones((srows,), _np.int32)
                tokS, self._state = eng.prefill_suffix_paged(
                    self._state, toks, ones, ones,
                    _np.zeros((srows, self.pages_per_slot), _np.int32),
                    _np.full((srows,), self.slots, _np.int32),
                    _np.zeros((srows,), bool), wide=self.suffix_wide,
                    **self._sampling)
                jax.block_until_ready(tokS.data)
        # the root-store and batched hit-adoption programs (inert here:
        # TRASH->TRASH COW self-copies, out-of-bounds store and cross
        # rows — shapes are padded to `slots`, so one trace of each
        # covers every retire pass and every admission group)
        if self.cache.enabled:
            self._store_rows({})
            self._apply_prefix_hits([])
        # warm the disaggregated-handoff adoption scatters too: the
        # first `.at[].set` per pool array otherwise compiles on the
        # scheduler thread mid-serving (a ~200 ms TTFT spike on the
        # first adopted request, measured on the CPU rig)
        if self.pool.alloc(0, 1):
            st = self._state
            fake = {"length": 1, "carry": 0, "emitted": [0], "mem_vl": 1,
                    "k": [_np.zeros((1,) + tuple(p.shape[2:]), _np.float32)
                          for p in st["k_pools"]],
                    "v": [_np.zeros((1,) + tuple(p.shape[2:]), _np.float32)
                          for p in st["v_pools"]],
                    "ck": [_np.zeros((1,) + tuple(c.shape[2:]),
                                     _np.float32)
                           for c in st["cross_k"]],
                    "cv": [_np.zeros((1,) + tuple(c.shape[2:]),
                                     _np.float32)
                           for c in st["cross_v"]]}
            self._adopt(0, fake)
            self.pool.release(0)
        reg.counter("compile/warmup_compiles").inc(
            eng.compile_guard.signatures - before)
        eng.compile_guard.mark_steady()

    # ------------------------------------------------------------ scheduler
    def _run(self):
        try:
            self._run_loop()
        except BaseException as e:
            # the thread is dying (a crash outside the dispatch try, e.g.
            # the `batcher.thread` fault point): fail whatever is queued
            # so no future is left unresolvable, then let it die —
            # `healthy` flips false and the router (if any) takes over
            self._fail_inflight(RuntimeError(
                f"{self._label()} dispatcher thread died"))
            self.cancel_pending(RuntimeError(
                f"{self._label()} dispatcher thread died"))
            # injected deaths exit quietly (the crash is the test's
            # point); real crashes re-raise for the interpreter's
            # thread-exception hook
            if not isinstance(e, _faults.FaultInjected):
                raise
        finally:
            # a burst dispatched ahead is left to the device and never
            # read: whoever stops the thread fails its rows and takes
            # their pages back (``_fail_inflight``)
            self._flight = None

    def _fail_inflight(self, error):
        """Fail the requests the scheduler already took off the queue
        (slots and the waiting line) and take every page back (a burst
        dispatched ahead went with the scheduler's thread: ``_run``)."""
        for i, s in enumerate(self._slots):
            if s is not None and not s.req.future.done():
                s.req.future._fail(error)
            self._slots[i] = None
        for r in self._pending:
            if not r.future.done():
                r.future._fail(error)
        self._pending.clear()
        self.pool.reset()

    def _run_loop(self):
        while not self._stop.is_set():
            _faults.fire("batcher.thread", tag=self.name)
            self._arrival.clear()
            if not self._step_once():
                # idle: wait briefly for an arrival, and leave it in the
                # queue for the next pass's intake
                self._arrival.wait(0.05)

    def _step_once(self) -> bool:
        """One scheduler iteration: retire -> admit -> decode -> collect;
        while every slot decodes, the NEXT burst is dispatched between
        this one's dispatch and its collect (``_pass_once``). Returns
        False when there was nothing to do (idle)."""
        if self._drained():
            return False
        acc = self._pass
        self._pass_seq += 1
        try:
            with _tel.phase("sched.step", acc, "step_s",
                            {"iter": self._iter + 1}):
                busy = self._pass_once()
        finally:
            self._pass_seq += 1
        self._hist_pass.observe(("h_pass_ms", acc["step_s"] * 1e3))
        if acc["iterations"]:
            self._hist_pass.observe(
                ("h_burst_ms", (acc["dispatch_s"] + acc["readback_s"]) * 1e3))
        rows = self._rows
        for block in (self._hist, self._hist_pass):
            # (a pass that dispatched a burst has flushed the first)
            rows.update(block.flush() or ())
        with self._stats_lock:
            for k, v in acc.items():
                self.stats[k] = self.stats[k] + v
            self.stats.update(rows)
        acc.clear()
        rows.clear()
        return busy

    def _pass_once(self) -> bool:
        """The pass inside its ``sched.step`` span; every phase adds its
        seconds to ``self._pass``."""
        acc = self._pass
        with _tel.phase("sched.intake", acc, "intake_s"):
            while True:
                try:
                    self._pending.append(self._queue.get_nowait())
                except queue.Empty:
                    break
            if self._pending:
                self._pending = collections.deque(
                    self._expire(list(self._pending)))
        try:
            # the whole iteration is one poison domain: an exception
            # anywhere (a partial admit that staged pages, a prefix
            # insert mid-refcount, a collect on poisoned state) must
            # release every page and fail every slot, not kill the
            # scheduler thread with pages still referenced.
            with _tel.phase("sched.retire", acc, "retire_s"):
                self._retire()
            with _tel.phase("sched.admit", acc, "admit_s"):
                admitted = self._admit()
            # a burst the pass before dispatched ahead is this pass's own:
            # nothing more is dispatched for it (read after admit: a
            # poisoned admission has dropped it)
            flight, self._flight = self._flight, None
            if flight is None:
                live = self._live()
                if not live:
                    return admitted > 0
                with _tel.phase("sched.capacity", acc, "capacity_s"):
                    self._ensure_capacity(live)
                live = self._live()
                if not live:
                    return True
                with _tel.phase("sched.dispatch", acc, "dispatch_s"):
                    flight = self._dispatch(live)
            if self._may_run_ahead(flight):
                # (a span of its own name; its seconds are a dispatch's)
                with _tel.phase("sched.dispatch.ahead", acc, "dispatch_s"):
                    self._flight = self._dispatch_ahead(flight)
            # the device runs the burst: what this pass's retire and admit
            # observed becomes a block here, where the thread would only
            # wait (``_step_once`` publishes it with the pass's seconds)
            self._rows.update(self._hist.flush() or ())
            self._collect(flight)
        except Exception as e:  # noqa: BLE001 - fail the slots, not the thread
            self._poison(e)
        return True

    def _live(self):
        """Slots that take part in the decode burst: occupied, not
        finished, and (where the prompt lives in the pages) all of the
        prompt in."""
        return [i for i, s in enumerate(self._slots)
                if s is not None and s.decoding]

    def _retire(self):
        """Resolve finished/expired slots and free their pages — the
        between-dispatches safe point."""
        now = time.perf_counter()
        reg = _tel.registry()
        traced = _tracing.trace_enabled()
        done = []
        for i, s in enumerate(self._slots):
            if s is None:
                continue
            r = s.req
            if not s.finished and r.deadline is not None \
                    and now > r.deadline:
                reg.counter("serve/deadline_exceeded").inc()
                r.future._fail(DeadlineExceeded(
                    f"request deadline passed after {len(s.emitted)} of "
                    f"{r.max_new} tokens — retired mid-decode"))
                s.finished = True
            if s.finished:
                done.append((i, s))
        if done and self.cache.enabled:
            self._register_roots(done)
        for i, s in done:
            r = s.req
            # donate the retiring chain to the prefix trie BEFORE the
            # release: the trie's cache_acquire keeps the pages alive
            # (refcounted) while the slot's own references go away
            with _tel.phase("sched.register_prefix", self._pass,
                            "register_prefix_s",
                            {"request_id": r.future.request_id}):
                self._register_prefix(i, s)
            self.pool.release(i)
            self._slots[i] = None
            fut = r.future
            if not fut.done():
                fut.weights_version = s.version
                fut.replica = self.name
                if s.drafts:
                    fut.drafts = list(s.drafts)
                fut.finished_at = now
                parts = fut._resolve(list(s.emitted))
                if parts and "deliver_ms" in parts:
                    # the caller has taken its first chunk (one that has
                    # not is skipped: ``_note_read`` completes ``phases``)
                    self._observe(("h_deliver_ms", parts["deliver_ms"]))
                    self._observe(("h_ttft_ms", parts["queue_ms"]
                                   + parts["prefill_ms"]
                                   + parts["deliver_ms"]))
            if traced and fut.active_at is not None:
                self._trace_request(s, now)
            with self._stats_lock:
                self.stats["retired"] += 1
            reg.counter("infer/requests").inc()
            reg.counter("infer/tokens").inc(len(s.emitted))
            wd = self._watchdog
            if wd is not None:
                wd.note_request(request_id=r.future.request_id,
                                completed=1)

    def _trace_request(self, s, now) -> None:
        """The request-level spans of a retiring slot, made from its
        timeline in this one place: ``trace.queue | trace.seat |
        trace.prefill (trace.adopt) | trace.decode`` follow one another
        from the enqueue (or the preemption) to ``now``."""
        fut = s.req.future
        since = fut.enqueued_at if fut.requeued_at is None \
            else fut.requeued_at
        args = {"replica": self.name}
        tokens = {"replica": self.name, "tokens": len(s.emitted)}
        adopted = (fut.phases or {}).get("adopted")
        spans = [("trace.queue", since, fut.admitted_at, args)]
        if fut.first_chunk_at > fut.admitted_at:
            spans.append(("trace.seat", fut.admitted_at,
                          fut.first_chunk_at, args))
        spans += [
            ("trace.adopt" if adopted else "trace.prefill",
             fut.first_chunk_at, fut.active_at, tokens if adopted else args),
            ("trace.decode", fut.active_at, now, tokens)]
        for name, t0, t1, a in spans:
            _tracing.span(name, _tel.us_of(t0), a,
                          request_id=fut.request_id, end_us=_tel.us_of(t1))

    def _adopt(self, slot: int, frames: dict) -> bool:
        """Adopt prefilled KV frames (``serving.disagg``) into ``slot``'s
        pages and cross buffers WITHOUT re-running the prefill — the
        decode half of a disaggregated handoff. Host-side ``.at[].set``
        scatters between dispatches; shapes/dtypes never change, so the
        decode program is untouched. Returns False on any geometry
        mismatch or failure (the caller then re-prefills from the
        prompt — zero lost requests by construction)."""
        import jax.numpy as jnp

        try:
            L = int(frames["length"])
            mvl = int(frames["mem_vl"])
            st = dict(self._state)
            if len(frames["k"]) != len(st["k_pools"]):
                return False
            if mvl > self.mem_len or L < 1 \
                    or L > self.pages_per_slot * self.page_size:
                return False
            if not self.pool.ensure(slot, L):
                return False
            # indices ride as TRACED operands (jnp scalars), never
            # Python ints: a concrete index bakes into the compiled
            # scatter, so every distinct slot/page combination would
            # compile its own program ON the scheduler thread mid-run —
            # measured as a multi-hundred-ms TTFT tail on the CPU rig
            slot_idx = jnp.asarray(slot, jnp.int32)
            kps, vps, cks, cvs = [], [], [], []
            for i in range(len(st["k_pools"])):
                kp, vp = st["k_pools"][i], st["v_pools"][i]
                ck, cv = st["cross_k"][i], st["cross_v"][i]
                k = _np.asarray(frames["k"][i])
                v = _np.asarray(frames["v"][i])
                if k.shape != (L,) + kp.shape[2:] or v.shape != k.shape:
                    return False
                for pi in range(_pages.pages_for(L, self.page_size)):
                    page = jnp.asarray(int(self.pool.table[slot, pi]),
                                       jnp.int32)
                    lo = pi * self.page_size
                    hi = min(L, lo + self.page_size)
                    kp = kp.at[page, :hi - lo].set(
                        jnp.asarray(k[lo:hi], kp.dtype))
                    vp = vp.at[page, :hi - lo].set(
                        jnp.asarray(v[lo:hi], vp.dtype))
                # zero-fill the slot's cross row beyond mem_vl so the
                # buffer matches what a local prefill_paged (which pads
                # the projections to mem_len) would have written —
                # bit-identical decode regardless of the slot's
                # previous occupant
                ckf = _np.zeros((self.mem_len,) + tuple(ck.shape[2:]),
                                _np.dtype(ck.dtype))
                cvf = _np.zeros_like(ckf)
                cka = _np.asarray(frames["ck"][i])
                cva = _np.asarray(frames["cv"][i])
                if cka.shape != (mvl,) + tuple(ck.shape[2:]) or \
                        cva.shape != cka.shape:
                    return False
                ckf[:mvl] = cka
                cvf[:mvl] = cva
                kps.append(kp)
                vps.append(vp)
                cks.append(ck.at[slot_idx].set(jnp.asarray(ckf, ck.dtype)))
                cvs.append(cv.at[slot_idx].set(jnp.asarray(cvf, cv.dtype)))
            st["k_pools"] = tuple(kps)
            st["v_pools"] = tuple(vps)
            st["cross_k"] = tuple(cks)
            st["cross_v"] = tuple(cvs)
            st["mem_vl"] = st["mem_vl"].at[slot_idx].set(mvl)
            self._state = st
            return True
        except Exception:  # noqa: BLE001 - torn frames = re-prefill
            return False

    # ------------------------------------------------------ prefix caching
    def _new_store(self):
        """The device-resident store of root frames, built as the state
        is: for each cross buffer ``[slots, mem_len, H, D]`` (every
        layer's K, then every layer's V) one ``[max_roots, mem_len, H,
        D]`` array of its dtype, and the rows' valid lengths. A trie
        root pins one row. None with the cache off."""
        if not self.cache.enabled:
            return None
        import jax.numpy as jnp

        st, n = self._state, self.cache.max_roots
        return (tuple(jnp.zeros((n,) + tuple(c.shape[1:]), c.dtype)
                      for c in st["cross_k"] + st["cross_v"]),
                jnp.zeros((n,), st["mem_vl"].dtype))

    def _store_rows(self, by_row) -> None:
        """ONE compiled program copies the whole cross rows and valid
        lengths of slots into store rows (``by_row``: row -> slot),
        device to device: nothing is read back, nothing waited for. The
        index lists are padded to ``slots`` with out-of-bounds rows that
        the scatter drops, so one program covers every retire pass; the
        store is donated. It is enqueued before the admission prefill
        that may overwrite those slots: device order keeps it sound. The
        index vectors are built here for this one call and go to it as
        numpy: the call uploads them, nothing eager stands before it."""
        import jax

        sids = _np.zeros((self.slots,), _np.int32)
        rows = _np.full((self.slots,), self.cache.max_roots, _np.int32)
        for j, (row, slot) in enumerate(by_row.items()):
            rows[j] = row
            sids[j] = slot
        if self._store_fn is None:
            def _store(cross, mem, frames, vl, sids, rows):
                return (tuple(f.at[rows].set(c[sids], mode="drop")
                              for f, c in zip(frames, cross)),
                        vl.at[rows].set(mem[sids], mode="drop"))
            self._store_fn = jax.jit(_store, donate_argnums=(2, 3))
        st = self._state
        self._store = self._store_fn(
            st["cross_k"] + st["cross_v"], st["mem_vl"], *self._store,
            sids, rows)
        if by_row:
            self._pass["prefix_store_dispatches"] += 1
            self._pass["prefix_rows_stored"] += len(by_row)

    def _register_roots(self, done) -> None:
        """Give every retiring prompt that is new to the trie its root
        and fill the new roots' store rows in one dispatch for the pass.
        Where the trie evicted a root of this very pass for its row, the
        row's later slot wins (the earlier prompt has no root left)."""
        acc = self._pass
        with _tel.phase("sched.register_prefix.store", acc,
                        "register_prefix_s"):
            by_row = {}
            for i, s in done:
                row = self.cache.add_root(s.req.prompt)
                if row is not None:
                    by_row[row] = i
            if by_row:
                with _tel.phase("sched.register_prefix.readback", acc,
                                "register_readback_s"):
                    self._store_rows(by_row)

    def _apply_prefix_hits(self, hits) -> None:
        """ONE batched device update for every prefix hit admitted this
        iteration: a single gather/scatter duplicates all COW pages
        across every layer's K/V pool, and a single gather/scatter
        copies the roots' store rows (cross frames + ``mem_vl``) into
        the adopting slots, whole rows, device to device. The
        per-request ``.at[].set`` chains this replaces ran sequentially
        on the scheduler thread and were measured at ~9 ms per hit on
        the CPU rig — more than the batched cold replay they were
        saving. Rows are padded to ``slots`` (COW pads as TRASH
        self-copies, cross rows as out-of-bounds drops), so one compiled
        program covers every admission-group size. The four index
        vectors are this call's own and go to it as numpy."""
        import jax

        st = self._state
        n = self.slots
        src = _np.zeros((n,), _np.int32)   # TRASH -> TRASH no-ops
        dst = _np.zeros((n,), _np.int32)
        sids = _np.full((n,), n, _np.int32)  # OOB rows dropped
        rows = _np.zeros((n,), _np.int32)
        for i, (slot, hit) in enumerate(hits):
            if hit.cow is not None:
                src[i] = hit.cow[0]
                dst[i] = self.pool.table[slot, len(hit.full_pages)]
            sids[i] = slot
            rows[i] = hit.row
        if self._hits_fn is None:
            def _apply(kps, vps, c_k, c_v, mem, frames, vl, src, dst,
                       sids, rows):
                kps = tuple(kp.at[dst].set(kp[src]) for kp in kps)
                vps = tuple(vp.at[dst].set(vp[src]) for vp in vps)
                cross = tuple(c.at[sids].set(f[rows], mode="drop")
                              for c, f in zip(c_k + c_v, frames))
                mem = mem.at[sids].set(vl[rows], mode="drop")
                return kps, vps, cross[:len(c_k)], cross[len(c_k):], mem
            self._hits_fn = jax.jit(_apply)
        out = self._hits_fn(st["k_pools"], st["v_pools"],
                            st["cross_k"], st["cross_v"], st["mem_vl"],
                            *self._store, src, dst, sids, rows)
        st = dict(st)
        (st["k_pools"], st["v_pools"], st["cross_k"], st["cross_v"],
         st["mem_vl"]) = out
        self._state = st

    def _register_prefix(self, slot: int, s) -> None:
        """Donate a retiring slot's page chain to the prefix trie so a
        later request sharing the prompt + target history adopts instead
        of recomputing. Pure bookkeeping: the prompt's root and its
        frames are ``_register_roots``' (a prompt whose root went since
        is skipped)."""
        if not self.cache.enabled:
            return
        r = s.req
        target = [self._engine._bos,
                  *(() if r.prefix is None else r.prefix),
                  *s.emitted][:s.length]
        pages = list(self.pool.owned(slot))[
            :_pages.pages_for(s.length, self.page_size)]
        self.cache.insert(r.prompt, target, pages)

    def _seed_from_frames(self, slot: int, r, fr: dict) -> None:
        """A disaggregated handoff just adopted prefilled KV into
        ``slot``: register it in the prefix trie too, so later
        same-prompt requests on this decode worker hit the cache. The
        root's store row is filled from the slot's cross rows, which
        ``_adopt`` has just written, not from the host copy."""
        if not self.cache.enabled:
            return
        row = self.cache.add_root(r.prompt)
        if row is not None:
            self._store_rows({row: slot})
        L = int(fr["length"])
        target = ([self._engine._bos]
                  + [int(t) for t in fr["emitted"]])[:L]
        pages = list(self.pool.owned(slot))[
            :_pages.pages_for(L, self.page_size)]
        self.cache.insert(r.prompt, target, pages)

    def _ensure_with_evict(self, slot: int, upto: int) -> bool:
        """``pool.ensure`` with one retry after asking the trie to evict
        unreferenced cached pages — cached-but-idle KV yields to live
        requests before admission is refused."""
        if self.pool.ensure(slot, upto):
            return True
        need = _pages.pages_for(upto, self.page_size) \
            - len(self.pool.owned(slot))
        if self.cache.evict(max(need, 1)) == 0:
            return False
        return self.pool.ensure(slot, upto)

    def _stage_slot(self, slot: int, r):
        """Allocate (or adopt from the prefix trie) the pages ``slot``
        needs for the request's full forced target prefix, adopting the
        root's cross frames on a hit. Returns ``(ok, hit)``: ``ok``
        False means the pool cannot stage this request right now (the
        caller puts it back); ``hit`` None means the cold path — BOS
        prefill, then a teacher-forced suffix replay if the request
        carries a prefix."""
        target_len = 1 + (0 if r.prefix is None
                          else int(r.prefix.shape[0]))
        reg = _tel.registry()
        hit = None
        if r.frames is None and self.cache.enabled:
            target = [self._engine._bos] + ([] if r.prefix is None
                                            else [int(t) for t in r.prefix])
            hit = self.cache.match(r.prompt, target)
            with self._stats_lock:
                self.stats["prefix_lookups"] += 1
            if hit is not None and hit.matched < 1:
                # a bare root offers no adoptable pages, and the BOS
                # prime re-runs the encoder anyway — nothing to win
                hit = None
        if hit is not None:
            ok = self.pool.adopt_ref(slot, hit.full_pages)
            if ok:
                ok = self._ensure_with_evict(slot, target_len)
            if ok and hit.cow is not None:
                # the first page past the fully-adopted run becomes this
                # slot's private copy of the donor's partial page (the
                # replay appends into the copy, never the original); the
                # copy itself rides in the admission group's single
                # batched device update (``_apply_prefix_hits``)
                with self._stats_lock:
                    self.stats["cow_copies"] += 1
                reg.counter("infer/prefix_cow_copies").inc()
            if not ok:
                self.pool.release(slot)
                hit = None
            else:
                with self._stats_lock:
                    self.stats["prefix_hits"] += 1
                    self.stats["prefix_tokens_saved"] += \
                        int(r.prompt.shape[0]) + hit.matched
                reg.counter("infer/prefix_tokens_saved").inc(
                    int(r.prompt.shape[0]) + hit.matched)
        if hit is None:
            ok = self.pool.alloc(slot, 1) \
                or (self.cache.evict(1) > 0 and self.pool.alloc(slot, 1))
            if ok:
                ok = self._ensure_with_evict(slot, target_len)
            if not ok:
                self.pool.release(slot)
                return False, None
        return True, hit

    def prefix_stats(self) -> dict:
        """Prefix-cache snapshot (trie stats + batcher-side COW
        counter) — the worker health verb's prefix block."""
        out = self.cache.snapshot()
        with self._stats_lock:
            out["cow_copies"] = self.stats["cow_copies"]
        return out

    def prefix_digests(self, limit=None):
        """Most-recently-used root digests — the compact advertisement
        behind the router's prefix-affinity placement."""
        return self.cache.digests(limit)

    def _admit(self) -> int:
        """Fill vacated slots from the waiting line: requests carrying
        prefilled KV frames (disaggregated handoff) are ADOPTED straight
        into their slots; prefix-trie hits adopt their cached pages and
        replay only the uncached suffix; the rest go through ONE padded
        (slots, bucket) prefill-into-pages dispatch (cold rows with a
        forced prefix join the suffix replay afterwards); stream each
        admitted row's first token. Respects the free-page watermark,
        evicting idle cached pages before refusing admission."""
        if not self._enc_mem:
            return self._admit_prompts()
        free = [i for i, s in enumerate(self._slots) if s is None]
        if not free or not self._pending:
            return 0
        reg = _tel.registry()
        version = getattr(self._engine, "weights_version", None)
        if self.cache.enabled and version != self._cache_tag:
            # weights hot-swapped mid-serving: every cached page holds
            # KV from the OLD weights — serving it would silently mix
            # model versions
            self.cache.flush()
            self._cache_tag = version
        picked = []
        while free and self._pending:
            if self.pool.free_pages - len(picked) <= self._admit_free_pages \
                    and self.pool.pages_in_use > 0:
                # cached-but-unreferenced pages are reclaimable
                # headroom: the trie yields before admission stalls
                short = self._admit_free_pages + len(picked) + 1 \
                    - self.pool.free_pages
                if self.cache.evict(short) == 0:
                    break  # keep headroom for requests already decoding
            r = self._pending.popleft()
            slot = free.pop(0)
            ok, hit = self._stage_slot(slot, r)
            if not ok:
                self._pending.appendleft(r)
                free.insert(0, slot)
                break
            picked.append((slot, r, hit))
        reg.histogram("infer/admitted_per_iter").observe(len(picked))
        if not picked:
            return 0
        hit_rows = [(slot, hit) for slot, _r, hit in picked
                    if hit is not None]
        if hit_rows:
            try:
                _faults.fire("batcher.dispatch", tag=self.name)
                self._apply_prefix_hits(hit_rows)
            except Exception as e:  # noqa: BLE001 - fail futures, not thread
                for _slot, r, _hit in picked:
                    if not r.future.done():
                        r.future._fail(e)
                self._poison(e)
                return 0
        adopt, cold, suffix = [], [], []
        for slot, r, hit in picked:
            if r.frames is not None and self._adopt(slot, r.frames):
                adopt.append((slot, r))
                continue
            if r.frames is not None:
                # handoff arrived but cannot be adopted (mismatched
                # geometry / torn frames): fall back to a local
                # prefill from the prompt — the request still serves
                r.frames = None
                with self._stats_lock:
                    self.stats["re_prefills"] += 1
                reg.counter("disagg/re_prefills").inc()
            if hit is not None:
                suffix.append((slot, r, hit))
            else:
                cold.append((slot, r))
                if r.prefix is not None:
                    # forced history, nothing cached: BOS-prime first,
                    # then replay the whole prefix through the SAME
                    # suffix program a cache hit uses (bit-identity)
                    suffix.append((slot, r, None))
        n_admitted = 0
        if adopt:
            t_admit = time.perf_counter()
            for slot, r in adopt:
                fr = r.frames
                r.frames = None
                s = _Slot(r, self._seq)
                self._seq += 1
                s.length = int(fr["length"])
                s.carry = int(fr["carry"])
                s.emitted = [int(t) for t in fr["emitted"]]
                s.version = version
                self._slots[slot] = s
                self._seed_from_frames(slot, r, fr)
                # no prefill ran here: ``service_ms`` is the adoption's
                # own host work
                self._first_token(s, t_admit, adopted=True)
                if s.carry == self._engine._eos \
                        or len(s.emitted) >= r.max_new:
                    s.finished = True
            with self._stats_lock:
                self.stats["adopted"] += len(adopt)
            n_admitted += len(adopt)
            reg.counter("disagg/handoffs").inc(len(adopt))
        if cold:
            bucket = self._bucket_for(
                max(r.prompt.shape[0] for _, r in cold))
            # admission sub-batch menu: the prefill dispatch shape is
            # the smallest power-of-two row count covering the admitted
            # set, so a single-request admission costs a (1, bucket)
            # forward, not a full (slots, bucket) one — admission-heavy
            # (short-response) loads would otherwise spend more on
            # prefill than on decode
            rows = 1
            while rows < len(cold):
                rows *= 2
            rows = min(rows, self.slots)
            src = _np.full((rows, bucket), self._pad, _np.int32)
            vl = _np.full((rows,), bucket, _np.int32)
            slot_ids = _np.full((rows,), self.slots, _np.int32)  # OOB
            first_pages = _np.zeros((rows,), _np.int32)
            active = _np.zeros((rows,), bool)
            for i, (slot, r) in enumerate(cold):
                n = r.prompt.shape[0]
                src[i, :n] = r.prompt
                vl[i] = n
                slot_ids[i] = slot
                first_pages[i] = self.pool.table[slot, 0]
                active[i] = True
            try:
                _faults.fire("batcher.dispatch", tag=self.name)
                with _tel.phase("sched.admit.prefill", self._pass,
                                "prefill_s", _one_request(cold)) as ph:
                    tok0, self._state = self._engine.prefill_paged(
                        self._state, src, vl, slot_ids, first_pages,
                        active, seed=self._iter, **self._sampling)
                    if self._spec_on:
                        # prime the draft's KV over the same prompt rows;
                        # best-effort — prefix-hit/adopted rows skip this
                        # (an unprimed draft only lowers acceptance,
                        # never correctness: verification is always the
                        # target)
                        _, self._dstate = self._engine.draft.prefill_paged(
                            self._dstate, src, vl, slot_ids, first_pages,
                            active, seed=self._iter, **self._sampling)
                    tok0 = tok0.asnumpy()
            except Exception as e:  # noqa: BLE001 - fail futures, not thread
                for slot, r, _hit in picked:
                    if not r.future.done():
                        r.future._fail(e)
                self._poison(e)
                return 0
            reg.histogram("infer/prefill_ms").observe(ph.seconds * 1e3)
            for i, (slot, r) in enumerate(cold):
                if r.prefix is not None:
                    # its first token comes from the suffix replay; the
                    # BOS-prime sample is overridden by the forced
                    # history
                    continue
                self._activate(slot, r, int(tok0[i]), ph.t0, version, 1)
                n_admitted += 1
        if suffix:
            srows = 1
            while srows < len(suffix):
                srows *= 2
            srows = min(srows, self.slots)
            need = 0
            plans = []
            for slot, r, hit in suffix:
                target = [self._engine._bos] + [int(t) for t in r.prefix]
                start = hit.matched if hit is not None else 1
                plans.append((slot, r, target, start))
                need = max(need, len(target) - start)
            s_len = next(s for s in self._suffix_menu if s >= need)
            toks = _np.zeros((srows, s_len), _np.int32)
            vl_s = _np.ones((srows,), _np.int32)
            q_off = _np.zeros((srows,), _np.int32)
            tables = _np.zeros((srows, self.pages_per_slot), _np.int32)
            sids = _np.full((srows,), self.slots, _np.int32)  # OOB
            act = _np.zeros((srows,), bool)
            for i, (slot, r, target, start) in enumerate(plans):
                tail = target[start:]
                toks[i, :len(tail)] = tail
                vl_s[i] = len(tail)
                q_off[i] = start
                tables[i] = self.pool.table[slot]
                sids[i] = slot
                act[i] = True
            try:
                _faults.fire("batcher.dispatch", tag=self.name)
                with _tel.phase("sched.admit.prefill", self._pass,
                                "prefill_s", _one_request(suffix)) as ph:
                    tokS, self._state = self._engine.prefill_suffix_paged(
                        self._state, toks, vl_s, q_off, tables, sids, act,
                        seed=self._iter, wide=self.suffix_wide,
                        **self._sampling)
                    tokS = tokS.asnumpy()
            except Exception as e:  # noqa: BLE001 - fail futures, not thread
                for slot, r, _hit in picked:
                    if not r.future.done():
                        r.future._fail(e)
                self._poison(e)
                return 0
            reg.histogram("infer/prefill_ms").observe(ph.seconds * 1e3)
            for i, (slot, r, target, start) in enumerate(plans):
                self._activate(slot, r, int(tokS[i]), ph.t0, version,
                               len(target))
                n_admitted += 1
        with self._stats_lock:
            self.stats["admitted"] += n_admitted
        if self.cache.enabled:
            reg.gauge("infer/prefix_hit_rate").set(self.cache.hit_rate())
            reg.gauge("infer/pages_shared").set(self.pool.shared_pages)
        return n_admitted

    # ----------------------------------------- a prompt that lives in pages
    def _admit_prompts(self) -> int:
        """Admission for a net with no encoder memory. A waiting request
        takes a free slot and the pages for its whole prompt; then ONE
        chunk of the oldest prompt still entering goes through the chunk
        program (queries at ``entered`` over the history pages plus the
        chunk), which writes the slot's pages. The decoding slots take
        their burst between two chunks; a prompt's last chunk samples its
        first token. Returns requests placed plus chunks dispatched."""
        reg = _tel.registry()
        version = getattr(self._engine, "weights_version", None)
        placed = 0
        for slot, s in enumerate(self._slots):
            if s is not None or not self._pending:
                continue
            if self.pool.free_pages <= self._admit_free_pages \
                    and self.pool.pages_in_use > 0:
                break  # keep headroom for requests already decoding
            r = self._pending[0]
            n = int(r.prompt.shape[0])
            if not (self.pool.alloc(slot, 1)
                    and self.pool.ensure(slot, n + 1)):
                self.pool.release(slot)
                break
            self._pending.popleft()
            s = _Slot(r, self._seq)
            self._seq += 1
            s.base, s.entered, s.version = n, 0, version
            r.future.admitted_at = time.perf_counter()
            self._slots[slot] = s
            placed += 1
        reg.histogram("infer/admitted_per_iter").observe(placed)
        entering = [i for i, s in enumerate(self._slots)
                    if s is not None and not s.finished
                    and s.carry is None]
        if not entering:
            return placed
        slot = min(entering, key=lambda i: self._slots[i].admitted_seq)
        s = self._slots[slot]
        r = s.req
        part = r.prompt[s.entered:s.entered + self.chunk]
        toks = _np.full((1, self.chunk), self._pad, _np.int32)
        toks[0, :len(part)] = part
        fut = r.future
        try:
            _faults.fire("batcher.dispatch", tag=self.name)
            with _tel.phase("sched.admit.prefill_chunk", self._pass,
                            "prefill_chunk_s",
                            {"slot": slot,
                             "chunk": s.entered // self.chunk,
                             "request_id": fut.request_id}) as ph:
                if fut.first_chunk_at is None:
                    fut.first_chunk_at = ph.t0
                out, self._state = self._engine.prefill_suffix_paged(
                    self._state, toks,
                    _np.full((1,), len(part), _np.int32),
                    _np.full((1,), s.entered, _np.int32),
                    self.pool.table[slot:slot + 1],
                    _np.full((1,), slot, _np.int32), _np.ones((1,), bool),
                    seed=self._iter, wide=True, **self._sampling)
                out = out.asnumpy()
        except Exception as e:  # noqa: BLE001 - fail futures, not thread
            self._poison(e)
            return 0
        chunk_s = ph.seconds
        reg.histogram("infer/prefill_ms").observe(chunk_s * 1e3)
        self._observe(("h_chunk_ms", chunk_s * 1e3))
        # a chunk is this net's admission prefill: it counts there too,
        # so that ``admit_s - prefill_s`` stays admit's own time
        self._pass["prefill_s"] += chunk_s
        self._pass["prompt_chunks"] += 1
        self._pass["prompt_tokens"] += len(part)
        self._note_counts("prefill", out[1:])
        s.entered += len(part)
        if s.entered >= s.base:
            self._activate(slot, r, int(out[0]), None, version, s.base, s=s)
            self._pass["admitted"] += 1
        return placed + 1

    def _note_counts(self, program: str, counts) -> None:
        """Device-side counts that rode a read-back, into the pass's sums
        (published with the phase seconds in the one lock hold), in the
        order and under the names the net declares them."""
        acc, at = self._pass, 0
        for name, n in self._count_fields:
            key = program + "_" + name
            if n > 1:
                acc[key] = acc[key] + counts[at:at + n].astype(_np.int64)
            else:
                acc[key] += int(counts[at])
            at += n

    def _activate(self, slot: int, r, first_tok: int, t0, version,
                  length: int, s=None) -> None:
        """Install the freshly-prefilled request into its slot and
        stream its first sampled token (TTFT instant): shared by the
        cold-prefill and suffix-replay admission paths (``t0``: their
        dispatch, where the request took its slot), and by the last
        chunk of a prompt that entered its pages in chunks (``s``, the
        slot it has held since ``admitted_at``)."""
        if s is None:
            s = _Slot(r, self._seq)
            self._seq += 1
        s.length = length  # cached positions (prime + prefix, or prompt)
        s.carry = first_tok
        s.version = version
        s.emitted.append(s.carry)
        self._slots[slot] = s
        self._first_token(s, t0)
        if s.carry == self._engine._eos or len(s.emitted) >= r.max_new:
            s.finished = True

    def _first_token(self, s, dispatched_at, adopted=False) -> None:
        """A request's first token is on the host: stamp ``active_at``,
        take everything that is said of its admission from the timeline
        (``phases``, ``queue_wait_ms``, the rolling windows, the
        ``infer/`` histograms, the window histograms of the three parts
        that have ended) and stream what it has. ``dispatched_at`` is the
        admission prefill's dispatch for a request that took its slot
        there; a prompt that entered in chunks has both stamps already."""
        fut = s.req.future
        reg = _tel.registry()
        if fut.admitted_at is None:
            fut.admitted_at = fut.first_chunk_at = dispatched_at
        fut.active_at = now = time.perf_counter()
        parts = fut._set_phases(adopted)
        fut.queue_wait_ms = wait = (fut.admitted_at
                                    - fut.enqueued_at) * 1e3
        self._note_wait(wait)
        reg.histogram("infer/queue_wait_ms").observe(wait)
        for hist, part in _PART_KEYS[:3]:     # the three that have ended
            self._observe((hist, parts[part]))
        fut._stream_tokens(list(s.emitted), at=now)
        ttft = (fut.first_token_at - fut.enqueued_at) * 1e3
        reg.histogram("infer/ttft_ms").observe(ttft)
        self._note_ttft(ttft)

    def _ensure_capacity(self, live):
        """Grow page allocations so every live row can cache
        ``iter_tokens`` more entries; on pool exhaustion PREEMPT the
        youngest row (free its pages, restart it from its prompt at the
        queue head) rather than stalling the whole batch."""
        for i in list(live):
            s = self._slots[i]
            if s is None or s.finished:
                continue  # preempted/bounced by an earlier row's fight
            # a row near its max_new needs less than a full burst; beyond
            # its allocation the device's surplus burst steps land in the
            # trash page, so the cap is safe. A speculative round writes
            # up to spec_k entries ahead and ACCEPTED entries must land
            # in real pages, so the cap stretches by spec_k too. A net
            # whose step yields up to two tokens writes two positions a
            # step; the one a refused draft leaves past the cap is never
            # read.
            base = s.base
            if self._spec_on:
                grow = self.spec_k + 1
                cap = base + s.req.max_new + self.spec_k
            else:
                grow = self.iter_tokens * self._step_tokens
                cap = base + s.req.max_new
            upto = min(s.length + grow, cap)
            while not self.pool.ensure(i, upto):
                # idle cached pages yield before any live row is
                # preempted — the trie is a cache, not a tenant
                if self.cache.evict(1) > 0:
                    continue
                victims = [j for j in self._live() if j != i]
                if not victims:
                    # nothing left to preempt: this request cannot make
                    # progress right now — bounce it back to the caller
                    with self._stats_lock:
                        self.stats["rejected"] += 1
                    _tel.registry().counter(
                        "infer/rejected_backpressure").inc()
                    s.req.future._fail(Backpressure(
                        f"{self._label()}: page pool exhausted "
                        f"({self.pool.free_pages} free) with nothing to "
                        "preempt"))
                    self.pool.release(i)
                    self._slots[i] = None
                    break
                j = max(victims,
                        key=lambda x: self._slots[x].admitted_seq)
                self._preempt(j)

    def _preempt(self, slot):
        """Recompute-style preemption: free the slot's pages and restart
        the request from its prompt at the head of the line (greedy
        decoding regenerates the identical tokens)."""
        s = self._slots[slot]
        self.pool.release(slot)
        self._slots[slot] = None
        s.req.future._requeue(time.perf_counter())
        self._pending.appendleft(s.req)
        with self._stats_lock:
            self.stats["preempted"] += 1
        _tel.registry().counter("infer/preempted").inc()

    def _dispatch(self, live):
        """One decode-iteration dispatch over the slot batch: pure
        staging + the jitted ``InferStep.decode_iter`` call — linted
        sync-free (``tools/mxlint.py``, pass ``no-sync``); the host reads
        happen in ``_collect`` after the device work is in flight.

        With speculation on, the iteration is one draft proposal burst
        (k tokens per live slot against the draft's pools) plus ONE
        target verification dispatch scoring all k+1 positions; both
        engines' weights come from one coherent ``spec_pair()`` snapshot
        so a concurrent hot swap can never mix draft/target versions."""
        t0 = time.perf_counter()
        _faults.fire("batcher.hang", tag=self.name)
        _faults.fire("batcher.dispatch", tag=self.name)
        tokens = _np.zeros((self.slots,), _np.int32)
        lengths = _np.zeros((self.slots,), _np.int32)
        active = _np.zeros((self.slots,), bool)
        rows = [(i, self._slots[i]) for i in live]
        for i, s in rows:
            tokens[i] = s.carry
            lengths[i] = s.length
            active[i] = True
        self._iter += 1
        if self._spec_on:
            pair = self._engine.spec_pair()
            t_d = time.perf_counter()
            dbuf, self._dstate = self._engine.spec_draft(
                self._dstate, self.pool.table, tokens, lengths, active,
                k=self.spec_k, pair=pair, seed=self._iter)
            draft_ms = (time.perf_counter() - t_d) * 1e3
            buf, self._state = self._engine.spec_verify(
                self._state, self.pool.table, dbuf, tokens, lengths,
                active, pair=pair, wide=self.spec_wide)
            return _Burst(rows, buf, pair[2], lengths, t0, draft_ms)
        version = getattr(self._engine, "weights_version", None)
        buf, self._state = self._engine.decode_iter(
            self._state, self.pool.table, tokens, lengths, active,
            steps=self.iter_tokens, seed=self._iter, **self._sampling)
        return _Burst(rows, buf, version, lengths, t0)

    def _may_run_ahead(self, flight) -> bool:
        """Whether the burst after ``flight`` may be dispatched before
        ``flight`` is read: only where the read-back could change nothing
        the next burst is made of, and nothing could be seated whatever
        arrives in the meantime. Every slot holds the decoding row
        ``flight`` runs for (none free, none with a prompt still
        entering, none retired or preempted since); no row can reach its
        ``max_new_tokens`` within ``flight``, reckoned at the most tokens
        a step can yield; speculation is off; and the pool grants every
        row the pages of a SECOND burst as it stands, with no eviction
        and no preemption (where it does not, the pass that follows
        fights for pages as ever). All of it from state the scheduler
        holds: nothing is read from the device."""
        if self._spec_on or len(flight.rows) < self.slots:
            return False
        most = self.iter_tokens * self._step_tokens
        for i, s in flight.rows:
            if self._slots[i] is not s or s.finished \
                    or len(s.emitted) + most >= s.req.max_new:
                return False
        for i, s in flight.rows:
            # ``s.length`` is the row's before ``flight``
            if not self.pool.ensure(i, min(s.length + 2 * most,
                                           s.base + s.req.max_new)):
                return False
        return True

    def _dispatch_ahead(self, flight):
        """The burst after ``flight`` for the same rows, dispatched before
        ``flight`` is read: its tokens and lengths come from ``flight``'s
        token block on the device (``InferStep.next_carry``), so the
        device goes from one burst to the next while the host reads,
        streams, retires and takes in. Staging and two enqueues, sync-free
        by lint like ``_dispatch``; the caller has asked
        ``_may_run_ahead``."""
        t0 = time.perf_counter()
        _faults.fire("batcher.hang", tag=self.name)
        _faults.fire("batcher.dispatch", tag=self.name)
        tokens, lengths = self._engine.next_carry(
            flight.buf, flight.lengths, steps=self.iter_tokens)
        self._iter += 1
        version = getattr(self._engine, "weights_version", None)
        buf, self._state = self._engine.decode_iter(
            self._state, self.pool.table, tokens, lengths, self._all_active,
            steps=self.iter_tokens, seed=self._iter, **self._sampling)
        self._pass["bursts_ahead"] += 1
        _tel.registry().counter("infer/bursts_ahead").inc()
        return _Burst(flight.rows, buf, version, lengths, t0)

    def _collect(self, flight):
        """Read back a burst's token block — the scheduler's ONE sync
        point — then stream, account lengths, and mark retirements for
        the next iteration's safe point. What is read is what was
        dispatched: the rows are ``flight``'s, and a row that has ended
        or left its slot since (an end token or a deadline inside the
        burst before, while this one was already queued) ran this burst
        for nothing: its part is dropped."""
        version, draft_ms = flight.version, flight.draft_ms
        live = [(i, s) for i, s in flight.rows
                if self._slots[i] is s and not s.finished]
        acc = self._pass
        with _tel.phase("sched.collect.readback", acc, "readback_s"):
            toks = flight.buf.asnumpy()
        if self._count_fields:
            self._note_counts("decode", toks[
                :, self.iter_tokens * self._step_cols:].ravel())
        # a burst dispatched ahead began when the one before it ended on
        # the device, which the host saw no later than its read-back
        now = time.perf_counter()
        iter_ms = (now - max(flight.t0, self._read_at)) * 1e3
        self._read_at = now
        with _tel.phase("sched.collect", acc, "collect_s"):
            reg = _tel.registry()
            emitted_total = 0
            eos = self._engine._eos
            if draft_ms is not None:
                reg.histogram("infer/spec_draft_ms").observe(draft_ms)
            for i, s in live:
                fresh = []
                if self._spec_on:
                    # row layout: [t_0..t_k, count]; count = accepted
                    # drafts + the bonus token (0 for inactive rows).
                    # Every emitted token is the target's own greedy
                    # argmax — acceptance only decides how many land per
                    # round, never which.
                    burst = int(toks[i, self.spec_k + 1])
                    reg.histogram("infer/spec_accept_len").observe(
                        max(burst - 1, 0))
                elif self._step_tokens > 1:
                    fresh = self._take_steps(s, toks[i], eos)
                    burst = 0
                else:
                    burst = self.iter_tokens
                for j in range(burst):
                    tok = int(toks[i, j])
                    # this step cached the previous carry (a step of two
                    # positions, ``_take_steps``: the carry, and its
                    # draft where the draft was kept)
                    s.length += 1
                    s.carry = tok
                    fresh.append(tok)
                    if tok == eos or len(s.emitted) + len(fresh) \
                            >= s.req.max_new:
                        s.finished = True
                        break
                s.emitted.extend(fresh)
                s.version = version
                emitted_total += len(fresh)
                s.req.future._stream_tokens(fresh)
            occupancy = len(live) / self.slots
            # published with the pass's phase seconds in the one
            # ``_stats_lock`` hold at the end of ``_step_once``
            acc["iterations"] += 1
            acc["occupancy_sum"] += occupancy
            acc["tokens"] += emitted_total
            reg.gauge("infer/batch_occupancy").set(occupancy)
            reg.gauge("infer/pages_in_use").set(self.pool.pages_in_use)
            reg.gauge("infer/page_fragmentation").set(
                self.pool.fragmentation([s.length if s is not None else 0
                                         for s in self._slots]))
            if emitted_total:
                reg.histogram("infer/decode_ms_per_token").observe(
                    iter_ms / emitted_total)
                reg.gauge("infer/tokens_per_sec").set(
                    emitted_total / (iter_ms / 1e3))
            wd = self._watchdog
            if wd is not None:
                wd.notify_step(seconds=iter_ms / 1e3)
                wd.note_request(inflight=len(live) + len(self._pending))

    def _take_steps(self, s, row, eos):
        """A row of the token block of a net whose step yields up to two
        tokens: four columns a step, ``[token, second token, count,
        draft]``. The row moves ``count`` positions a step (the step
        cached its carry, and the draft too where the draft was the
        token); ``max_new_tokens`` cuts a second token that would pass
        it. Returns the tokens to stream."""
        fresh, cols = [], self._step_cols
        hist = _tel.registry().histogram("infer/mtp_accept_len")
        for j in range(self.iter_tokens):
            first, second, count, draft = (
                int(t) for t in row[cols * j:cols * (j + 1)])
            if count < 1:
                break       # the burst stopped this row (an end token)
            s.drafts.append((len(s.emitted) + len(fresh), draft))
            hist.observe(count - 1)
            for tok in (first, second)[:count]:
                s.length += 1
                s.carry = tok
                fresh.append(tok)
                if tok == eos or len(s.emitted) + len(fresh) \
                        >= s.req.max_new:
                    s.finished = True
                    return fresh
        return fresh

    def _poison(self, err):
        """A decode dispatch failed: the donated pool state is gone, so
        fail every in-flight request, rebuild the pools, and keep the
        thread alive for fresh work (fail the futures, not the
        thread)."""
        self._flight = None  # it ran on the state that is gone
        for i, s in enumerate(self._slots):
            if s is not None:
                if not s.req.future.done():
                    s.req.future._fail(err)
                self._slots[i] = None
        self.cache.flush()  # the pages the trie indexed no longer exist
        self.pool.reset()
        self._state = self._engine.init_paged_state(
            self.slots, self.num_pages, self.page_size, self.mem_len)
        self._store = self._new_store()  # donated by a dispatch that failed
        if self._spec_on:
            self._dstate = self._engine.init_draft_state(
                self.slots, self.num_pages, self.page_size, self.mem_len)

    def slot_arrays(self) -> dict:
        """The arrays indexed by slot as they stand, ``{name: (array a
        layer, ...)}`` (empty for a net that declares none): what each
        slot's last occupant left. For checks and debugging, on a STOPPED
        batcher only: a running scheduler donates them to its next
        dispatch."""
        if self._thread is not None:
            raise MXNetError("slot_arrays() reads a stopped batcher: a "
                             "running scheduler donates the arrays")
        return {name: tuple(self._state[name])
                for name in self._engine.slot_state["slot_arrays"]}

    def paged_state(self) -> dict:
        """The device state as it stands (pools, slot arrays, counts): for
        checks that drive the engine's programs by hand on what the
        scheduler left, on a STOPPED batcher only. A program the caller
        hands it to donates it: the batcher cannot serve afterwards."""
        if self._thread is not None:
            raise MXNetError("paged_state() reads a stopped batcher: a "
                             "running scheduler donates the state")
        return self._state

    @property
    def sustained_occupancy(self) -> float:
        """Mean decode-batch occupancy across every iteration so far —
        the open-loop bench's headline gate (>= 0.9 under load)."""
        with self._stats_lock:
            n = self.stats["iterations"]
            return self.stats["occupancy_sum"] / n if n else 0.0
