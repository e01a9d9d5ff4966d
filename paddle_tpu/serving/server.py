"""Continuous-batching inference server over the paged-KV decode
primitive (ISSUE 16 tentpole).

Request lifecycle — admission → prefill → continuous-batch decode loop
→ detokenize (caller-side):

1. **Admission**: :meth:`InferenceServer.submit` enqueues a request;
   the decode thread admits from the queue each time it plans a launch,
   whenever a batch slot AND enough free KV pages exist — new requests
   join the in-flight batch instead of waiting for it to drain (the
   continuous-batching property).  Page tables come from one
   shared :class:`~paddle_tpu.serving.pagepool.PagePool`; each request
   reserves ``prompt + max_new_tokens`` worth of pages up front, so a
   request that admits can never die of pool exhaustion mid-decode —
   exhaustion is pure admission backpressure.
2. **Prefill**: every request admitted in the same round runs in ONE
   ``flash_attention_packed`` launch (mixed prompt lengths packed into
   a single [1, B·T] row), which also writes the prompt K/V into the
   request's pages and yields the first generated token — the TTFT
   moment.  Where the model's plan can continue a prefill from what a
   sequence holds (``model.chunk`` > 0: ``model.continues``), a prompt
   longer than a chunk is prefilled instead a chunk a launch, one prompt
   at a time in admission order, and **every decoding row rides in each
   chunk launch** (``DecoderModel.launch_chunk``): one program advances
   both, so the weights a decode step would read alone are read once
   for both, and no row waits behind a long prefill.  A decode launch
   runs only when no chunk is pending.  The last chunk yields the first
   token.
3. **Decode loop**: one ``paged_decode_attention`` launch a step over a
   fixed-width batch (``--serve_max_batch``; inactive slots are
   padded with scratch-page tables so there is exactly one compiled
   decode shape).  Finished requests retire when their last token is
   collected, their pages recycle instantly — the kernel's stale-page
   immunity makes a freed page safe to reissue without scrubbing.

**The loop runs one launch ahead of the host** (PERF.md §6, PR 30).  A
turn of the decode thread builds launch k+1 and queues it on the device
while launch k still runs, then collects launch k's token ids (nothing
else comes back: the logits stay on the device), emits them and turns
round; the device goes from one launch straight into the next, and the
host's build, upload, dispatch, emit and its waits for the interpreter
lock lie under the device's work.  Depth is one.  What that means for a
row:

- a row of the decode launch in flight is fed in the next launch **from
  the device**: the jitted step picks its id out of the ids of the
  launch before (``DecoderModel.launch_decode``'s ``prev``/``src``),
  merged with host-known ids for rows that just joined;
- a row that launch k ends **by its budget** is known to the host
  beforehand and is not in k+1; a row that ends **by EOS** is learned
  one launch late: it rides in k+1 dead, writes one K/V row into pages
  it still owns, inside its reservation, and its k+1 token is neither
  emitted nor counted (``serve_rows_discarded_total``).  Its pages are
  released when the host learns of the EOS; a prefill admitted into
  them is queued behind k+1 on the device, so order keeps it safe;
- a prefill's first token joins from the host: the rows of a prefill in
  flight sit out the decode launch queued behind it and join the one
  after, fed the id the host has collected by then.  No launch waits
  for the host but the first after a lone prefill (and after a swap or
  an empty server): ``serve_launch_total{kind, queued=behind|idle}``;
- whatever assumed "nothing in flight" waits for it: a parked swap is
  applied only once everything the old model launched is collected,
  ``stop()`` and a failing turn collect what is queued first.

**A model that generates by diffusion over blocks** (``block_length``
B > 0) takes **block launches** where another takes decode launches:
every row of one computes its request's current block, B positions,
and reveals the share of its masked positions that the static schedule
gives the step (``model.reveal_schedule``: which positions is the
device's choice).  Once every position of a block is revealed, the
block is **committed**: its K/V are written once more from its final
ids, in the same row as the next block's first step (the row's tile
holds both, 2B positions under the block-causal mask), so a block of
two denoising steps takes two rows of two launches, and a commit never
takes a row of its own.  A prompt of L ids is prefilled through its
first ⌊L/B⌋·B positions; the L mod B ids left ride, unmasked, in the
first block.  The schedule is static, so the host knows each row's
phase without reading the device: a row's block comes from the host
where it starts (all masked, or the prompt's last ids) and from the
block launch it is queued behind otherwise (the block the device left,
``src``), and a row's prefill in flight holds nothing it needs, so it
joins the launch queued behind that prefill.  A block's tokens are
emitted when its last denoising step is collected, in order, up to an
EOS or the budget, and the request ends there: a request ends by its
budget at the end of the block that reaches it, which takes no commit
(nothing reads its K/V), and by an EOS one launch late, the row that
carries its commit riding dead as a row past its EOS does.  Pages
cover the prompt and the budget rounded up to B.  Each request keeps
the record of its finished blocks (``Request.blocks``: the block as
each denoising step found it and as the last left it, whether it was
committed), which is what a reference replays.

The kill switch ``--serve_continuous=false`` degrades the same loop to
sequential single-request serving (admit one, run to completion, batch
width 1).  Because every per-request computation in
``serving/model.py`` is row-independent, both modes generate
byte-for-byte identical tokens, those of a plain serial greedy loop —
pinned in both directions by ``tests/test_serving_server.py``.

Telemetry (all optional, live when ``paddle_tpu.observe`` is active):
``serve_ttft_seconds`` / ``serve_request_seconds`` reservoir histograms
(p99 SLO source), ``serve_queue_depth`` / ``serve_batch_size`` gauges,
``serve_requests`` / ``serve_tokens_generated`` counters, the two
counters above, ``serve_page_pool_pages`` pool census, and the span
family below.
Threads are ``ptpu-serve-decode`` and ``ptpu-serve-http`` (the conftest
thread-leak guard and ptpu-lint key on the prefix).

Spans (``observe.trace``; one shared no-op each when tracing is off).
**Spans follow launches, not host phases**: a turn of the decode thread
is one ``serve_loop_iter`` holding one launch span, which bears launch
k's attributes and runs from the collect of launch k−1 to launch k's own
(after its emit), so the launch spans tile the thread's time in launch
order and the device runs launch k inside span k (to within the emit
before it: a few hundred microseconds).  Under span k::

    serve_loop_iter
    ├─ serve_prefill{n, t_pad, prompt_tokens, moe_tokens, conv_tokens,
    │                scan_tokens, attn_pairs, requests, queued
    │                [, riding, chunk_start]}
    │  [└─ serve_decode_step{…}: a chunk launch's riding rows]
    │  or serve_decode_step{batch, live_tokens, live_pages, attended_tokens,
    │                       state_rows, queued, experts_hit, expert_load_max
    │                       [, discarded]
    │                       [, block_rows, commit_rows, revealed, emitted]}
    │    (from idle: serve_step_build · *_dispatch of launch k itself)
    │    serve_admit{queued} · serve_step_build · *_dispatch  of launch k+1
    │    *_fetch · serve_step_emit                            of launch k
    └─ serve_snapshot

A chunk launch is both over the same period: ``serve_prefill`` states
its chunk (``prompt_tokens`` its real positions, ``attn_pairs`` the
pairs its queries see, earlier chunks' keys included, ``chunk_start``
its first position, ``riding`` the rows it carries; ``n`` 1, ``t_pad``
the chunk) and, where rows ride, ``serve_decode_step`` inside it states
theirs as a decode launch would; ``serve_launch_total`` counts it as
``kind=chunk``.
``serve_step_build`` is the host's numpy input build, ``*_dispatch`` the
host→device copy of the inputs and the jitted call's return (the
launch), ``*_fetch`` the ``np.asarray`` that waits for the device and
brings the token ids back (both in ``serving/model.py``),
``serve_step_emit`` the token bookkeeping, finishes and page releases.
A span's duration is the period from collect to collect: what a step
costs.  ``queued`` says what the device had when the launch was queued
(``behind`` a launch not yet collected, or ``idle``).
``live_tokens`` is Σ ``lengths`` of the launched rows — the K/V positions
the step attends over — and ``live_pages`` the pages they occupy;
``attended_tokens`` is what the layers must read of them, summed over
the layers that attend (a window layer reads a row's newest ``window``
only, a conv layer none);
``batch`` the rows whose token was emitted (``discarded`` the others).
A block launch also states ``block_rows`` (the rows it computes, each a
denoising step), ``commit_rows`` (those whose launch carries their
previous block's commit as well), ``revealed`` (the positions its rows
reveal) and ``emitted`` (the tokens its collect emits); there ``batch``
is the rows still live when it was collected, ``live_tokens`` and
``attended_tokens`` count each row up to its tile's end, 2B from its
start (what the kernel reads; a row that does not commit has B dead
positions there), and
``serve_block_passes_total{kind=denoise|commit}`` and the gauge
``serve_tokens_per_launch`` count the same.
Where the plan has conv layers, ``conv_tokens`` is the prompt tokens
times those layers, where it has mamba layers ``scan_tokens`` the
prompt tokens times those, and ``state_rows`` the launched rows times
the two: the sequences whose fixed-size state the step reads and writes
back.  Under a plan with mamba layers that state lies in the request's
**slot**, one of ``width + 1``: a request takes one at admission and
gives it back when it finishes or fails, every launch hands each row's
slot to the step, and the last slot is the idle rows'.  A conv layer's
smaller state lies at the number of the request's first page (the step
is handed no slots: PERF.md §7), an idle row's at the scratch page's.
Either way a state follows its request whatever batch row the request
has in a launch.  A row riding one launch past its EOS writes its old
place on the device before the prefill that a new owner is queued
behind, and a prefill writes all of a place: a freed one needs no
clearing.
Where the plan has routed layers, ``moe_tokens`` is the prompt tokens
times those layers, and the step's own counts come back with its tokens
and are set before the span closes: ``experts_hit`` (experts with a
token of an active row, summed over the routed layers) and
``expert_load_max`` (the most tokens on one expert).  Each
request carries a ``trace_id`` (its submitter's trace, else its own):
at admission ``serve_queue_wait`` (submit → admit) and at the finish
``serve_request`` (submit → last token; ``prompt``, ``tokens``,
``ttft_ms``) are recorded under it.

Crash safety: with ``snapshot_path`` set, the allocator state persists
atomically after every mutation; a restarted server restores it only
if it validates (:class:`~paddle_tpu.serving.pagepool.TornSnapshot`
otherwise), then releases the orphaned tables — KV content died with
the process — and serves from a verified-clean pool.  A torn page
table is never served; ``testing/fault.py`` SIGKILLs this promise.
"""

from __future__ import annotations

import collections
import contextlib
import gc
import itertools
import json
import threading
import time
from http.server import BaseHTTPRequestHandler
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..analysis.lockorder import named_condition
from ..core.device import ensure_compile_cache
from ..utils import FLAGS, enforce, get_logger
from .model import DecoderModel, reveal_schedule
from .pagepool import PagePool, PagePoolExhausted, SCRATCH_PAGE, TornSnapshot

try:                         # telemetry optional, as in loader.py
    from ..observe import REGISTRY as _registry
    from ..observe import counter as _counter, gauge as _gauge
    from ..observe import histogram as _histogram, trace as _trace
    from ..observe import fleet as _fleet
    from ..observe.http import make_threading_server, resolve_bind_host
except ImportError:  # pragma: no cover - standalone copy
    _counter = _gauge = _histogram = _trace = _fleet = _registry = None
    make_threading_server = resolve_bind_host = None

log = get_logger("serving")

#: a launch period (one turn of the decode loop: PERF.md §3) longer than
#: this many seconds is a host stall: a decode turn takes 5-10 ms, a
#: prefill turn of the longest prompts under 100
STALL_S = 0.25

#: Decode-loop thread name (thread-leak guard + ptpu-lint contract).
DECODE_THREAD_NAME = "ptpu-serve-decode"
#: HTTP front-end thread name.
HTTP_THREAD_NAME = "ptpu-serve-http"

_REQ_IDS = itertools.count()


class _NoSpan:
    """What ``with _span(...) as sp`` binds without telemetry."""

    def set(self, **attrs) -> None:
        pass


_NO_SPAN = _NoSpan()


def _span(name: str, **attrs):
    if _trace is None:
        return contextlib.nullcontext(_NO_SPAN)
    # ptpu: lint-ok[PT-METRIC] forwarding shim; callers pass literals
    return _trace.span(name, **attrs)


class Request:
    """One generation request and its lifecycle state.  ``tokens`` holds
    the generated ids (prompt excluded); ``length`` counts tokens whose
    K/V the launches queued so far write to this request's pages (the
    one in flight included); ``table`` is its page-table row and
    ``slot`` where its fixed-size state lies, once admitted.

    Under a model with a ``block_length``: ``at`` and ``todo`` are
    where the planning stands (the start of the block its next pass
    works on, and that block's passes still to plan as reveal counts, 0
    the commit), ``fresh`` the block that block starts from until its
    first pass is planned, ``block`` the block as
    the newest collected pass left it (−1 where masked), ``states`` the
    current block as it started and after each of its collected
    denoising steps and ``confs`` each step's confidences (the log of
    a position's top probability), and ``blocks`` the finished blocks:
    ``{"start", "states", "confs", "committed"}`` (the last state is the
    block's ids)."""

    __slots__ = ("id", "prompt", "max_new_tokens", "tokens", "state",
                 "error", "done", "length", "next_token", "table", "slot",
                 "t_submit", "t_admit", "t_first", "t_done", "trace_id",
                 "at", "todo", "fresh", "block", "states",
                 "confs", "blocks")

    def __init__(self, prompt: Sequence[int], max_new_tokens: int):
        self.id = f"req{next(_REQ_IDS)}"
        self.prompt = [int(t) for t in prompt]
        self.max_new_tokens = int(max_new_tokens)
        self.tokens: List[int] = []
        self.state = "queued"            # queued|active|done|failed
        self.error: Optional[str] = None
        self.done = threading.Event()
        self.length = 0                  # tokens materialized in pages
        self.next_token = -1             # newest token the host has read
        self.table: Optional[np.ndarray] = None
        self.slot = -1
        self.at = 0
        self.todo: List[int] = []
        self.fresh = self.block = None
        self.states: List[np.ndarray] = []
        self.confs: List[np.ndarray] = []
        self.blocks: List[Dict] = []
        self.t_submit = time.perf_counter()
        self.t_admit: Optional[float] = None
        self.t_first: Optional[float] = None
        self.t_done: Optional[float] = None
        # the submitter's trace if it submits from inside a span (an
        # RPC handler, a client's own span), else a trace of its own
        ctx = None if _trace is None else _trace.current_context()
        self.trace_id: Optional[str] = None if _trace is None \
            else ctx.trace_id if ctx is not None else _trace.new_trace_id()

    @property
    def ttft_s(self) -> Optional[float]:
        return None if self.t_first is None else self.t_first - self.t_submit

    @property
    def latency_s(self) -> Optional[float]:
        return None if self.t_done is None else self.t_done - self.t_submit

    def record_span(self, name: str, t_end: float, **attrs) -> None:
        """``name`` from submit to ``t_end`` in this request's trace
        (returns at once when tracing is off)."""
        if _trace is not None:
            ts, dur = _trace.clock_us(self.t_submit), t_end - self.t_submit
            # ptpu: lint-ok[PT-METRIC] forwarding shim; callers pass literals
            _trace.record_span(name, ts, dur * 1e6, self.trace_id,
                               request=self.id, **attrs)


class _Launch:
    """One program the device is given: a prefill, a decode step or a
    chunk launch.  ``rows`` are its requests in batch order (a chunk
    launch's: the decoding rows it carries), ``src`` (decode, chunk) the
    batch index each row had in the decode or chunk launch this one is
    queued behind, from which it takes its id on the device (−1: from
    the host), ``attrs`` what its span states, ``handle`` the model's
    launch once queued.  A block launch (a decode step of a model with a
    ``block_length``) has ``passes``: per row (its tile's start, the
    positions it reveals, whether it finishes its block, the block it
    starts from or None, whether it commits the block before it).  A
    chunk launch has ``chunk``: (the request whose prompt it continues,
    the chunk's start, its positions) and ``ride``, what the decode
    step span of its rows states (None when no row rides)."""

    __slots__ = ("kind", "rows", "src", "attrs", "handle", "passes",
                 "chunk", "ride")

    def __init__(self, kind: str, rows: List[Request], attrs: Dict,
                 src: Sequence[int] = (), passes: Sequence = (),
                 chunk=None, ride: Optional[Dict] = None):
        self.kind = kind                 # prefill|decode|chunk
        self.rows = rows
        self.src = src
        self.attrs = attrs
        self.handle = None
        self.passes = passes
        self.chunk = chunk
        self.ride = ride


class SwapTicket:
    """A pending hot-swap: the fully built replacement model plus the
    handshake back to the requester.  ``event`` fires once the decode
    loop has applied (or rolled back) the swap; ``report`` then holds
    the outcome — ``result`` (``ok``/``rolled_back``), the pointer-flip
    ``pause_s``, and which in-flight requests were re-prefilled."""

    __slots__ = ("model", "version", "inflight", "exported_at",
                 "event", "report")

    def __init__(self, model: DecoderModel, version: str, inflight: str,
                 exported_at: Optional[float]):
        self.model = model
        self.version = version
        self.inflight = inflight
        self.exported_at = exported_at
        self.event = threading.Event()
        self.report: Dict = {"result": "pending", "version": version,
                             "inflight": inflight}

    def wait(self, timeout: Optional[float] = None) -> Dict:
        if not self.event.wait(timeout):
            raise TimeoutError(f"swap to {self.version[:12]} not applied "
                               f"within {timeout}s")
        return dict(self.report)


class InferenceServer:
    """The continuous-batching decode loop around a
    :class:`~paddle_tpu.serving.model.DecoderModel` and a
    :class:`~paddle_tpu.serving.pagepool.PagePool`.

    With ``--rollout`` (default on) the server also speaks the
    zero-downtime train→serve protocol (``serving/rollout.py``):
    :meth:`request_swap` parks a fully built replacement model as a
    :class:`SwapTicket`; the decode loop applies it at a launch
    boundary with nothing in flight — ``drain`` finishes in-flight requests on the OLD model first
    (admissions pause), ``reprefill`` flips immediately and restarts
    in-flight generation from the prompt on the NEW model — so every
    response's tokens come from exactly one model.  ``--rollout=false``
    is the kill switch: no swap surface, ``/healthz`` and the 404 body
    byte-identical to the pre-rollout server."""

    def __init__(self, model: DecoderModel,
                 max_batch: Optional[int] = None,
                 n_pages: Optional[int] = None,
                 page_size: Optional[int] = None,
                 continuous: Optional[bool] = None,
                 snapshot_path: Optional[str] = None,
                 rollout: Optional[bool] = None,
                 model_version: str = "unversioned",
                 model_exported_at: Optional[float] = None):
        self.model = model
        self.max_batch = int(FLAGS.get("serve_max_batch")
                             if max_batch is None else max_batch)
        n_pages = int(FLAGS.get("kv_pool_pages")
                      if n_pages is None else n_pages)
        page_size = int(FLAGS.get("kv_page_size")
                        if page_size is None else page_size)
        self.continuous = bool(FLAGS.get("serve_continuous")
                               if continuous is None else continuous)
        enforce(self.max_batch >= 1,
                f"serve_max_batch must be >= 1, got {self.max_batch}")
        # the one decode shape: sequential mode (the kill switch) is
        # batch width 1 of the same loop
        self._width = self.max_batch if self.continuous else 1
        self.snapshot_path = snapshot_path
        self.pool = self._make_pool(n_pages, page_size, snapshot_path)
        # where a mamba plan's sequence keeps its state: a slot a row of
        # the decode batch, the last for idle rows (a conv plan's lies at
        # its first page: PERF.md §7)
        self._slotted = bool(model.mamba_layers)
        self._scratch_slot = self._width
        self._free_slots = list(range(self._width))
        # the cache pools the model's plan needs, as new_pools gave
        # them: every launch hands them on and donates their arrays
        self._pools = model.new_pools(n_pages, page_size, self._n_slots(),
                                      self._width)
        # one page-table width for every request: enough pages to cover
        # a max_context-long sequence (or the whole pool if smaller)
        self.max_pages = min(self.pool.capacity,
                             self.pool.pages_needed(model.cfg.max_context))
        self._cond = named_condition("serve.admission")
        self._queue: collections.deque = collections.deque()
        self._active: List[Request] = []
        # admitted requests whose prompt is prefilled a chunk a launch
        # (the model's ``chunk``), in admission order: the first has its
        # chunks launched, the others wait for theirs
        self._chunking: collections.deque = collections.deque()
        # launches queued on the device and not collected yet, oldest
        # first: the decode thread's alone, at most two (one being
        # collected, one ahead of the host)
        self._inflight: collections.deque = collections.deque()
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        self._httpd = None
        self._http_thread: Optional[threading.Thread] = None
        self.served = 0
        self.generated_tokens = 0
        self.rollout_enabled = bool(FLAGS.get("rollout")
                                    if rollout is None else rollout)
        self.model_version = model_version
        self.model_exported_at = model_exported_at
        self.rollout_state = "serving"     # serving|swapping|rolled_back
        self.last_swap_error: Optional[str] = None
        self._pending_swap: Optional[SwapTicket] = None
        # bound once: a registry look-up (name, help, lock) per token
        # or per step is host time inside the decode loop
        self._m_tokens = None if _counter is None else _counter(
            "serve_tokens_generated", "tokens generated across requests")
        self._m_batch = None if _gauge is None else _gauge(
            "serve_batch_size",
            "requests in the most recent inference launch")
        # the evidence for releasing pages by layer type (ROADMAP M7):
        # one page table serves every layer, so a window layer keeps
        # what it will never read again
        self._m_behind = None if _gauge is None else _gauge(
            "serve_kv_pages_behind_window",
            "layer-pages (one layer's K and V of one page) that the "
            "rows of the latest decode step hold wholly behind a "
            "window layer's window")
        self._m_cache_row = None if _gauge is None else _gauge(
            "serve_cache_bytes_per_token",
            "what one position holds in the cache pools over all "
            "layers, as stored")
        self._m_state = None if _gauge is None else _gauge(
            "serve_state_bytes_per_sequence",
            "what one sequence holds over the layers that keep a "
            "fixed-size state (conv, mamba), whatever its length")
        self._m_slots = None if _gauge is None else _gauge(
            "serve_state_slots_used",
            "state slots held by admitted requests")
        self._publish_cache_sizes(model)
        self._m_launch = None if _counter is None else _counter(
            "serve_launch_total",
            "programs queued on the device by kind (decode | prefill) "
            "and by what the device had when they were queued: behind "
            "= a launch the host had not collected yet, idle = nothing")
        self._m_passes = None if _counter is None else _counter(
            "serve_block_passes_total",
            "block launch rows, by kind: denoise (a row computed: it "
            "reveals positions of its block) | commit (a row that also "
            "writes the finished block before it's K/V)")
        self._m_emitted = None if _gauge is None else _gauge(
            "serve_tokens_per_launch",
            "tokens the most recent block launch emitted when collected")
        self._m_discarded = None if _counter is None else _counter(
            "serve_rows_discarded_total",
            "rows a decode launch computed for a request that EOS had "
            "already ended (the host learns of an EOS one launch late)")
        self._m_stall = None if _counter is None else _counter(
            "serve_stall_total",
            f"launch periods longer than {STALL_S} s, by the phase of "
            "the turn that took most of it (admit | build | dispatch | "
            "fetch | emit)")
        # the turn's phases as they start, (phase, host clock), and the
        # collector's count at the turn before: see _note_period
        self._marks: List = []
        self._collections = 0

    # what a caller that knows a K and a V pool reaches for (the
    # benchmark's warm-up): the first and the last of the pools of the
    # layers that attend, which for a latent plan are the one pool whose
    # rows are both.  The states it does not know of the steps supply
    # (DecoderModel._pools_of)
    _k_pool = property(lambda self: self._pools[0])
    _v_pool = property(
        lambda self: self._pools[self.model.n_kv_pools - 1])

    def _publish_cache_sizes(self, model: DecoderModel) -> None:
        if self._m_cache_row is not None:
            self._m_cache_row.set(model.cache_bytes_per_token())
            self._m_state.set(model.state_bytes_per_sequence())

    @staticmethod
    def _make_pool(n_pages: int, page_size: int,
                   snapshot_path: Optional[str]) -> PagePool:
        """Fresh pool, or crash recovery from a prior snapshot: a valid
        snapshot restores and then RELEASES every orphaned table (the
        KV content died with the previous process); a torn one is
        refused and replaced by a fresh pool.  Either way the served
        pool verifies clean — never a torn page table."""
        if snapshot_path:
            try:
                pool = PagePool.restore(snapshot_path)
            except FileNotFoundError:
                pool = None
            except TornSnapshot as e:
                log.warning("pool snapshot refused (%s); starting fresh",
                            e)
                pool = None
            if pool is not None:
                enforce(pool.n_pages == n_pages
                        and pool.page_size == page_size,
                        f"pool snapshot geometry {pool.n_pages}x"
                        f"{pool.page_size} != configured {n_pages}x"
                        f"{page_size}")
                for owner in pool.owners():
                    pool.release(owner)
                pool.verify()
                return pool
        return PagePool(n_pages, page_size)

    # ------------------------------------------------------------ lifecycle
    def start(self) -> "InferenceServer":
        if self._thread is None:
            # every (B, T) prefill bucket is a compile: keep them
            # across restarts of the serving process
            ensure_compile_cache()
            self._stop = False
            self._thread = threading.Thread(
                target=self._loop, name=DECODE_THREAD_NAME, daemon=True)
            self._thread.start()
            self._publish_serving_info()
        return self

    def stop(self) -> None:
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=30.0)
        self.stop_http()
        # unblock every waiter; their requests will never run
        with self._cond:
            pending = list(self._queue) + list(self._active)
            self._queue.clear()
            self._active = []
            self._chunking.clear()
            swap, self._pending_swap = self._pending_swap, None
        for r in pending:
            self._release(r)
            r.state = "failed"
            r.error = "server stopped"
            r.done.set()
        if swap is not None:       # a parked swap never applies now
            swap.report.update(result="rolled_back",
                               error="server stopped")
            swap.event.set()

    def __enter__(self) -> "InferenceServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # ------------------------------------------------------------- clients
    def submit(self, prompt: Sequence[int],
               max_new_tokens: int = 16) -> Request:
        """Enqueue a generation request; returns immediately.  Rejects
        (raises) only what could NEVER run: an empty prompt, a sequence
        longer than ``max_context``, or a page-table need beyond the
        whole pool — a merely-busy pool is backpressure, not an error."""
        enforce(len(prompt) >= 1, "empty prompt")
        enforce(max_new_tokens >= 1,
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        total = self._positions(len(prompt), max_new_tokens)
        enforce(total <= self.model.cfg.max_context,
                f"prompt + max_new_tokens = {total} exceeds max_context "
                f"{self.model.cfg.max_context}")
        enforce(self.pool.pages_needed(total) <= self.max_pages,
                f"request needs {self.pool.pages_needed(total)} pages, "
                f"page tables hold {self.max_pages}")
        vocab = self.model.cfg.vocab
        enforce(all(0 <= int(t) < vocab for t in prompt),
                f"prompt token out of range [0, {vocab})")
        r = Request(prompt, max_new_tokens)
        with self._cond:
            enforce(not self._stop, "server is stopped")
            self._queue.append(r)
            self._publish_queue_locked()
            self._cond.notify_all()
        return r

    def result(self, r: Request, timeout: Optional[float] = None
               ) -> List[int]:
        """Block until a request finishes; returns its generated token
        ids (prompt excluded)."""
        if not r.done.wait(timeout):
            raise TimeoutError(f"{r.id}: no result within {timeout}s")
        if r.state != "done":
            raise RuntimeError(f"{r.id}: {r.error or r.state}")
        return list(r.tokens)

    def generate(self, prompt: Sequence[int], max_new_tokens: int = 16,
                 timeout: Optional[float] = None) -> List[int]:
        return self.result(self.submit(prompt, max_new_tokens), timeout)

    def stats(self) -> Dict[str, int]:
        with self._cond:
            q, a = len(self._queue), len(self._active)
            rollout = None
            if self.rollout_enabled:
                rollout = {"model_version": self.model_version,
                           "model_exported_at": self.model_exported_at,
                           "rollout_state": self.rollout_state,
                           "last_swap_error": self.last_swap_error}
        out = {"queue_depth": q, "active": a,
               "free_pages": self.pool.free_pages(),
               "used_pages": self.pool.used_pages(),
               "served": self.served,
               "generated_tokens": self.generated_tokens,
               "continuous": int(self.continuous),
               "max_batch": self.max_batch}
        if rollout is not None:
            # gated on the kill switch so --rollout=false keeps stats()
            # (and with it the /healthz body) byte-identical to the
            # pre-rollout server
            out.update(rollout)
        slo_ms = float(FLAGS.get("serve_slo_ms") or 0.0)
        if slo_ms > 0 and _registry is not None:
            # WINDOWED p99 (last 60s), not the lifetime reservoir: a
            # recovered server must stop advertising a stale bad p99
            # forever.  Gated on the flag (default 0) so the default
            # /healthz body stays byte-identical.
            h = _registry.find("serve_ttft_seconds")
            p99 = h.window_quantile(0.99, 60.0) \
                if h is not None and hasattr(h, "window_quantile") \
                else None
            out["ttft_p99_ms"] = None if p99 is None \
                else round(p99 * 1e3, 3)
            out["slo_met"] = int(p99 is None or p99 * 1e3 <= slo_ms)
        return out

    # ------------------------------------------------------------ hot swap
    def request_swap(self, model: DecoderModel,
                     version: str = "unversioned",
                     inflight: Optional[str] = None,
                     exported_at: Optional[float] = None) -> SwapTicket:
        """Park a fully built replacement model for the decode loop to
        apply at its next step boundary; returns the
        :class:`SwapTicket` to ``wait()`` on.  The model must already
        be built, verified, and probed — this method does NO loading
        (``rollout.swap_from_artifact`` is the full pipeline)."""
        enforce(self.rollout_enabled,
                "rollout disabled (--rollout=false): request_swap refused")
        inflight = str(FLAGS.get("rollout_inflight")
                       if inflight is None else inflight)
        enforce(inflight in ("drain", "reprefill"),
                f"unknown in-flight policy {inflight!r} "
                "(expected 'drain' or 'reprefill')")
        # same architecture is the contract (continuous training swaps
        # weights, not shapes): pools, page tables, and every compiled
        # shape bucket carry over only because the config is identical
        enforce(model.cfg == self.model.cfg,
                f"swap model config {model.cfg} != serving config "
                f"{self.model.cfg}")
        ticket = SwapTicket(model, version, inflight, exported_at)
        with self._cond:
            enforce(not self._stop, "server is stopped")
            enforce(self._pending_swap is None,
                    "a swap is already in progress")
            self._pending_swap = ticket
            self.rollout_state = "swapping"
            ticket.report["inflight_at_request"] = len(self._active)
            self._cond.notify_all()
        self._publish_serving_info()
        return ticket

    def record_swap_failure(self, reason: str) -> None:
        """Record a swap that failed BEFORE a ticket was ever parked
        (artifact verify/load/probe ran off-thread and rolled back).
        The old model keeps serving; ``/healthz`` carries the reason."""
        with self._cond:
            self.rollout_state = "rolled_back"
            self.last_swap_error = reason
        self._publish_serving_info()

    def _apply_swap_locked(self, ticket: SwapTicket) -> List[Request]:
        """Apply a parked swap at a launch boundary with nothing in
        flight (``_cond`` held).  Returns the in-flight requests to re-prefill on the new
        model (``reprefill`` policy; empty under ``drain``, which only
        gets here with no actives).  Failure to stand up the new pools
        rolls back — the old model/pools were never unhooked."""
        t0 = time.perf_counter()
        old_version = self.model_version
        try:
            pools = ticket.model.new_pools(self.pool.n_pages,
                                           self.pool.page_size,
                                           self._n_slots(), self._width)
        except Exception as e:  # noqa: BLE001 - rollback, keep serving
            self._pending_swap = None
            self.rollout_state = "rolled_back"
            self.last_swap_error = f"pool standup: {type(e).__name__}: {e}"
            ticket.report.update(result="rolled_back",
                                 error=self.last_swap_error)
            if _counter is not None:
                _counter("rollout_swap_total",
                         "hot-swap attempts by outcome").inc(
                    result="rolled_back")
            log.error("swap to %s rolled back (%s)", ticket.version[:12],
                      self.last_swap_error)
            ticket.event.set()
            return []
        reprefill: List[Request] = []
        if ticket.inflight == "reprefill" and self._active:
            # restart in-flight generation from the prompt on the NEW
            # model: drop every old-model token (exactly-one-model
            # semantics), keep the page tables — fresh pools mean the
            # prompt K/V is rewritten by the re-prefill
            for r in self._active:
                r.tokens.clear()
                r.length = 0
                r.next_token = -1
                r.t_first = None
            reprefill = list(self._active)
            self._chunking.clear()
            ticket.report["reprefilled"] = [r.id for r in reprefill]
        self.model = ticket.model
        self._pools = pools
        self._publish_cache_sizes(ticket.model)
        self.model_version = ticket.version
        self.model_exported_at = ticket.exported_at
        self.rollout_state = "serving"
        self.last_swap_error = None
        self._pending_swap = None
        pause_s = time.perf_counter() - t0
        ticket.report.update(result="ok", pause_s=pause_s)
        if _counter is not None:
            _counter("rollout_swap_total",
                     "hot-swap attempts by outcome").inc(result="ok")
            _histogram("rollout_swap_pause_seconds",
                       "decode-loop pause for the atomic pointer flip "
                       "(pool standup + in-flight bookkeeping; the "
                       "model build/verify/probe ran off-thread)"
                       ).observe(pause_s)
            g = _gauge("rollout_model_version",
                       "1 for the live artifact digest, 0 for retired "
                       "ones (info gauge keyed by digest label)")
            if old_version:
                g.set(0.0, digest=old_version)
            g.set(1.0, digest=ticket.version)
        log.info("hot-swapped model %s -> %s (pause %.1f ms, %d "
                 "re-prefilled)", old_version[:12], ticket.version[:12],
                 pause_s * 1e3, len(reprefill))
        ticket.event.set()
        return reprefill

    def _publish_serving_info(self) -> None:
        """Push model version + rollout state into the fleet identity so
        every frame this process pushes carries them (``/fleet/topology``
        and the ``--watch`` version column)."""
        if _fleet is None or not self.rollout_enabled:
            return
        _fleet.set_serving_info(version=self.model_version,
                                state=self.rollout_state,
                                exported_at=self.model_exported_at,
                                error=self.last_swap_error)

    # ---------------------------------------------------------- decode loop
    def _loop(self) -> None:
        while True:
            with self._cond:
                while not self._stop and not self._queue \
                        and not self._active and not self._inflight \
                        and self._pending_swap is None:
                    self._cond.wait(0.05)
                if self._stop:
                    break
            # there is work: whatever this thread does for it from here
            # to the next wait lies under one span
            with _span("serve_loop_iter"):
                try:
                    ran = self._turn()
                except Exception as e:  # noqa: BLE001 - one bad batch
                    # must not kill the serve loop: fail its requests,
                    # recycle their pages, keep serving the queue
                    self._fail_active(e)
                    ran = True
                if ran and self.snapshot_path:
                    with _span("serve_snapshot"):
                        self.pool.snapshot(self.snapshot_path)
        self._drain()        # stopped: nothing stays queued on the device

    def _turn(self) -> bool:
        """One turn of the loop is one launch's span: from the collect
        of the launch before it to its own.  Under it the NEXT launch is
        built and queued behind it on the device, then its own ids are
        collected and emitted; from idle its own build and dispatch come
        first.  So the device goes from one launch into the next while
        the host works a launch ahead, and the spans tile this thread's
        time in launch order."""
        idle = not self._inflight
        self._marks = []
        cpu = time.thread_time()
        cur = self._next_launch() if idle else self._inflight[0]
        if cur is None:
            return False
        with self._spans(cur) as step:
            if idle:
                self._launch(cur)
            ahead = self._next_launch()
            if ahead is not None:
                self._launch(ahead)
            self._collect(step)
        self._note_period(cur.kind, time.thread_time() - cpu)
        return True

    @staticmethod
    @contextlib.contextmanager
    def _spans(launch: _Launch):
        """The launch's span over its turn: ``serve_prefill`` or
        ``serve_decode_step``; a chunk launch is both over the same
        period, ``serve_prefill`` with its chunk's counts and, where rows
        ride in it, ``serve_decode_step`` with theirs.  Yields the span
        the collect states the rows' outcome on."""
        if launch.kind == "decode":
            with _span("serve_decode_step", **launch.attrs) as step:
                yield step
        elif launch.ride is None:
            with _span("serve_prefill", **launch.attrs) as step:
                yield step
        else:
            with _span("serve_prefill", **launch.attrs), \
                    _span("serve_decode_step", **launch.ride) as step:
                yield step

    def _note_period(self, kind: str, cpu_s: float) -> None:
        """The turn that just ended, if it took longer than
        :data:`STALL_S`: one tick of ``serve_stall_total`` under the
        phase that took most of it and one line with what tells a
        sleeping thread from a running one (its CPU seconds) and a
        collection from neither.  One serve run in five meets such a
        period of 2-4.5 s with tracing off, where no span says in which
        phase (PERF.md §6)."""
        marks = self._marks + [("", time.perf_counter())]
        collections = sum(g["collections"] for g in gc.get_stats())
        wall = marks[-1][1] - marks[0][1]
        if wall > STALL_S:
            took: Dict[str, float] = {}
            for (phase, t0), (_, t1) in zip(marks, marks[1:]):
                took[phase] = took.get(phase, 0.0) + (t1 - t0)
            phase = max(took, key=took.get)
            if self._m_stall is not None:
                self._m_stall.inc(phase=phase)
            log.warning(
                "serve stall: the launch period of a %s took %.3f s, "
                "%.3f s of it in %s (this thread ran for %.3f s of it; "
                "%d gc collections since the period before)", kind, wall,
                took[phase], phase, cpu_s,
                collections - self._collections)
        self._collections = collections

    def _next_launch(self) -> Optional[_Launch]:
        """What to queue now, behind whatever is in flight: a parked
        swap is applied first (only with nothing in flight, so no launch
        straddles the flip), then the admitted are prefilled (a prompt
        longer than the model's chunk joins the prompts prefilled a
        chunk a launch), then the next chunk with the active rows riding
        in it, else the active rows advance one token."""
        reprefill: List[Request] = []
        swapped = False
        self._marks.append(("admit", time.perf_counter()))
        with self._cond:
            if self._stop:
                return None
            pending = self._pending_swap
            if pending is not None and (pending.inflight == "reprefill"
                                        or not self._active):
                # the atomic pointer flip, at a launch boundary.  drain
                # policy only flips once the actives emptied; reprefill
                # flips now and restarts them below.  Either waits for
                # what the old model still runs to be collected
                if self._inflight:
                    return None
                reprefill = self._apply_swap_locked(pending)
                pending, swapped = None, True
            # a pending drain swap pauses admission: new requests
            # must first-run on the NEW model, and the flip waits
            # for the actives to finish on the old one
            admitted = [] if pending is not None \
                else self._admit_locked()
        if swapped:
            self._publish_serving_info()
        for r in admitted:
            r.record_span("serve_queue_wait", r.t_admit)
        whole = []
        for r in reprefill + admitted:
            if 0 < self.model.chunk < len(r.prompt):
                r.table = self._table(r)
                self._chunking.append(r)
            else:
                whole.append(r)
        if whole:
            return self._plan_prefill(whole)
        if self._chunking:
            return self._plan_chunk()
        return self._plan_decode()

    def _admit_locked(self) -> List[Request]:
        """Move requests queue → active while a batch slot and enough
        free pages exist.  Sequential mode (the kill switch) admits one
        request only when the batch is empty — single-request serving."""
        admitted: List[Request] = []
        with _span("serve_admit", queued=len(self._queue)):
            while self._queue \
                    and len(self._active) + len(admitted) < self._width:
                r = self._queue[0]
                try:
                    self.pool.alloc(r.id, self._positions(
                        len(r.prompt), r.max_new_tokens))
                except PagePoolExhausted:
                    break            # backpressure: retry after retires
                self._queue.popleft()
                if self._slotted:
                    r.slot = self._free_slots.pop()
                r.t_admit = time.perf_counter()
                r.state = "active"
                self._active.append(r)
                admitted.append(r)
        if admitted:
            self._publish_queue_locked()
            self._publish_slots()
        return admitted

    def _positions(self, prompt: int, budget: int) -> int:
        """Positions a request writes: its prompt and budget, up to the
        end of the last block where the model generates by blocks."""
        b = self.model.block_length or 1
        return -(-(prompt + budget) // b) * b

    def _queued(self) -> str:
        """``behind`` a launch the host has not collected, or on an
        ``idle`` device (start, after a drain, after a lone prefill)."""
        return "behind" if self._inflight else "idle"

    def _prefilled(self, r: Request) -> int:
        """The prompt positions a prefill runs: all of them, or the
        whole blocks of a model that generates by blocks."""
        b = self.model.block_length
        return len(r.prompt) // b * b if b else len(r.prompt)

    def _plan_prefill(self, admitted: List[Request]) -> _Launch:
        """One packed launch for every request admitted this round;
        produces each request's first generated token (TTFT), or, where
        the model generates by blocks, sets each request's first block
        (:meth:`_start_blocks`)."""
        t_pad = max(map(self._prefilled, admitted))
        # bucket the pad length: bounded set of compiled prefill shapes
        t_pad = max(-(-t_pad // 16) * 16, 16)
        t_pad = min(t_pad, self.model.cfg.max_context)
        prompt_tokens = sum(map(self._prefilled, admitted))
        for r in admitted:
            r.table = self._table(r)
            if self.model.block_length:
                self._start_blocks(r)
        return _Launch("prefill", admitted, dict(
            n=len(admitted), t_pad=t_pad, prompt_tokens=prompt_tokens,
            moe_tokens=prompt_tokens * self.model.routed_layers,
            conv_tokens=prompt_tokens * self.model.conv_layers,
            scan_tokens=prompt_tokens * self.model.mamba_layers,
            attn_pairs=self.model.attn_pairs(
                [self._prefilled(r) for r in admitted]),
            requests=",".join(r.id for r in admitted),
            queued=self._queued()))

    def _table(self, r: Request) -> np.ndarray:
        """``r``'s page table at the one width every request's has."""
        t = self.pool.table_of(r.id)
        return np.array(t + [SCRATCH_PAGE] * (self.max_pages - len(t)),
                        np.int32)

    def _plan_decode(self) -> Optional[_Launch]:
        """The active rows advance in a single fixed-width paged-
        attention launch: one token a row, or, where the model generates
        by blocks, one pass over each row's block (:meth:`_plan_blocks`).
        A row of the decode launch in flight is fed from it on the
        device, unless that launch ends it by its budget; whether it
        ended by EOS the host learns one launch late, and such a row
        rides along dead (:meth:`_collect` drops its token).  A row
        whose prefill is in flight joins at the launch after this one,
        from the host."""
        if self.model.block_length:
            return self._plan_blocks()
        behind = self._inflight[-1] if self._inflight else None
        flying = {} if behind is None \
            else {id(r): i for i, r in enumerate(behind.rows)}
        ending = behind.chunk[0] if behind is not None and behind.chunk \
            else None
        rows, src = [], []
        for r in self._active:
            i = flying.get(id(r), -1)
            # a prompt not yet whole in its pages, or whose last chunk
            # is in flight, has no token to feed
            if r.length < len(r.prompt) or r is ending \
                    or i >= 0 and (behind.kind == "prefill"
                                   or len(r.tokens) + 1 >= r.max_new_tokens):
                continue
            rows.append(r)
            src.append(i)
        if not rows:
            return None
        enforce(len(rows) <= self._width,
                f"active {len(rows)} exceeds batch width {self._width}")
        # what the step attends over: each row's length INCLUDING the
        # token it feeds, and the pages those lengths occupy
        fed = [r.length + 1 for r in rows]
        return _Launch("decode", rows, dict(
            batch=len(rows), live_tokens=sum(fed),
            live_pages=sum(map(self.pool.pages_needed, fed)),
            attended_tokens=self.model.attended_tokens(fed),
            state_rows=len(rows) * self.model.state_layers,
            queued=self._queued()), src)

    def _plan_chunk(self) -> _Launch:
        """The next chunk of the oldest prompt still being prefilled, with
        every row :meth:`_plan_decode` would advance riding in it: one
        launch advances both."""
        r = self._chunking[0]
        start = r.length
        n = min(self.model.chunk, len(r.prompt) - start)
        ride = self._plan_decode()
        rows, src = (ride.rows, ride.src) if ride is not None else ([], [])
        pairs = self.model.attn_pairs([start + n]) \
            - self.model.attn_pairs([start])
        return _Launch("chunk", rows, dict(
            n=1, t_pad=self.model.chunk, prompt_tokens=n,
            moe_tokens=n * self.model.routed_layers,
            conv_tokens=n * self.model.conv_layers,
            scan_tokens=n * self.model.mamba_layers, attn_pairs=pairs,
            requests=r.id, queued=self._queued(), riding=len(rows),
            chunk_start=start), src, chunk=(r, start, n),
            ride=None if ride is None else ride.attrs)

    def _start_blocks(self, r: Request) -> None:
        """``r``'s first block: it starts at its prompt's last whole
        block, carrying the ids past it unmasked."""
        at = self._prefilled(r)
        fresh = np.full((self.model.block_length,), -1, np.int32)
        fresh[:len(r.prompt) - at] = r.prompt[at:]
        r.block, r.states, r.confs, r.blocks = None, [], [], []
        self._enter_block(r, at, fresh)

    def _enter_block(self, r: Request, at: int, fresh: np.ndarray) -> None:
        """``r``'s block at ``at``, starting from ``fresh``: its
        denoising steps, then its commit (a 0) unless it is the
        request's last block.  :meth:`_plan_blocks` never launches the
        commit alone: it rides in the next block's first step."""
        last = self._positions(len(r.prompt), r.max_new_tokens) \
            - self.model.block_length
        r.at, r.fresh = at, fresh
        r.todo = list(reveal_schedule(int((fresh < 0).sum()),
                                      self.model.cfg.denoise_steps))
        if at < last:
            r.todo.append(0)

    def _plan_blocks(self) -> Optional[_Launch]:
        """One block launch: every active row with a pass to go takes
        its next one, a row whose next pass is its block's commit in
        one row with the next block's first denoising step.  A row's
        block comes from the host where the block starts or where the
        launch before it was collected, and else from the launch in
        flight, on the device; a committing row's finished block comes
        from there, and the block after it is masked on the device."""
        b = self.model.block_length
        behind = self._inflight[-1] if self._inflight else None
        flying = {} if behind is None or behind.kind != "decode" \
            else {id(r): i for i, r in enumerate(behind.rows)}
        rows, src, passes = [], [], []
        for r in self._active:
            if not r.todo:              # its last pass is launched
                continue
            start, fed = r.at, r.fresh
            src.append(-1 if fed is not None else flying.get(id(r), -1))
            reveal = r.todo.pop(0)
            commit = reveal == 0
            if commit:                  # _enter_block leaves a next block
                self._enter_block(r, r.at + b, np.full((b,), -1, np.int32))
                reveal = r.todo.pop(0)
            r.fresh = None
            finishes = not r.todo or r.todo[0] == 0
            passes.append((start, reveal, finishes, fed, commit))
            rows.append(r)
            if not r.todo and r.at + b < self._positions(
                    len(r.prompt), r.max_new_tokens):
                self._enter_block(r, r.at + b, np.full((b,), -1, np.int32))
        if not rows:
            return None
        enforce(len(rows) <= self._width,
                f"active {len(rows)} exceeds batch width {self._width}")
        # each row's kernel length: its tile of 2B, from its start
        fed = [at + 2 * b for at, *_ in passes]
        return _Launch("decode", rows, dict(
            batch=len(rows), live_tokens=sum(fed),
            live_pages=sum(map(self.pool.pages_needed, fed)),
            attended_tokens=self.model.attended_tokens(fed), state_rows=0,
            queued=self._queued(), block_rows=len(rows),
            commit_rows=sum(p[4] for p in passes),
            revealed=sum(p[1] for p in passes)), src, passes)

    def _launch(self, launch: _Launch) -> None:
        """Build the launch's inputs and queue it on the device."""
        rows, n = launch.rows, len(launch.rows)
        self._marks.append(("build", time.perf_counter()))
        if self._m_launch is not None:
            self._m_launch.inc(kind=launch.kind,
                               queued=launch.attrs["queued"])
        if launch.kind == "prefill":
            with _span("serve_step_build"):
                tokens = np.zeros((n, launch.attrs["t_pad"]), np.int32)
                for i, r in enumerate(rows):
                    r.length = self._prefilled(r)
                    tokens[i, :r.length] = r.prompt[:r.length]
                lengths = np.array([r.length for r in rows], np.int32)
                tables = np.stack([r.table for r in rows])
                slots = self._slots_kw(rows, n)
            # testing knob: a seeded-slow artifact (manifest
            # debug_prefill_delay_ms) inflates TTFT here — inside the
            # TTFT stamp, before the launch — so a canary bake has a
            # deterministic latency regression to detect.  Swap probes
            # call model.prefill directly and never pay it.
            delay = getattr(self.model, "debug_prefill_delay_s", 0.0)
            if delay:
                time.sleep(delay)
            self._marks.append(("dispatch", time.perf_counter()))
            launch.handle = self.model.launch_prefill(
                *self._pools, tokens, lengths, tables, **slots)
        elif launch.passes:
            self._launch_blocks(launch)
            return
        else:
            with _span("serve_step_build"):
                tokens, tables, lengths, active, src, slots = \
                    self._rows_in(launch)
                if launch.chunk is not None:
                    r, start, c = launch.chunk
                    chunk = np.zeros((self.model.chunk,), np.int32)
                    chunk[:c] = r.prompt[start:start + c]
                    r.length = start + c
                    if r.length == len(r.prompt):
                        self._chunking.popleft()
            if self._m_batch is not None:
                self._m_batch.set(n)
            if self._m_behind is not None and self.model.cfg.window:
                self._m_behind.set(self.model.pages_behind_window(
                    lengths[:n].tolist(), self.pool.page_size))
            # rows with ``src`` >= 0 take their ids from the launch this
            # one is queued behind, on the device
            prev = self._inflight[-1].handle if max(launch.src, default=-1) \
                >= 0 else None
            self._marks.append(("dispatch", time.perf_counter()))
            if launch.chunk is None:
                launch.handle = self.model.launch_decode(
                    *self._pools, tokens, tables, lengths, active, prev,
                    src, **slots)
            else:
                launch.handle = self.model.launch_chunk(
                    *self._pools, chunk, np.array([start, c], np.int32),
                    r.table, r.slot if self._slotted else r.table[0],
                    tokens, tables, lengths, active, prev, src, **slots)
        self._inflight.append(launch)

    def _rows_in(self, launch: _Launch):
        """A decode step's inputs for the launch's rows at the batch's
        width, each row's length advanced by the token it feeds: (fed
        ids, page tables, lengths, active, ``src``, the slots keyword)."""
        rows, n, b = launch.rows, len(launch.rows), self._width
        tokens = np.zeros((b,), np.int32)
        src = np.full((b,), -1, np.int32)
        lengths = np.ones((b,), np.int32)
        active = np.zeros((b,), bool)
        tables = np.full((b, self.max_pages), SCRATCH_PAGE, np.int32)
        slots = self._slots_kw(rows, b)
        for i, r in enumerate(rows):
            if launch.src[i] < 0:
                tokens[i] = r.next_token
            r.length += 1
            lengths[i] = r.length
            tables[i] = r.table
        src[:n] = launch.src
        active[:n] = True
        return tokens, tables, lengths, active, src, slots

    def _launch_blocks(self, launch: _Launch) -> None:
        """Build a block launch's inputs and queue it on the device."""
        rows, n, b = launch.rows, len(launch.rows), self.model.block_length
        with _span("serve_step_build"):
            w = self._width
            blocks = np.full((w, b), -1, np.int32)
            src = np.full((w,), -1, np.int32)
            starts = np.zeros((w,), np.int32)
            active = np.zeros((w,), bool)
            reveal = np.zeros((w,), np.int32)
            commit = np.zeros((w,), bool)
            tables = np.full((w, self.max_pages), SCRATCH_PAGE, np.int32)
            for i, (r, (at, rv, _, fed, cm)) in enumerate(
                    zip(rows, launch.passes)):
                if launch.src[i] < 0:
                    blocks[i] = r.block if fed is None else fed
                starts[i], reveal[i], tables[i] = at, rv, r.table
                commit[i] = cm
                r.length = at + b * (1 + cm)
            src[:n] = launch.src
            active[:n] = True
        if self._m_batch is not None:
            self._m_batch.set(n)
        if self._m_passes is not None:
            self._m_passes.inc(n, kind="denoise")
            commits = launch.attrs["commit_rows"]
            if commits:
                self._m_passes.inc(commits, kind="commit")
        # rows with ``src`` >= 0 take their blocks from the launch this
        # one is queued behind, on the device
        prev = self._inflight[-1].handle if max(launch.src) >= 0 else None
        self._marks.append(("dispatch", time.perf_counter()))
        launch.handle = self.model.launch_block_step(
            *self._pools, blocks, tables, starts, active, reveal, commit,
            prev, src)
        self._inflight.append(launch)

    def _collect(self, step) -> None:
        """Wait for the oldest launch in flight, bring its ids back and
        emit them; ``step`` is its span.  A row whose request ended
        while the launch was queued (EOS, learned a launch late) is
        dropped: not emitted, not counted."""
        launch = self._inflight.popleft()
        handle, launch.handle = launch.handle, None
        self._marks.append(("fetch", time.perf_counter()))
        if launch.passes:
            after, conf, _, routed = self.model.collect_block_step(handle)
            step.set(**routed)
            with _span("serve_step_emit"):
                self._marks.append(("emit", time.perf_counter()))
                self._emit_blocks(launch, after, conf, step)
            return
        rows, firsts = launch.rows, 0   # the last ``firsts`` rows' token
        if launch.kind == "prefill":    # is the first after its prompt
            ids, _ = self.model.collect_prefill(handle)
            if self.model.block_length:     # tokens come from blocks
                return
            firsts = len(rows)
        elif launch.kind == "chunk":
            ids, token, _ = self.model.collect_chunk(handle)
            r, start, n = launch.chunk
            ids = ids[:len(rows)]
            if start + n == len(r.prompt):
                rows, ids, firsts = rows + [r], np.append(ids, token), 1
        else:
            ids, _, routed = self.model.collect_decode(handle)
            step.set(**routed)      # experts_hit, expert_load_max
        with _span("serve_step_emit"):
            now = time.perf_counter()
            self._marks.append(("emit", now))
            live = [(r, t, k >= len(rows) - firsts) for k, (r, t)
                    in enumerate(zip(rows, ids.tolist()))
                    if r.state == "active"]
            self._count_tokens(len(live))
            for r, token, first in live:
                if first:
                    r.t_first = now
                    if _histogram is not None:
                        _histogram("serve_ttft_seconds",
                                   "submit-to-first-token latency"
                                   ).observe(now - r.t_submit)
                self._emit_token(r, token)
            dead = len(rows) - len(live)
            if dead:
                if self._m_discarded is not None:
                    self._m_discarded.inc(dead)
                # a chunk launch's span states its riding rows alone
                step.set(batch=len(live) - firsts * (launch.kind == "chunk"),
                         discarded=dead)

    def _emit_blocks(self, launch: _Launch, after: np.ndarray,
                     conf: np.ndarray, step) -> None:
        """A block launch's rows as collected: each live row's block
        becomes what the step left (kept in the block's states, with its
        confidences), a commit marks the block the row finished
        committed (collects are in launch order: the newest logged) and
        starts the new block's states from the all-masked block, and a
        block its last denoising step finished is logged and its tokens
        emitted."""
        b = self.model.block_length
        emitted = live = 0
        for i, r in enumerate(launch.rows):
            if r.state != "active":
                continue
            live += 1
            at, _, finishes, fed, commit = launch.passes[i]
            if commit:
                r.blocks[-1]["committed"] = True
                at, fed = at + b, np.full((b,), -1, np.int32)
            if fed is not None:
                r.states, r.confs = [fed], []
            r.states.append(after[i].copy())
            r.confs.append(conf[i].copy())
            r.block = after[i].copy()
            if finishes:
                emitted += self._emit_block(r, at)
        self._count_tokens(emitted)
        if self._m_emitted is not None:
            self._m_emitted.set(emitted)
        dead = len(launch.rows) - live
        if dead and self._m_discarded is not None:
            self._m_discarded.inc(dead)
        step.set(batch=live, emitted=emitted,
                 **({"discarded": dead} if dead else {}))

    def _emit_block(self, r: Request, at: int) -> int:
        """``r``'s block at ``at`` is finished: log it and emit its
        generated positions in order until the request ends.  → the
        tokens emitted."""
        r.blocks.append({"start": at,
                         "states": [a.tolist() for a in r.states],
                         "confs": [c.tolist() for c in r.confs],
                         "committed": False})
        if r.t_first is None:
            r.t_first = time.perf_counter()
            if _histogram is not None:
                _histogram("serve_ttft_seconds",
                           "submit-to-first-token latency"
                           ).observe(r.t_first - r.t_submit)
        n = 0
        for token in r.block[max(len(r.prompt) - at, 0):].tolist():
            self._emit_token(r, token)
            n += 1
            if r.state != "active":
                break
        return n

    def _drain(self) -> None:
        """Collect whatever is still in flight, outside its span: after
        a stop or a failed turn.  A launch that cannot be collected is
        dropped."""
        while self._inflight:
            try:
                self._collect(_NO_SPAN)
            except Exception:  # noqa: BLE001 - the failure is the
                # caller's to report; the loop must end with no launch
                log.exception("a launch in flight could not be collected")

    def _fail_active(self, e: Exception) -> None:
        """A turn raised: what the device still has is collected first
        (a token it made belongs to its request), then every request
        still active fails and its pages recycle."""
        log.exception("decode loop error; failing %d in-flight "
                      "request(s)", len(self._active))
        self._drain()
        with self._cond:
            failed, self._active = self._active, []
            self._chunking.clear()
        for r in failed:
            self._release(r)
            r.state = "failed"
            r.error = f"{type(e).__name__}: {e}"
            r.done.set()
            if _histogram is not None:
                # unit events: window_rate = failures/s — the
                # canary bake's error-rate signal and the
                # --slo rate-objective source
                _histogram("serve_request_failures",
                           "failed requests as unit events "
                           "(windowed rate = failures/sec)"
                           ).observe(1.0)

    def _count_tokens(self, n: int) -> None:
        """``n`` tokens came back from one launch."""
        self.generated_tokens += n
        if self._m_tokens is not None:
            self._m_tokens.inc(n)

    def _emit_token(self, r: Request, token: int) -> None:
        """Record one generated token, of a decode step or of a finished
        block; finish the request on EOS or the token budget, releasing
        its pages for immediate recycling."""
        r.tokens.append(token)
        r.next_token = token
        if token == self.model.cfg.eos_id \
                or len(r.tokens) >= r.max_new_tokens:
            self._finish(r)

    def _n_slots(self) -> Optional[int]:
        return self._width + 1 if self._slotted else None

    def _slots_kw(self, rows: List[Request], b: int) -> Dict:
        """The ``slots`` a launch of ``b`` rows hands the step (idle rows
        the scratch slot's), or nothing where the plan keeps no state in
        slots."""
        if not self._slotted:
            return {}
        slots = np.full((b,), self._scratch_slot, np.int32)
        slots[:len(rows)] = [r.slot for r in rows]
        return {"slots": slots}

    def _release(self, r: Request) -> None:
        """A request's pages and slot go back: it finished or failed."""
        self.pool.release(r.id)
        if r.slot >= 0:          # released once: it finished or failed
            self._free_slots.append(r.slot)
            self._publish_slots()

    def _publish_slots(self) -> None:
        if self._m_slots is not None:
            self._m_slots.set(self._width - len(self._free_slots))

    def _finish(self, r: Request) -> None:
        r.t_done = time.perf_counter()
        r.state = "done"
        self._release(r)
        with self._cond:
            if r in self._active:
                self._active.remove(r)
            self._cond.notify_all()
        self.served += 1
        if _histogram is not None:
            _histogram("serve_request_seconds",
                       "submit-to-last-token latency").observe(
                r.latency_s)
            _counter("serve_requests", "requests served").inc()
        r.record_span("serve_request", r.t_done, prompt=len(r.prompt),
                      tokens=len(r.tokens),
                      ttft_ms=round(r.ttft_s * 1e3, 3))
        r.done.set()

    def _publish_queue_locked(self) -> None:
        if _gauge is not None:
            _gauge("serve_queue_depth",
                   "requests waiting for admission").set(len(self._queue))

    # --------------------------------------------------------- HTTP front
    def start_http(self, port: Optional[int] = None) -> int:
        """Serve ``POST /v1/generate`` + ``GET /healthz`` on
        ``--serve_bind`` (loopback unless explicitly opted out, same
        trust contract as ``--metrics_bind``).  Returns the bound port."""
        enforce(make_threading_server is not None,
                "observe.http unavailable: no HTTP front-end")
        if self._httpd is not None:
            return self._httpd.server_address[1]
        port = int(FLAGS.get("serve_port")) if port is None else int(port)
        host = resolve_bind_host("serve_bind")
        self._httpd = make_threading_server(host, port, _make_handler(self))
        self._httpd.daemon_threads = True
        self._http_thread = threading.Thread(
            target=self._httpd.serve_forever,
            kwargs={"poll_interval": 0.1},
            name=HTTP_THREAD_NAME, daemon=True)
        self._http_thread.start()
        bound = self._httpd.server_address[1]
        log.info("serving endpoint on http://%s:%d (/v1/generate /healthz)",
                 host, bound)
        return bound

    def stop_http(self) -> None:
        httpd, self._httpd = self._httpd, None
        t, self._http_thread = self._http_thread, None
        if httpd is not None:
            httpd.shutdown()
            httpd.server_close()
        if t is not None:
            t.join(timeout=5.0)


def _make_handler(server: InferenceServer):
    class _Handler(BaseHTTPRequestHandler):
        server_version = "paddle-tpu-serving"

        def _send(self, code: int, payload: Dict) -> None:
            data = json.dumps(payload).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def do_GET(self) -> None:  # noqa: N802 - stdlib API
            if self.path.split("?", 1)[0].rstrip("/") == "/healthz":
                self._send(200, dict(server.stats(), status="ok"))
            else:
                # /v1/swap only exists with rollout on — the kill
                # switch keeps this body byte-identical to pre-rollout
                paths = ["/v1/generate", "/healthz"]
                if server.rollout_enabled:
                    paths.append("/v1/swap")
                self._send(404, {"error": "unknown path",
                                 "paths": paths})

        def _do_swap(self) -> None:
            """POST /v1/swap {"artifact": dir[, "inflight": policy]} —
            the rolling coordinator's per-replica step.  Runs the full
            off-thread pipeline (verify → load → probe → flip) and
            returns the swap report; 500 carries a rolled-back report,
            so the coordinator halts without guessing."""
            try:
                n = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(n) or b"{}")
                from . import rollout as _rollout
                report = _rollout.swap_from_artifact(
                    server, body["artifact"],
                    inflight=body.get("inflight"))
                ok = report.get("result") in ("ok", "unchanged")
                if ok and body.get("reason"):
                    # a coordinator-driven ROLLBACK swap: the swap
                    # itself succeeded (back to the old artifact) but
                    # the reason — e.g. a failed canary bake — must
                    # land on /healthz as a rolled_back state
                    server.record_swap_failure(str(body["reason"]))
                    report = dict(report, reason=str(body["reason"]))
                self._send(200 if ok else 500, report)
            except BrokenPipeError:
                pass
            except Exception as e:  # noqa: BLE001 - bad request must
                self._send(400, {"error": str(e)})  # never kill serving

        def do_POST(self) -> None:  # noqa: N802 - stdlib API
            path = self.path.split("?", 1)[0].rstrip("/")
            if path == "/v1/swap" and server.rollout_enabled:
                self._do_swap()
                return
            if path != "/v1/generate":
                self._send(404, {"error": "unknown path"})
                return
            try:
                n = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(n) or b"{}")
                prompt = body["prompt"]
                max_new = int(body.get("max_new_tokens", 16))
                req = server.submit(prompt, max_new)
                tokens = server.result(req, timeout=60.0)
                self._send(200, {"id": req.id, "tokens": tokens,
                                 "ttft_ms": round(req.ttft_s * 1e3, 3),
                                 "latency_ms": round(
                                     req.latency_s * 1e3, 3)})
            except BrokenPipeError:      # client hung up mid-response
                pass
            except Exception as e:  # noqa: BLE001 - a bad request must
                self._send(400, {"error": str(e)})  # never kill serving

        def log_message(self, fmt: str, *args) -> None:
            log.debug("http %s", fmt % args)

    return _Handler
