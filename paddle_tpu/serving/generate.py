"""Generative serving: prefill/decode split scheduling over a decoder.

``GenerativeEngine`` extends :class:`~paddle_tpu.serving.engine.ServingEngine`
with the autoregressive workload class: requests are token prompts,
responses are token streams. The engine owns a fixed set of **batch
slots** — one shared KV-page bucket per slot batch — and splits work into
the two phases a builder's dict holds (``models/decoder.py``; the keys are
tabled in docs/SERVING.md "What a builder hands the engine"):

* **prefill** — queued requests are admitted into free slots at decode-
  chunk boundaries and prefilled as one slot-masked batch per prompt
  bucket (padded to the bucket length). The prefill writes the slot's KV
  pages, merges the slot's generation state, and produces the request's
  FIRST token — streamed immediately.
* **decode** — every active slot advances ``decode_chunk`` forwards per
  dispatch (a token each, or what the decode net's yield says: see
  **Yield** below) as ONE ``run_chained`` scan (the paged KV caches ride the scan
  carry, donation-proven, updated in place; sampling runs in-program so
  no host round-trip separates tokens). Sequences sit at *different
  positions* inside one batch — position is data, not shape, so every
  chunk reuses one executable per (phase, bucket). The
  ``serving_decode_recompiles_total`` guard turns any violation (a shape
  leaking into a cache key as KV grows) into a counted, logged event and
  a CI-gated metric.

**Yield.** What a decode dispatch gave its slots is read in one place
(:class:`_Yield`). A decode net says how its tokens come out: with nothing
said, one token a forward for every slot whose gate is open
(``next_token`` [slots, 1], the autoregressive builders); or, under
``yield``, ``tokens`` [slots, W] with ``count`` [slots, 1] (and
``revealed_at`` [slots, W]) a forward: a model that generates a block of
``block_length`` positions at a time (``models/sdar_moe.py``) carries the
block through several forwards, yields 0 tokens on those that reveal
positions and up to ``block_length`` on the one that commits the block. A
request's budget may end inside a block (the block's tail is dropped), a
deadline is checked after a dispatch whatever forward of a block it ended
on (the slot's block state is device state and is reseeded by the next
prefill), and such a model's prefill streams no token: its first tokens
come with its first committed block. Every token metric, the per-token
timing, the cache walk and the settle loop read the yield.

**One dispatch ahead.** The dispatch thread launches a turn (its bucket
prefills, then its decode chunk) while the decode chunk of the turn before
is still running, and only then fetches and settles that one
(``Executor.run*(return_numpy=FETCH_LATER)``): the device always has the
next program queued behind the running one, and settle, schedule, admit,
feed, bind and the runtime's launch and return latencies are off its
critical path. Same programs, same order on the device, same state, same
tokens. What the loop observes decides how far it looks ahead:

* a request whose budget ends inside a launched dispatch (one token a
  forward, so the host can count) leaves its slot at that launch: its gate
  is cleared on the device before the next launch and the slot may be
  seated again in the next turn. A stop only the tokens show (``eos_id``, a
  deadline, a block model's yield) is found at the settle, one dispatch
  late: that dispatch's tokens for the slot are dropped as the rest of a
  chunk past a stop is (``serving_lookahead_dropped_tokens_total``);
* a turn that needs fetched values before it can launch (a chunked prefill,
  a speculative verify, a prefix-cache copy-in or publish) first settles
  what is in flight and runs as the serial loop did: the drained case of
  the same loop;
* with slots free whose last answers are out and a queue that cannot fill
  them, the thread waits for newcomers (``await_newcomers``) as long as the
  chunk in flight leaves it: that chunk's expected end (the last decode
  chunk's wall, from when it became the device's oldest) less twice what a
  turn's launches took. So a caller that resubmits on completion is seated
  in the turn after, as in the serial loop (``serving_seat_lag_turns``); it
  comes within milliseconds, and the turn is then launched well before the
  chunk ends.

ISSUE 20 adds two composable phases on the same slot/bucket discipline:

* **prefix reuse + chunked prefill** — admission first matches the
  prompt against the content-hash :class:`~.prefix_cache.PrefixCache`;
  matched whole pages are COPIED into the slot's KV rows and only the
  suffix is prefilled, one ``prefill_chunk``-token slot-masked slice per
  scheduler iteration, interleaved with the resident decode chunks (the
  same path admits prompts longer than the largest bucket). The final
  slice samples the first token in-program and flips the slot's decode
  gate (the model's ``active_var``); completed prefills publish their pages.
* **speculative decoding** (``GenerationConfig.speculative``) — each
  round a host-side draft (prompt-lookup n-gram by default, swappable
  via ``engine.draft_fn``) proposes ``spec_k - 1`` tokens and the target
  verifies the whole chunk in ONE dispatch; ``spec_accept`` commits the
  longest agreeing prefix + bonus token in-program. Greedy speculative
  output is bit-exact vs non-speculative decode — the verify scores each
  position with the identical model and context, so acceptance never
  changes WHAT is generated, only how many dispatches it takes.

Contract (inherited, unchanged): every submitted request reaches EXACTLY
ONE terminal outcome. Streamed tokens are partial results, not outcomes —
a request that expires mid-stream settles ``DeadlineExceeded`` (typed)
with its partial tokens still readable from the future. Deadlines apply
per token: they are re-checked before every prefill and after every
decode chunk, so an expired stream stops within ``decode_chunk`` forwards.

Failure isolation: an injected ``batch_dispatch`` fault (the chaos gate's
kill-one-batch leg) fires before any launch: what is in flight is settled,
then exactly the streams of that dispatch fail, typed ``BatchFailed``, and
the engine keeps serving. A REAL executor failure (at a launch, or at a
fetch taken later, with a successor already launched on the state the
failed dispatch produced) may have consumed donated state buffers, so it
fails every resident stream typed, those of every dispatch in flight too,
drops their results and resets the generation state once — never a silent
wrong-token continuation.
"""
from __future__ import annotations

import dataclasses
import logging
import time
from collections import Counter, defaultdict
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from .. import monitor as _monitor
from .. import trace as _trace
from ..core.types import np_dtype
from ..executor import FETCH_LATER
from ..resilience import faults as _faults
from ..resilience.deadline import Deadline, DeadlineExceeded
from .engine import (DEFAULT_TENANT, BatchFailed, EngineStopped,
                     ServingConfig, ServingEngine, ServingFuture, _Request)

__all__ = ["GenerationConfig", "GenerativeEngine"]

logger = logging.getLogger("paddle_tpu.serving")

_LOOP_HELP = ("wall of one phase of the generative dispatch thread's loop "
              "(idle_wait, await_newcomers, schedule, admit, feed, settle, "
              "publish); with the executor's dispatches they tile the "
              "thread's time")


def _var_names(var) -> List[str]:
    return [] if var is None else [var.name]


def _loop_phase(name: str, parent=None):
    """One phase of the dispatch thread's loop: the span ``serving.<name>``
    (``FLAGS_trace``) and an observation on
    ``serving_loop_seconds{phase=<name>}`` (``FLAGS_monitor``), from one
    timing (docs/OBSERVABILITY.md "Phase spans")."""
    return _trace.phase("serving." + name, parent=parent, histogram=(
        "serving_loop_seconds", _LOOP_HELP, {"phase": name}))


class _Yield:
    """What one chained decode dispatch of ``steps`` forwards gave each
    slot, forward by forward: ``tokens`` [steps, slots, W] of which the
    first ``counts`` [steps, slots] of a forward are real, and (a model
    that generates a block at a time) ``revealed_at`` [steps, slots, W],
    the forward of its block at which each was revealed. ``rows`` is what
    a slot's sequence moves on in the cache with a forward that yields:
    one row for a model that yields one token a forward (W = 1, every
    count 1), a block for one that commits blocks."""

    def __init__(self, outs, steps: int, slots: int, block: int):
        W = block or 1
        self.steps, self.rows = steps, W
        self.tokens = np.asarray(outs[0]).reshape(steps, slots, W)
        if block:
            self.counts = np.asarray(outs[1]).reshape(steps, slots)
            self.revealed_at = np.asarray(outs[2]).reshape(steps, slots, W)
        else:
            self.counts = np.ones((steps, slots), np.int64)
            self.revealed_at = None

    def of(self, slot: int, budget: int, cut=None):
        """``(tokens, revealed_at, forwards, dropped)`` for the request in
        ``slot`` with ``budget`` tokens still to come: the tokens it takes
        in order (through ``cut``, the stop-token rule), the forward each
        was revealed at (empty where the model has none), how many of the
        dispatch's forwards were this request's (through the one that
        yielded its last token if it ends here, else all of them), and how
        many tokens those forwards yielded past its budget."""
        counts = self.counts[:, slot]
        real = np.arange(self.rows)[None, :] < counts[:, None]
        toks = self.tokens[:, slot][real]
        take = toks[:budget]
        if cut is not None:
            take = cut(take)
        ends = len(take) == budget or len(take) < min(len(toks), budget)
        forwards, dropped = self.steps, 0
        if ends and len(take):
            fwd = np.repeat(np.arange(self.steps), counts)
            forwards = int(fwd[len(take) - 1]) + 1
            dropped = int(counts[:forwards].sum()) - len(take)
        at = () if self.revealed_at is None else \
            self.revealed_at[:, slot][real][:len(take)]
        return take, at, forwards, dropped

    def moved(self, slot: int):
        """[steps]: the rows the slot's sequence had moved on in the cache
        before each forward of the dispatch."""
        c = (self.counts[:, slot] > 0) * self.rows
        return np.cumsum(c) - c


@dataclasses.dataclass
class GenerationConfig:
    """Generative-scheduling knobs (the serving half; model geometry —
    slots, pages, buckets — lives on the ``build_gpt_generative`` dict)."""

    decode_chunk: int = 4          # forwards per chained decode dispatch;
    # also the deadline-enforcement granularity
    max_new_tokens_default: int = 16
    eos_id: int = -1               # < 0: no stop token
    # -- prefix-reuse KV cache (ISSUE 20, tentpole leg a) ----------------
    prefix_cache: bool = True      # content-hash prompt pages, share them
    prefix_cache_pages: int = 64   # LRU bound on stored pages
    # -- chunked prefill -------------------------------------------------
    chunked_prefill: bool = True   # admit long/cold prompts slice by
    # slice between decode chunks instead of one monolithic prefill
    # -- speculative decoding (tentpole leg b) ---------------------------
    speculative: bool = False      # draft k tokens, verify in one dispatch

    def resolve(self) -> "GenerationConfig":
        if self.decode_chunk < 1:
            raise ValueError(f"generation: decode_chunk must be >= 1, got "
                             f"{self.decode_chunk}")
        if self.max_new_tokens_default < 1:
            raise ValueError(f"generation: max_new_tokens_default must be "
                             f">= 1, got {self.max_new_tokens_default}")
        if self.prefix_cache_pages < 1:
            raise ValueError(f"generation: prefix_cache_pages must be >= 1, "
                             f"got {self.prefix_cache_pages}")
        return self


@dataclasses.dataclass
class _GenRequest(_Request):
    prompt: np.ndarray = None      # [L] int64
    bucket: int = 0                # prompt bucket (0: chunked-only admit)
    max_new: int = 1
    slot: int = -1                 # assigned batch slot, -1 while queued
    emitted: int = 0
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    # chunked-prefill / prefix-reuse bookkeeping (dispatcher thread only)
    chunked: bool = False          # admitted via chunk slices
    prefilled: bool = False        # decode-eligible (prefill complete)
    prefix_rows: int = 0           # KV rows copied in from the prefix cache
    next_off: int = 0              # next prompt offset to prefill
    # tokens the dispatches launched and not yet settled will yield it, by
    # the host's count (a token a forward; 0 where only the tokens say)
    ahead: int = 0


@dataclasses.dataclass
class _Launched:
    """One dispatch the device has and the host has not fetched: what
    ``_settle_inflight`` needs to settle it as if it had just returned."""

    phase: str                     # "prefill" | "decode"
    reqs: List[_GenRequest]        # the requests it carries (a prefill's
    # in row order), resident when it was launched
    pending: Any                   # executor.DeferredFetch
    span: Any                      # its root span, ended at its settle
    t0: float                      # perf_counter() before the launch call
    index: int                     # decode dispatches launched up to and
    # with it: what a slot's ``serving_seat_lag_turns`` counts from
    bucket: int = 0                # a prefill's
    ahead: int = 0                 # tokens a request, counted at launch


class GenerativeEngine(ServingEngine):
    """See module docstring. ``model`` is a builder's dict
    (``build_gpt_generative`` and the like); parameters must already be
    initialized in ``scope`` (run the model's
    startup program first). Generation state (tokens/positions/KV pages)
    is planted and reset by the engine itself."""

    def __init__(self, model: dict, scope=None, place=None, executor=None,
                 config: Optional[ServingConfig] = None,
                 gen_config: Optional[GenerationConfig] = None):
        decode = model["decode"]
        # how the decode net's tokens come out (module docstring "Yield")
        out = decode.get("yield")
        super().__init__(decode["main"], feed_names=[],
                         fetch_list=([decode["next_token"]] if out is None
                                     else [out["tokens"], out["count"],
                                           out["revealed_at"]]),
                         scope=scope, place=place, executor=executor,
                         config=config)
        self._model = model
        # rows a decode forward carries a slot where the model generates a
        # block at a time; 0: one token a forward
        self._block = int(model.get("block_length") or 0)
        if bool(self._block) != (out is not None):
            raise ValueError(
                "serving: a model that generates a block at a time names "
                "both its block_length and its decode net's yield")
        self.gen_config = (gen_config or GenerationConfig()).resolve()
        self._slots: List[Optional[_GenRequest]] = \
            [None] * int(model["batch_slots"])
        self._max_seq = int(model["max_seq"])
        self._page_size = int(model["page_size"])
        self._buckets = tuple(model["prompt_buckets"])
        # recompile guard: (phase, bucket) -> True once its executable
        # exists; any LATER cache growth on the same key is a recompile
        self._compiled_buckets: Dict[tuple, bool] = {}
        self.decode_recompiles = 0
        # chunked prefill + speculative verify programs, where the builder
        # has them (without, a prompt has to fit a bucket and decode is
        # plain)
        self._chunk = model.get("chunk")
        self._verify = model.get("verify")
        self._prefill_chunk = int(model.get("prefill_chunk") or
                                  self._page_size)
        self._spec_k = int(model.get("spec_k") or 0)
        missing = [f"prefill[{b}]['rows']"
                   for b, net in model["prefill"].items()
                   if "rows" not in net] + [
            f"cache_kinds[{n!r}]" for names in model["cache_vars"]
            for n in names if n not in model.get("cache_kinds", ())]
        if missing:
            raise ValueError(
                "serving: a model names the sequences a dispatch of each "
                "prefill net carries (rows) and the kind of every layer's "
                "state (cache_kinds); missing: " + ", ".join(missing))
        # the model names its own state, a layer at a time, and says of
        # what kind each layer's is (``cache_kinds``): a (K, V) cache pair
        # whose rows follow the sequence (``full``: every position,
        # ``window``: a ring of the last ones), with row counts and type of
        # its own; a
        # ``latent`` layer's cache, whose rows follow the sequence as a
        # ``full`` layer's do but are one "head" and no K/V pair (one array
        # of compressed rows with their rotary keys); or a ``recurrent``
        # layer's state, which has no rows at all. And the per-slot decode
        # gate.
        self._state_kinds = {n: model["cache_kinds"][n]
                             for names in model["cache_vars"] for n in names}
        of_kind = lambda *ks: [tuple(names) for names in model["cache_vars"]
                               if self._state_kinds[names[0]] in ks]
        self._cache_names = of_kind("full", "window")
        self._active_var = model["active_var"]
        # (shape, dtype) of a layer's K cache, or of a latent layer's one
        # array -> how many layers hold such
        sd = lambda n: (tuple(model["state_vars"][n][0]),
                        model["state_vars"][n][1])
        self._cache_shapes = Counter(sd(nk) for nk, _ in self._cache_names)
        # the same by the kind of the layer's cache and its values' width
        # (a value row may be narrower than a key row)
        self._cache_walks = Counter(
            (*sd(nk), int(model["state_vars"][nv][0][3]),
             self._state_kinds[nk]) for nk, nv in self._cache_names)
        self._latent_shapes = Counter(sd(n) for n, in of_kind("latent"))
        # rows a layer's cache holds -> how many layers hold that many
        self._cache_rows = Counter()
        for shapes in (self._cache_shapes, self._latent_shapes):
            for (shape, _), n in shapes.items():
                self._cache_rows[int(shape[2])] += n
        # what a dispatch counted on the device, fetched beside its tokens
        # and handed to what counts it (a net's ``counted``: pairs of a
        # variable and ``count(phase, array, sums)``, the op's own reading
        # of its layout; ``sums`` is this engine's, for what such a
        # function adds up since the engine was built)
        self._sums = Counter()
        gc = self.gen_config
        self._prefix_cache = None
        if gc.prefix_cache and self._chunk is not None:
            from .prefix_cache import PrefixCache
            self._prefix_cache = PrefixCache(
                self._page_size, capacity_pages=gc.prefix_cache_pages)
        if self._block and (gc.speculative or self._chunk is not None
                            or self._verify is not None):
            raise ValueError(
                "serving: a model that generates a block at a time has no "
                "speculative, prefix-cache or chunked-prefill phase")
        self._speculative = bool(
            gc.speculative and self._verify is not None and self._spec_k >= 2)
        # host-side draft proposer for speculative decoding: callable
        # (history_tokens: np.ndarray, n: int) -> n proposed tokens.
        # Default: prompt-lookup n-gram (see _ngram_draft). Swappable for
        # tests and for a real draft model.
        self.draft_fn = None
        # -- one dispatch ahead (module docstring) -------------------------
        # launched and not fetched, oldest first (dispatch thread only; a
        # list so that another thread may copy it)
        self._inflight: List[_Launched] = []
        # this turn may leave its dispatches unfetched (False: every launch
        # is settled at once, the serial order)
        self._ahead = False
        # the host can count a request's tokens before they exist: a token
        # a forward, no verify round that accepts as many as it likes
        self._counts_ahead = not self._block and not self._speculative
        self._gates: set = set()   # vacated slots whose gate is still open
        # slot -> the last request that left it at a launch (its budget
        # counted out): until its last dispatch is settled its caller
        # cannot know, so nobody is awaited for that slot
        self._leaving: Dict[int, _GenRequest] = {}
        self._vacated: Dict[int, int] = {}   # slot -> ``index`` it ended in
        self._decode_launches = 0
        self._cursor: Optional[int] = None   # ``index`` being settled
        self._head_t = 0.0         # the last fetch return (perf_counter)
        self._decode_wall: Optional[float] = None   # last decode chunk's
        self._launch_cost = 0.0    # schedule to decode launch, last turns
        self._clear_gates = None   # jitted gate update, built at first use
        self.prefill_chunks = 0    # chunk slices dispatched (per request)
        self.spec_chunks = 0       # verify dispatches
        self.spec_accepted = 0     # draft tokens accepted in total

    # -- state lifecycle -------------------------------------------------
    def reset_generation_state(self) -> None:
        """Plant zeroed generation state (tokens, positions, KV pages) in
        the scope. Called at warm-up/start and after a real mid-dispatch
        failure (consumed donated buffers are never reused)."""
        held = defaultdict(int)
        for name, (shape, dt) in self._model["state_vars"].items():
            zeros = np.zeros(shape, np_dtype(dt))
            self._scope.set_var(name, zeros)
            if name in self._state_kinds:
                held[self._state_kinds[name]] += zeros.nbytes
        # the time since the last dispatch was no wait of the device's
        self._exe.forget_last_dispatch()
        if _monitor.enabled():
            for kind, nbytes in held.items():
                _monitor.gauge(
                    "serving_kv_cache_bytes",
                    "bytes of per-layer state the engine planted, by the "
                    "kind of layer that owns them (window: a ring of the "
                    "last positions' keys and values; full: every "
                    "position's; latent: every position's compressed row "
                    "and rotary key, one for all heads; recurrent: a "
                    "fixed-size state)"
                ).labels(kind=kind).set(float(nbytes))

    def _ensure_state(self) -> None:
        for name in self._model["state_vars"]:
            if self._scope.find_var(name) is None:
                self.reset_generation_state()
                return

    def start(self) -> "GenerativeEngine":
        self._ensure_state()
        super().start()
        return self

    def warm_up(self, batch_sizes=None) -> int:
        """Compile every (phase, bucket) executable before traffic: each
        prefill bucket with an all-zero slot mask (no slot is touched) and
        one decode chunk on scratch state. Seeds the recompile guard —
        after warm-up, steady-state decode must never compile again.

        Unlike the base engine's stateless warm-up, this one RESETS the
        generation state and dispatches on the caller thread, so it must
        run before ``start()``: on a running engine it would zero resident
        streams' caches mid-generation while racing the dispatch thread —
        refused loudly instead."""
        with self._lock:
            if self._running:
                raise RuntimeError(
                    "serving: GenerativeEngine.warm_up resets the "
                    "generation state and cannot run on a started engine "
                    "(resident streams would silently decode from zeroed "
                    "caches); call it before start()")
        t0 = time.perf_counter()
        self.reset_generation_state()
        compiled = 0
        for bucket in self._buckets:
            net = self._model["prefill"][bucket]
            feed = self._prefill_feed(bucket, [])
            self._exe.run(net["main"], feed=feed,
                          fetch_list=self._prefill_fetches(bucket),
                          scope=self._scope)
            self._note_compiles("prefill", bucket, net["main"])
            compiled += 1
        self._exe.run_chained(self._program, feed={},
                              fetch_list=self._decode_fetches(),
                              steps=self.gen_config.decode_chunk,
                              scope=self._scope)
        self._note_compiles("decode", len(self._slots), self._program)
        compiled += 1
        # the gate's small update, on the array a dispatch leaves: no
        # window compiles it
        self._gates.add(0)
        self._flush_gates()
        if self._use_chunked():
            net = self._chunk
            self._exe.run(net["main"], feed=self._chunk_feed([]),
                          fetch_list=[net["first_token"].name],
                          scope=self._scope)
            self._note_compiles("chunk", self._prefill_chunk, net["main"])
            compiled += 1
        if self._speculative:
            net = self._verify
            self._exe.run(net["main"], feed=self._verify_feed([]),
                          fetch_list=[net["accept_len"].name,
                                      net["sampled"].name],
                          scope=self._scope)
            self._note_compiles("verify", self._spec_k, net["main"])
            compiled += 1
        self.reset_generation_state()
        if _monitor.enabled():
            _monitor.gauge(
                "serving_warm_up_seconds",
                "wall of the last GenerativeEngine.warm_up(): every "
                "(phase, bucket) executable built or loaded and run once"
            ).set(time.perf_counter() - t0)
        return compiled

    def _use_chunked(self) -> bool:
        """Chunked prefill is live when the model ships a chunk program
        and either admission leg needs it (long-prompt slicing or the
        prefix cache's suffix prefill)."""
        return self._chunk is not None and (
            self.gen_config.chunked_prefill or self._prefix_cache is not None)

    # -- submission ------------------------------------------------------
    def submit(self, prompt, *, max_new_tokens: Optional[int] = None,
               priority: int = 0, deadline_s: Optional[float] = None,
               trace_parent=None,
               tenant: Optional[str] = None) -> ServingFuture:
        """Admit one generation request (any thread). ``prompt`` is a 1-D
        int token array (a ``[1, L]`` row is accepted); the returned
        future STREAMS tokens (``ServingFuture.stream()``) and settles
        exactly once with the full token array or a typed error.
        ``trace_parent`` parents the request root span and ``tenant``
        attributes the request in the per-tenant ledger (fleet wire
        propagation — see ``ServingEngine.submit``)."""
        req = self._build_gen_request(prompt, max_new_tokens, priority,
                                      deadline_s, trace_parent, tenant)
        sub = _trace.start_span("serving.submit", parent=req.span,
                                priority=req.priority,
                                prompt_len=len(req.prompt))
        # the base engine's shared admission sequence: accounting, the
        # enqueue fault point, typed rejections, the dispatcher wake
        return self._admit_and_enqueue(req, sub)

    def _build_gen_request(self, prompt, max_new_tokens, priority,
                           deadline_s, trace_parent=None,
                           tenant=None) -> _GenRequest:
        prompt = np.asarray(prompt)
        if prompt.ndim == 2 and prompt.shape[0] == 1:
            prompt = prompt[0]
        if prompt.ndim != 1 or prompt.size == 0:
            raise ValueError(
                f"serving: prompt must be a non-empty 1-D token array, "
                f"got shape {prompt.shape}")
        prompt = prompt.astype(np.int64)
        L = int(prompt.shape[0])
        bucket = next((b for b in self._buckets if b >= L), None)
        chunked = False
        if bucket is None:
            # past the largest bucket: chunked prefill admits it slice by
            # slice (no bucket executable is ever built for this length)
            if not (self._chunk is not None
                    and self.gen_config.chunked_prefill):
                raise ValueError(
                    f"serving: prompt length {L} exceeds the largest "
                    f"prompt bucket {max(self._buckets)}; split or "
                    f"truncate the prompt (or enable chunked_prefill)")
            bucket, chunked = 0, True
        max_new = int(max_new_tokens
                      if max_new_tokens is not None
                      else self.gen_config.max_new_tokens_default)
        if max_new < 1:
            raise ValueError(f"serving: max_new_tokens must be >= 1, got "
                             f"{max_new}")
        if L + max_new > self._max_seq:
            raise ValueError(
                f"serving: prompt ({L}) + max_new_tokens ({max_new}) "
                f"exceeds the KV capacity max_seq {self._max_seq}")
        budget = self.config.deadline_s if deadline_s is None else deadline_s
        seq = next(ServingEngine._seq)
        dl = Deadline(budget, what=f"serving generation #{seq}") \
            if budget and budget > 0 else None
        tenant = str(tenant).strip() if tenant is not None else ""
        req = _GenRequest(seq=seq, feed={}, nrows=1,
                          sig=("gen", bucket or "chunk"),
                          priority=int(priority), deadline=dl,
                          submitted=self._now(), future=ServingFuture(),
                          tenant=tenant or DEFAULT_TENANT,
                          prompt=prompt, bucket=bucket, max_new=max_new,
                          chunked=chunked)
        req.span = self._request_root(trace_parent, seq=seq,
                                      prompt_len=L, max_new=max_new,
                                      priority=int(priority))
        req.future.trace_id = req.span.trace_id
        return req

    # -- scheduler -------------------------------------------------------
    def _idle_locked(self) -> bool:
        return (self._running and not self._queue and not self._inflight
                and not any(r is not None for r in self._slots))

    def _dispatch_forever(self) -> None:
        # Every instant of this thread lies in one leaf span (and, always
        # on, in one serving_loop_seconds phase or one executor dispatch):
        # idle_wait | await_newcomers | schedule | admit | <phase root>{feed,
        # executor.*} | publish | settle. A phase root (serving.prefill,
        # serving.decode) runs from its launch to its settle, so under the
        # lookahead two of them overlap; their leaves never do: a
        # dispatch's executor.fetch is taken later, under its own root.
        # Keep new work inside one of the leaves.
        self._current_batch = []
        while True:
            with self._lock:
                if self._idle_locked():
                    with _loop_phase("idle_wait"):
                        # no launch is coming that would clear them
                        self._flush_gates()
                        while self._idle_locked():
                            self._work.wait(timeout=0.05)
                            self._sweep_expired_locked(self._now())
                            self._update_pressure_locked(self._now())
                self._await_newcomers_locked()
                t_turn = time.perf_counter()
                with _loop_phase("schedule") as ph:
                    active = [r for r in self._slots if r is not None]
                    stopping = not self._running and (
                        not self._drain or (not self._queue and not active))
                    if stopping:
                        leftovers, self._queue = self._queue, []
                        self._slots = [None] * len(self._slots)
                        self._gauge_depth_locked()
                    else:
                        now = self._now()
                        self._sweep_expired_locked(now)
                        self._update_pressure_locked(now)
                        newcomers = self._refill_locked()
                        if ph.traced:
                            ph.set_attributes(
                                queued=len(self._queue),
                                resident=len(active) + len(newcomers),
                                newcomers=len(newcomers))
            if stopping:
                # what the device still has is settled first: a request
                # that ends in it completes, the others keep its tokens
                self._settle_inflight()
                for r in leftovers + active:
                    if not r.future.done():
                        self._settle_error(
                            r, "rejected_stopped",
                            EngineStopped("serving: engine stopped without "
                                          "draining"),
                            dispatched=(r in active))
                self._current_batch = []
                return
            # the crash guard settles every request the engine holds, not
            # just those of one dispatch; after the schedule requests only
            # leave, so once a turn
            self._guard_residents()
            # a turn that needs fetched values before it can launch settles
            # what is in flight and runs in the serial order
            self._ahead = self._may_look_ahead(newcomers)
            if not self._ahead:
                self._settle_inflight()
            launched = self._decode_launches
            if newcomers:
                with _loop_phase("admit") as ph:
                    bucketed = self._admit_newcomers(newcomers)
                    if ph.traced:
                        ph.set_attributes(
                            hits=sum(1 for r in newcomers if r.prefix_rows),
                            rows=sum(r.prefix_rows for r in newcomers))
                self._run_prefill(bucketed)
            # one chunk slice per pending chunked request per iteration,
            # INTERLEAVED with the resident decode chunk below — a long
            # cold prompt never stalls the decoders
            if any(r is not None and r.chunked and not r.prefilled
                   for r in self._slots):
                self._run_chunk_slices()
            if any(r is not None and r.prefilled for r in self._slots):
                if not (self._speculative and self._run_spec_chunk()):
                    self._run_decode_chunk()
            if self._decode_launches > launched:
                # a decaying high-water mark: a turn with a prefill costs
                # more than one without
                self._launch_cost = max(time.perf_counter() - t_turn,
                                        0.5 * self._launch_cost)
            # the device has this turn's work: fetch and settle the turn
            # before it, and this turn's prefills (a newcomer's first token
            # goes out when its prefill ends, before the chunk behind it)
            self._settle_inflight(keep=(
                self._inflight[-1] if self._inflight
                and self._decode_launches > launched else None))
            self._ahead = False

    def _held(self, also=()) -> List[_GenRequest]:
        """The requests being served: in a slot, or out of it already (the
        budget counted out at a launch) with the last dispatch in flight
        (``also``: dispatches no longer on the list). Any thread."""
        held = {id(r): r for r in self._slots if r is not None}
        for e in list(self._inflight) + list(also):
            held.update((id(r), r) for r in e.reqs if not r.future.done())
        return list(held.values())

    def _guard_residents(self) -> None:
        self._current_batch = self._held()

    def _may_look_ahead(self, newcomers: Sequence[_GenRequest]) -> bool:
        """Whether this turn's launches can be made behind a dispatch still
        in flight, read off the residents: a verify round drafts from the
        tokens before it, a chunk slice and a prefix-cache copy-in or
        publish pull buffers through the host."""
        if self._speculative:
            return False
        pc = self._prefix_cache
        if pc is not None and any(pc.pages_of(len(r.prompt))
                                  for r in newcomers):
            # a prompt with a whole page is copied in (a hit) or published
            # (a miss); one without is neither
            return False
        return not any(r is not None and r.chunked and not r.prefilled
                       for r in self._slots)

    def _await_newcomers_locked(self) -> None:
        """Between the settle of a chunk and the next turn's launches, under
        ``_lock``: while slots are free whose last answer is out and the
        queue cannot fill them, wait for ``submit`` (which notifies
        ``_work``) as long as the chunk in flight leaves: its expected end
        (the last decode chunk's wall, counted from the fetch return that
        made it the device's oldest) less twice what a turn's launches last
        took. A caller that resubmits when its request completes is then
        seated in this turn and not the one after. With a queue, or nothing
        in flight, the wait is never entered."""
        if not self._inflight or self._decode_wall is None:
            return
        until = self._head_t + self._decode_wall - 2.0 * self._launch_cost

        def short() -> float:
            # free slots whose last request's outcome is out: a slot whose
            # request left it at a launch and is still in flight has no
            # caller who could know
            free = sum(r is None and (j not in self._leaving
                                      or self._leaving[j].future.done())
                       for j, r in enumerate(self._slots))
            if not self._running or len(self._queue) >= free:
                return 0.0
            return until - time.perf_counter()

        if short() <= 0:
            return
        with _loop_phase("await_newcomers"):
            left = short()
            while left > 0:
                self._work.wait(timeout=left)
                left = short()

    def _refill_locked(self) -> List[_GenRequest]:
        """Assign queued requests to free slots (FIFO). Runs under
        ``_lock``; the assigned requests count as dispatched from here on
        (the accounting's in-flight arm)."""
        # the slot that has stood empty longest first (never vacated, then
        # by the dispatch its request ended in): where a caller returns for
        # every slot, each slot waits its one turn and none waits two
        free = sorted((j for j, r in enumerate(self._slots) if r is None),
                      key=lambda j: (self._vacated.get(j, -1), j))
        taken: List[_GenRequest] = []
        while free and self._queue:
            r = self._queue.pop(0)
            r.slot = free.pop(0)
            self._slots[r.slot] = r
            self._dispatched += 1
            taken.append(r)
            ended = self._vacated.pop(r.slot, None)
            if ended is not None and _monitor.enabled():
                _monitor.histogram(
                    "serving_seat_lag_turns",
                    "decode dispatches launched after the one a slot's "
                    "request ended in and before the slot is seated again: "
                    "the turns the slot stood empty (0 with a queue, 1 "
                    "where a caller resubmits on completion)",
                    buckets=(0.0, 1.0, 2.0, 3.0, 4.0, 8.0)).observe(
                    float(self._decode_launches - ended))
        if taken:
            self._gauge_depth_locked()
        return taken

    # -- admission: prefix reuse + chunked prefill -----------------------
    def _admit_newcomers(self,
                         newcomers: List[_GenRequest]) -> List[_GenRequest]:
        """Route just-seated requests: a prefix-cache hit copies the
        matched pages into the slot and prefills ONLY the suffix via
        chunk slices; an over-bucket prompt goes chunked from row 0;
        everything else takes the classic bucket prefill (returned)."""
        bucketed: List[_GenRequest] = []
        for r in newcomers:
            rows = 0
            if self._prefix_cache is not None:
                rows, entries = self._prefix_cache.match(r.prompt)
                if _monitor.enabled():
                    (_monitor.counter("serving_prefix_hits_total",
                                      "requests that reused >= 1 cached "
                                      "prefix page") if rows else
                     _monitor.counter("serving_prefix_misses_total",
                                      "requests with no cached prefix "
                                      "page")).inc()
                    if rows:
                        _monitor.counter(
                            "serving_prefix_pages_reused_total",
                            "KV pages served from the prefix cache"
                        ).inc(rows // self._page_size)
                if rows:
                    self._copy_in_prefix(r.slot, entries)
                    r.prefix_rows, r.next_off, r.chunked = rows, rows, True
                    continue
            if r.chunked:
                r.next_off = 0
            else:
                bucketed.append(r)
        return bucketed

    def _copy_in_prefix(self, slot: int, entries: List[dict]) -> None:
        """Copy matched prefix pages into ``slot``'s KV rows. Copy-in (not
        aliasing) is the CoW story: the resident owns its rows outright,
        so later divergence or store eviction can never corrupt it."""
        P = self._page_size
        for li, (nk, nv) in enumerate(self._cache_names):
            for name, kv in ((nk, "k"), (nv, "v")):
                arr = np.array(self._scope.find_var(name))
                for i, e in enumerate(entries):
                    arr[slot, :, i * P:(i + 1) * P, :] = e[kv][li]
                self._scope.set_var(name, arr)

    def _publish_pages(self, r: _GenRequest) -> int:
        """After ``r``'s prefill completes, publish COPIES of its whole-
        page prompt rows under their chain hashes (cheap no-op for pages
        already stored). Returns the number of pages newly stored."""
        P, slot = self._page_size, r.slot

        def page_rows(i):
            ks, vs = [], []
            for nk, nv in self._cache_names:
                ks.append(np.array(np.asarray(
                    self._scope.find_var(nk))[slot, :, i * P:(i + 1) * P, :]))
                vs.append(np.array(np.asarray(
                    self._scope.find_var(nv))[slot, :, i * P:(i + 1) * P, :]))
            return ks, vs

        added = self._prefix_cache.insert(r.prompt, page_rows)
        if _monitor.enabled():
            _monitor.gauge(
                "serving_prefix_pages",
                "KV pages resident in the prefix cache").set(
                float(len(self._prefix_cache)))
        return added

    def _publish(self, reqs: Sequence[_GenRequest]) -> None:
        """The ``publish`` phase: the just-prefilled requests' whole prompt
        pages go to the prefix cache, before their first tokens go out (a
        client that has its token can count on its pages being stored).
        Each new page pulls every K/V buffer through the host."""
        if self._prefix_cache is None:
            return
        with _loop_phase("publish") as ph:
            pages = sum(self._publish_pages(r) for r in reqs)
            if ph.traced:
                ph.set_attributes(pages=pages, host_bytes=pages * sum(
                    int(getattr(self._scope.find_var(n), "nbytes", 0))
                    for pair in self._cache_names for n in pair))

    def _flush_gates(self) -> None:
        """Clear the decode gate (the model's ``active_var``) of every slot
        vacated since the last launch, on the device and in one update:
        later decode / verify dispatches leave those slots' state and cache
        rows untouched until the next admission re-arms them. Nothing comes
        to the host (the gate may be the future result of a dispatch still
        in flight, and reading it would wait that dispatch out); the update
        queues behind the dispatches that still needed the gate open.
        Called before every launch, under its ``feed`` phase, and when the
        engine goes idle."""
        if not self._gates:
            return
        slots, self._gates = sorted(self._gates), set()
        cur = self._scope.find_var(self._active_var)
        if cur is None:
            return
        import jax

        if self._clear_gates is None:
            self._clear_gates = jax.jit(lambda gate, keep: gate * keep)
        keep = np.ones(cur.shape, cur.dtype)
        keep[slots, 0] = 0
        with jax.default_device(self._exe.place.jax_device()):
            self._scope.set_var(self._active_var,
                                self._clear_gates(cur, keep))

    # -- chunked prefill -------------------------------------------------
    def _chunk_feed(self, pending: Sequence[_GenRequest]) -> dict:
        B, C = len(self._slots), self._prefill_chunk
        feed = {
            "chunk_ids": np.zeros((B, C), np.int64),
            "chunk_pos": np.zeros((B, C), np.int64),
            "chunk_start": np.zeros((B, 1), np.int64),
            "chunk_len": np.ones((B, 1), np.int64),
            "slot_mask": np.zeros((B, 1), np.float32),
            "sample_mask": np.zeros((B, 1), np.float32),
        }
        for r in pending:
            off, L = r.next_off, len(r.prompt)
            take = r.prompt[off:off + C]
            n = len(take)
            feed["chunk_ids"][r.slot, :n] = take
            if n < C:
                feed["chunk_ids"][r.slot, n:] = take[-1]
            feed["chunk_pos"][r.slot] = np.clip(
                off + np.arange(C), 0, self._max_seq - 1)
            feed["chunk_start"][r.slot, 0] = off
            feed["chunk_len"][r.slot, 0] = n
            feed["slot_mask"][r.slot, 0] = 1.0
            if off + n >= L:
                feed["sample_mask"][r.slot, 0] = 1.0
        return feed

    def _run_chunk_slices(self) -> None:
        """One prefill slice for EVERY pending chunked request, batched
        into a single slot-masked dispatch. A prompt's final slice samples
        its first token in-program and flips the slot's decode gate."""
        pending = [r for r in self._slots
                   if r is not None and r.chunked and not r.prefilled]
        live: List[_GenRequest] = []
        for r in pending:
            if not self._expired(r):
                live.append(r)
        if not live:
            return
        net = self._chunk
        span = _trace.NOOP_SPAN
        if _trace.enabled():
            span = _trace.root_span(
                "serving.prefill_chunk", requests=len(live),
                request_traces=",".join(r.span.trace_id for r in live))
        try:
            _faults.fault_point("batch_dispatch")
            with _loop_phase("feed", parent=span):
                feed = self._chunk_feed(live)
                self._flush_gates()
            t0 = time.perf_counter()
            with _trace.attach(span):
                outs = self._exe.run(net["main"], feed=feed,
                                     fetch_list=[net["first_token"].name],
                                     scope=self._scope)
            dt = time.perf_counter() - t0
        except _faults.InjectedFault as e:
            span.end(error=e)
            self._fail_group(live, e, phase="prefill_chunk")
            return
        except Exception as e:
            span.end(error=e)
            self._fail_all_resident(e, phase="prefill_chunk")
            return
        span.end()
        C = self._prefill_chunk
        done: List[_GenRequest] = []
        seated = 0
        for r in live:
            take = min(C, len(r.prompt) - r.next_off)
            seated += take
            r.next_off += take
            if r.next_off >= len(r.prompt):
                r.prefilled = True
                done.append(r)
        self._count_prefill_tokens(seated, len(self._slots) * C)
        self._publish(done)
        with _loop_phase("settle") as ph:
            self._note_compiles("chunk", self._prefill_chunk, net["main"])
            self.prefill_chunks += len(live)
            if _monitor.enabled():
                _monitor.counter(
                    "serving_prefill_chunks_total",
                    "chunked-prefill slices dispatched (per request)"
                ).inc(len(live))
                _monitor.histogram(
                    "serving_prefill_seconds",
                    "wall time of one slot-masked prefill dispatch"
                ).observe(dt)
            first = np.asarray(outs[0]).reshape(len(self._slots))
            for r in done:
                self._emit(r, [int(first[r.slot])], dt,
                           record_intertoken=False)
            self._settled(ph, done, len(done))

    # -- speculative decoding --------------------------------------------
    def _ngram_draft(self, hist: np.ndarray, n: int) -> List[int]:
        """Prompt-lookup drafting (model-free): find the most recent
        earlier occurrence of the last token and propose the tokens that
        followed it; pad by repeating. A wrong draft costs only its
        rejected verify rows — correctness rides on the verify dispatch,
        never the proposer."""
        last = int(hist[-1])
        prev = np.nonzero(hist[:-1] == last)[0]
        cand = hist[int(prev[-1]) + 1:int(prev[-1]) + 1 + n] \
            if prev.size else hist[:0]
        toks = [int(t) for t in cand]
        while len(toks) < n:
            toks.append(toks[-1] if toks else last)
        return toks

    def _draft(self, r: _GenRequest, n: int) -> List[int]:
        hist = np.concatenate(
            [r.prompt, np.asarray(r.out_tokens, np.int64)]) \
            if r.out_tokens else r.prompt
        if self.draft_fn is not None:
            toks = [int(t) for t in self.draft_fn(hist, n)]
            if len(toks) != n:
                raise ValueError(
                    f"serving: draft_fn returned {len(toks)} tokens, "
                    f"expected {n}")
            return toks
        return self._ngram_draft(hist, n)

    def _verify_feed(self, active: Sequence[_GenRequest]) -> dict:
        B, k = len(self._slots), self._spec_k
        feed = {
            "chunk_ids": np.zeros((B, k), np.int64),
            "chunk_pos": np.zeros((B, k), np.int64),
            "chunk_start": np.zeros((B, 1), np.int64),
            "slot_mask": np.zeros((B, 1), np.float32),
            "draft_ids": np.zeros((B, k - 1), np.int64),
        }
        for r in active:
            pos = len(r.prompt) + r.emitted - 1   # committed cache rows
            drafts = self._draft(r, k - 1)
            feed["chunk_ids"][r.slot, 0] = r.out_tokens[-1]
            feed["chunk_ids"][r.slot, 1:] = drafts
            feed["chunk_pos"][r.slot] = np.clip(
                pos + np.arange(k), 0, self._max_seq - 1)
            feed["chunk_start"][r.slot, 0] = pos
            feed["slot_mask"][r.slot, 0] = 1.0
            feed["draft_ids"][r.slot] = drafts
        return feed

    def _run_spec_chunk(self) -> bool:
        """One draft-then-verify round for every decode-eligible resident:
        the target scores the whole k-token chunk in ONE dispatch and
        commits the longest agreeing prefix + bonus token in-program.
        Returns False (caller falls back to the plain decode chunk) when
        any resident is too near its KV capacity for a full chunk."""
        active = [r for r in self._slots if r is not None and r.prefilled]
        k = self._spec_k
        if not active:
            return False
        for r in active:
            if len(r.prompt) + r.emitted - 1 + k > self._max_seq:
                return False
        span = _trace.NOOP_SPAN
        if _trace.enabled():
            span = _trace.root_span(
                "serving.spec_verify", k=k, requests=len(active),
                request_traces=",".join(r.span.trace_id for r in active))
        net = self._verify
        try:
            _faults.fault_point("batch_dispatch")
            with _loop_phase("feed", parent=span):
                feed = self._verify_feed(active)
                self._flush_gates()
            t0 = time.perf_counter()
            with _trace.attach(span):
                outs = self._exe.run(
                    net["main"], feed=feed,
                    fetch_list=[net["accept_len"].name,
                                net["sampled"].name],
                    scope=self._scope)
            dt = time.perf_counter() - t0
        except _faults.InjectedFault as e:
            span.end(error=e)
            self._fail_group(active, e, phase="spec_verify")
            return True
        except Exception as e:
            span.end(error=e)
            self._fail_all_resident(e, phase="spec_verify")
            return True
        span.end()
        with _loop_phase("settle") as ph:
            self._note_compiles("verify", k, net["main"])
            self.spec_chunks += 1
            accept = np.asarray(outs[0]).reshape(len(self._slots))
            sampled = np.asarray(outs[1]).reshape(len(self._slots), k)
            if _monitor.enabled():
                _monitor.histogram(
                    "serving_decode_chunk_seconds",
                    "wall time of one chained decode chunk").observe(dt)
            tokens = 0
            for r in active:
                if self._expired(r):
                    continue
                m = int(accept[r.slot])
                self.spec_accepted += m
                if _monitor.enabled():
                    _monitor.histogram(
                        "serving_spec_accepted_len",
                        "draft tokens accepted per verify chunk (0..k-1; "
                        "the bonus token is on top)").observe(float(m))
                take = self._cut_at_eos(
                    sampled[r.slot, :m + 1][:r.max_new - r.emitted])
                tokens += len(take)
                self._emit(r, [int(t) for t in take], dt)
            self._settled(ph, active, tokens)
        return True

    # -- prefill ---------------------------------------------------------
    @staticmethod
    def _count_prefill_tokens(prompt: int, run: int) -> None:
        """What one prefill dispatch's rows were: the prompt tokens it
        seated against the positions its program ran (rows times bucket or
        chunk length, padding and empty rows included)."""
        if _monitor.enabled():
            tokens = _monitor.counter(
                "serving_prefill_tokens_total",
                "tokens of prefill dispatches: kind=prompt the prompt "
                "tokens seated, kind=run the positions the programs ran "
                "(rows x bucket length)")
            tokens.labels(kind="prompt").inc(float(prompt))
            tokens.labels(kind="run").inc(float(run))

    def _prefill_rows(self, bucket: int) -> int:
        """Sequences one dispatch of this bucket's program carries, each
        row naming its slot (``slot_ids``)."""
        return self._model["prefill"][bucket]["rows"]

    def _prefill_feed(self, bucket: int,
                      reqs: Sequence[_GenRequest]) -> dict:
        B = self._prefill_rows(bucket)
        feed = {
            "prompt_ids": np.zeros((B, bucket), np.int64),
            "prompt_pos": np.tile(np.arange(bucket, dtype=np.int64),
                                  (B, 1)),
            "prompt_mask": np.zeros((B, bucket), np.float32),
            "prompt_len": np.ones((B, 1), np.int64),
            "slot_mask": np.zeros((B, 1), np.float32),
            "slot_ids": np.zeros((B, 1), np.int64),
        }
        for row, r in enumerate(reqs):
            L = len(r.prompt)
            feed["prompt_ids"][row, :L] = r.prompt
            feed["prompt_mask"][row, :L] = 1.0
            feed["prompt_len"][row, 0] = L
            feed["slot_mask"][row, 0] = 1.0
            feed["slot_ids"][row, 0] = r.slot
        return feed

    def _run_prefill(self, newcomers: List[_GenRequest]) -> None:
        by_bucket = defaultdict(list)
        for r in newcomers:
            by_bucket[r.bucket].append(r)
        # one dispatch per `rows` requests of a bucket: its program carries
        # that many sequences a dispatch
        groups = []
        for bucket in sorted(by_bucket):
            reqs = by_bucket[bucket]
            n = self._prefill_rows(bucket)
            groups += [(bucket, reqs[i:i + n])
                       for i in range(0, len(reqs), n)]
        for bucket, reqs in groups:
            net = self._model["prefill"][bucket]
            span = _trace.NOOP_SPAN
            if _trace.enabled():
                span = _trace.root_span(
                    "serving.prefill", bucket=bucket, requests=len(reqs),
                    request_traces=",".join(r.span.trace_id for r in reqs))
                for r in reqs:
                    r.dispatch_span = _trace.start_span(
                        "serving.dispatch", parent=r.span, phase="prefill",
                        bucket=bucket, slot=r.slot)
            try:
                _faults.fault_point("batch_dispatch")
                with _loop_phase("feed", parent=span):
                    feed = self._prefill_feed(bucket, reqs)
                    self._flush_gates()
                t0 = time.perf_counter()
                with _trace.attach(span):
                    pending = self._exe.run(
                        net["main"], feed=feed,
                        fetch_list=self._prefill_fetches(bucket),
                        scope=self._scope, return_numpy=FETCH_LATER)
            except _faults.InjectedFault as e:
                # fired before any dispatch: state intact. What is in
                # flight is sound and is settled as if this turn had not
                # begun; then only this group fails (typed) — the engine
                # keeps serving
                span.end(error=e)
                self._settle_inflight()
                self._fail_group(reqs, e, phase="prefill")
                continue
            except Exception as e:
                # a real failure may have consumed donated state buffers:
                # fail every resident stream typed + reset the state
                span.end(error=e)
                self._fail_all_resident(e, phase="prefill")
                return
            for r in reqs:
                r.prefilled = True
                r.next_off = len(r.prompt)
            # a prefill by blocks streams no token: its first come with
            # the slot's first committed block
            self._launched(_Launched(
                "prefill", reqs, pending, span, t0, self._decode_launches,
                bucket=bucket), 0 if self._block else 1)

    def _settle_prefill(self, e: _Launched, outs, dt: float) -> None:
        bucket, reqs = e.bucket, e.reqs
        net = self._model["prefill"][bucket]
        self._publish(reqs)
        with _loop_phase("settle") as ph:
            self._note_compiles("prefill", bucket, net["main"])
            noted = self._count("prefill", net,
                                outs[0 if self._block else 1:])
            if ph.traced:
                ph.set_attributes(launch_t0=e.t0, **noted)
            # a prefill by blocks seats a prompt's whole blocks; what
            # is left over opens the slot's first decode block
            whole = self._block or 1
            self._count_prefill_tokens(
                sum(len(r.prompt) // whole * whole for r in reqs),
                net["rows"] * bucket)
            if _monitor.enabled():
                _monitor.histogram(
                    "serving_prefill_seconds",
                    "wall time of one slot-masked prefill dispatch (where "
                    "it was launched behind another, from that one's "
                    "fetch return)").observe(dt)
            first = None if self._block \
                else np.asarray(outs[0]).reshape(-1)
            tokens = 0
            for i, r in enumerate(reqs):
                if r.future.done() or self._expired(r) or first is None:
                    continue
                # the first token's cost is the FIRST-TOKEN histogram's
                # story — it must not pollute the inter-token latency
                tokens += 1
                self._emit(r, [int(first[i])], dt, record_intertoken=False)
            self._settled(ph, reqs, tokens)

    # -- one dispatch ahead ------------------------------------------------
    def _launched(self, e: _Launched, ahead: int) -> None:
        """``e`` is on the device. Counted: whether it was queued behind a
        dispatch still running; and, where the host can count a request's
        tokens before they exist, who ends inside it: such a request leaves
        its slot now (its gate is cleared before the next launch, the slot
        can be seated in the next turn), so a length stop runs no forward
        the serial loop did not run. ``ahead``: tokens ``e`` yields each of
        its requests. Settled at once unless this turn looks ahead."""
        if _monitor.enabled():
            _monitor.counter(
                "serving_launches_total",
                "prefill and decode dispatches launched, by whether this "
                "engine had a dispatch in flight then (queued_behind="
                "running: the device goes from that one to this without "
                "waiting for the host) or none (idle)").labels(
                phase=e.phase, queued_behind="running" if self._inflight
                else "idle").inc()
        self._inflight.append(e)
        if self._counts_ahead:
            e.ahead = ahead
            for r in e.reqs:
                r.ahead += ahead
                if r.emitted + r.ahead >= r.max_new \
                        and self._slots[r.slot] is r:
                    self._retire(r, ended=e.index)
                    self._leaving[r.slot] = r
        if not self._ahead:
            self._settle_inflight()

    def _settle_inflight(self, keep: Optional[_Launched] = None) -> None:
        """Fetch and settle, oldest first, every dispatch in flight up to
        ``keep`` (the chunk the device is left with). Deadlines are judged
        here, a failure of the device's surfaces here."""
        while self._inflight and self._inflight[0] is not keep:
            e = self._inflight.pop(0)
            asked = time.perf_counter()
            try:
                outs = e.pending.take()
            except Exception as err:
                e.span.end(error=err)
                self._fail_all_resident(err, phase=e.phase, failed=e)
                return
            # the dispatch's own part of the device's time: from its launch
            # or, where it was queued behind another, that one's return
            now = time.perf_counter()
            dt, self._head_t = now - max(e.t0, self._head_t), now
            e.span.end()
            for r in e.reqs:
                r.ahead -= e.ahead
            self._cursor = e.index
            try:
                if e.phase == "prefill":
                    self._settle_prefill(e, outs, dt)
                else:
                    # what the wait for newcomers reckons a chunk's end
                    # from: the device's time only where the fetch had to
                    # wait for it. Where the results lay ready the thread
                    # came late by an unknown time that ``dt`` holds too;
                    # taking it would move the next wait's end out by as
                    # much, and the lateness would feed itself
                    if now - asked > 1e-3 or self._decode_wall is None:
                        self._decode_wall = dt
                    else:
                        self._decode_wall = min(self._decode_wall, dt)
                    self._settle_decode(e, outs, dt)
            finally:
                self._cursor = None

    # -- decode ----------------------------------------------------------
    def _run_decode_chunk(self) -> None:
        # only decode-eligible residents: slots mid-chunked-prefill keep
        # their in-program decode gate (the model's ``active_var``) at 0, so the
        # dispatch leaves their state and cache rows bit-untouched
        active = [r for r in self._slots if r is not None and r.prefilled]
        steps = self.gen_config.decode_chunk
        span = _trace.NOOP_SPAN
        if _trace.enabled():
            span = _trace.root_span(
                "serving.decode", steps=steps, requests=len(active),
                block_length=self._block,
                request_traces=",".join(r.span.trace_id for r in active))
        try:
            _faults.fault_point("batch_dispatch")
            if self._gates:
                with _loop_phase("feed", parent=span):
                    self._flush_gates()
            t0 = time.perf_counter()
            with _trace.attach(span):
                pending = self._exe.run_chained(
                    self._program, feed={},
                    fetch_list=self._decode_fetches(),
                    steps=steps, scope=self._scope,
                    return_numpy=FETCH_LATER)
        except _faults.InjectedFault as e:
            # the chaos gate's kill-one-batch: what is in flight is settled
            # first (the fault fires before the dispatch, the state is
            # untouched), then every stream in THIS batch that is still
            # open settles typed; freed slots are re-prefilled next
            # iteration
            span.end(error=e)
            self._settle_inflight()
            self._fail_group([r for r in active if not r.future.done()], e,
                             phase="decode")
            return
        except Exception as e:
            span.end(error=e)
            self._fail_all_resident(e, phase="decode")
            return
        self._decode_launches += 1
        self._launched(_Launched("decode", active, pending, span, t0,
                                 self._decode_launches), steps)

    def _settle_decode(self, e: _Launched, outs, dt: float) -> None:
        steps = self.gen_config.decode_chunk
        with _loop_phase("settle") as ph:
            self._note_compiles("decode", len(self._slots), self._program)
            n = len(self._fetch_names)
            noted = self._count("decode", self._model["decode"], outs[n:])
            out = _Yield(outs[:n], steps, len(self._slots), self._block)
            # a request that ended before this dispatch was settled (a
            # stop only its tokens showed, a deadline) ran it for nothing:
            # what it yielded is dropped like the rest of a chunk past a
            # stop
            active = [r for r in e.reqs if not r.future.done()]
            late = sum(int(out.counts[:, r.slot].sum())
                       for r in e.reqs if r.future.done())
            rows = self._observe_walk(active, steps, out)
            if ph.traced:
                # what ties this settle to its dispatch (the launch's host
                # time) and what that one dispatch's attention fetched
                ph.set_attributes(launch_t0=e.t0, **noted, **{
                    f"attn_rows_{kind}": int(n) for kind, n in rows.items()})
            if _monitor.enabled():
                _monitor.histogram(
                    "serving_decode_chunk_seconds",
                    "wall time of one chained decode dispatch (its "
                    "forwards yield what the decode net says: a token a "
                    "slot each, or a block's when it commits); where it "
                    "was launched behind another, from that one's fetch "
                    "return").observe(dt)
                if late:
                    _monitor.counter(
                        "serving_lookahead_dropped_tokens_total",
                        "tokens a dispatch yielded a slot whose request "
                        "had ended when the dispatch was launched, as "
                        "only the settle before showed (a stop token, a "
                        "deadline, a block model's yield): the one "
                        "dispatch by which such a stop is late").inc(late)
            tokens, theirs = 0, []
            for r in active:
                # mid-stream expiry: the typed outcome is the LAST word —
                # this chunk's tokens are discarded, the ones already
                # streamed remain readable as partial results
                if self._expired(r):
                    continue
                take, at, forwards, dropped = out.of(
                    r.slot, r.max_new - r.emitted, self._cut_at_eos)
                theirs.append((r.slot, forwards, dropped))
                tokens += len(take)
                # the forwards that were this request's, over its tokens
                self._emit(r, [int(t) for t in take], dt * forwards / steps,
                           revealed_at=[int(t) for t in at])
            if self._block:
                self._count_block_forwards(out, theirs)
            self._settled(ph, active, tokens)

    @staticmethod
    def _count_block_forwards(out: _Yield, theirs) -> None:
        """What a dispatch's forwards were, for a model that generates a
        block at a time; ``theirs``: per live request ``(slot, its
        forwards of the dispatch, tokens they yielded past its budget)``.
        Forwards that committed a block and forwards that revealed
        positions, the positions each of a committed block's forwards
        revealed, and the tokens last blocks generated past their answers'
        ends."""
        if not _monitor.enabled():
            return
        commits = forwards = dropped = 0
        revealed = _monitor.histogram(
            "serving_tokens_revealed_per_forward",
            "positions one denoise forward revealed in one slot's block, "
            "observed when the block commits",
            buckets=tuple(float(i) for i in range(1, 9)))
        for slot, n, tail in theirs:
            counts = out.counts[:n, slot]
            forwards, dropped = forwards + n, dropped + tail
            for s in np.nonzero(counts)[0]:
                commits += 1
                at = out.revealed_at[s, slot, :counts[s]]
                for t in range(int(at.max()) + 1):
                    revealed.observe(float((at == t).sum()))
        fw = _monitor.counter(
            "serving_block_forwards_total",
            "decode forwards of resident requests of a model that "
            "generates a block at a time, a slot at a time: kind=denoise "
            "revealed positions of a block and yielded no token, "
            "kind=commit ran a finished block, wrote its keys and values "
            "and yielded its tokens")
        fw.labels(kind="commit").inc(commits)
        fw.labels(kind="denoise").inc(forwards - commits)
        _monitor.counter(
            "serving_blocks_committed_total",
            "blocks committed to the cache and yielded").inc(commits)
        _monitor.counter(
            "serving_block_tail_tokens_total",
            "tokens a request's last block generated past the end of its "
            "answer, which are not streamed").inc(dropped)

    def _observe_walk(self, active: Sequence[_GenRequest], steps: int,
                      out: Optional[_Yield] = None) -> None:
        """How much of the residents' caches this decode dispatch's
        attention kernels walk: k-blocks fetched over k-blocks held, from
        the lengths this thread holds and the kernel module's own count
        (on the CPU too, where the primitive route scores every row).
        ``out``: what the dispatch yielded (without it, a row a forward).
        Returns the rows the kernel fetched by kind of cache (a key/value
        head's counted once), for the dispatch's span."""
        if not active or not _monitor.enabled():
            return {}
        from ..kernels import decode_grid_steps, decode_walk_blocks
        from ..kernels.latent_attention import latent_walk_blocks

        # a forward's first row sees the keys so far and its own: a
        # sequence's rows before the dispatch (the last token streamed is
        # this dispatch's first row; a block starts on a whole block) and
        # those it moved on inside it, which the yield says
        q_len = out.rows if out is not None else 1
        moved = np.arange(steps)[:, None] if out is None else np.stack(
            [out.moved(r.slot) for r in active], axis=1)
        before = np.array([(len(r.prompt) + r.emitted) // q_len * q_len
                           - (0 if self._block else 1) for r in active])
        lengths = before + 1 + moved
        fetched = held = keys = 0
        from ..kernels.decode_attention import kv_tile
        rows_by_kind, calls_by_kind = Counter(), Counter()
        steps_by_kind = Counter()
        for (shape, dt, v_dim, kind), n in self._cache_walks.items():
            tile = kv_tile(shape[1], shape[2], shape[3], np_dtype(dt),
                           self._page_size, v_dim=v_dim)[1]
            seen = np.minimum(lengths, shape[2])
            f, h = decode_walk_blocks(seen, shape, np_dtype(dt),
                                      self._page_size, q_len=q_len,
                                      rows=tile)
            fetched, held = fetched + n * f, held + n * h
            keys += n * int(np.minimum(lengths + q_len - 1, shape[2]).sum())
            rows_by_kind[kind] += n * f * tile
            calls_by_kind[kind] += n * steps
            steps_by_kind[kind] += n * decode_grid_steps(
                seen, shape, np_dtype(dt), self._page_size, q_len=q_len,
                v_dim=v_dim)
        for kind, rows in rows_by_kind.items():
            _monitor.counter(
                "decode_attention_rows_total",
                "cache rows the decode attention kernel fetched, a "
                "key/value head's counted once: per forward, resident "
                "sequence and layer with a K/V cache, whole k-blocks up to "
                "the sequence's last live one (kernels.decode_walk_blocks "
                "on the host's lengths), by the kind of the layer's cache "
                "(window: a ring, all of it once the window is passed)"
            ).labels(kind=kind).inc(rows)
            _monitor.counter(
                "decode_attention_grid_steps_total",
                "grid steps the decode attention kernel's calls ran for "
                "the resident sequences, a step a k-block of a group of "
                "heads (kernels.decode_grid_steps on the host's lengths): "
                "over decode_attention_rows_total / the tile's rows, the "
                "groups of heads a sequence's heads make (1 where a step "
                "carries all of them) where a step runs only for a block "
                "that is fetched, and more where a grid is sized by the "
                "cache").labels(kind=kind).inc(steps_by_kind[kind])
            _monitor.counter(
                "decode_attention_calls_by_kind_total",
                "calls of the decode attention over a K/V cache, a forward "
                "and layer, by the kind of the layer's cache").labels(
                kind=kind).inc(calls_by_kind[kind])
        if keys:
            _monitor.counter(
                "decode_attention_keys_total",
                "key rows the decode forwards' attention had to read: per "
                "forward, resident sequence and layer with a K/V cache, "
                "the keys its chunk's last row sees").inc(keys)
            _monitor.counter(
                "decode_attention_calls_total",
                "calls of the decode attention over a K/V cache: a "
                "forward and layer").inc(
                steps * sum(self._cache_shapes.values()))
        for (shape, dt), n in self._latent_shapes.items():
            f, h = latent_walk_blocks(np.minimum(lengths, shape[2]), shape,
                                      np_dtype(dt), self._page_size)
            fetched, held = fetched + n * f, held + n * h
        _monitor.histogram(
            "decode_attention_walk_share",
            "per decode dispatch: k-blocks the decode attention kernel "
            "fetches over k-blocks the resident sequences' caches hold "
            "(kernels.decode_walk_blocks on the host's lengths)"
        ).observe(fetched / held)
        return rows_by_kind

    # -- shared settle paths ---------------------------------------------
    def _expired(self, r: _GenRequest) -> bool:
        """Retire ``r`` with its typed ``DeadlineExceeded`` if its deadline
        has passed; True when it did."""
        if r.deadline is None or not r.deadline.expired:
            return False
        self._retire(r)
        self._settle_error(
            r, "deadline_exceeded",
            DeadlineExceeded(r.deadline.what, r.deadline.budget_s,
                             r.deadline.elapsed()),
            dispatched=True)
        return True

    def _cut_at_eos(self, take):
        eos = self.gen_config.eos_id
        if eos >= 0:
            hits = np.nonzero(take == eos)[0]
            if hits.size:
                take = take[:int(hits[0]) + 1]
        return take

    def _settled(self, ph, reqs: Sequence[_GenRequest], tokens: int) -> None:
        """Tail of every settle phase: the occupancy gauge, and what the
        phase settled as its span's attributes."""
        self._gauge_kv_occupancy()
        if ph.traced:
            ph.set_attributes(tokens=tokens, finished=sum(
                1 for r in reqs if r.future.done()))

    def _emit(self, r: _GenRequest, toks: List[int], dt: float,
              record_intertoken: bool = True, revealed_at=()) -> None:
        """Stream ``toks`` to the future (partial results; ``revealed_at``
        beside them where the model reveals a block's positions over
        several forwards) and settle the request when it reaches its token
        budget or stop token. ``record_intertoken=False`` on the
        prefill-produced first token: its cost belongs to
        ``serving_first_token_seconds``, not the inter-token
        distribution."""
        if toks:
            if not r.emitted and _monitor.enabled():
                # a request's first tokens: the prefill's, or, where a
                # prefill streams none, its first committed block's
                _monitor.histogram(
                    "serving_first_token_seconds",
                    "submit-to-first-token latency (prefill + queue)"
                ).observe(self._now() - r.submitted)
            r.future._emit_tokens(toks, revealed_at)
            r.out_tokens.extend(toks)
            r.emitted += len(toks)
            if _monitor.enabled():
                _monitor.counter(
                    "serving_decode_tokens_total",
                    "tokens streamed to generative requests").inc(len(toks))
                if record_intertoken:
                    h = _monitor.histogram(
                        "serving_intertoken_seconds",
                        "per-token wall time within a decode chunk "
                        "(p50/p99 in the snapshot)")
                    for _ in toks:
                        h.observe(dt / len(toks))
        done = r.emitted >= r.max_new
        eos = self.gen_config.eos_id
        if not done and eos >= 0 and toks and toks[-1] == eos:
            done = True
        if done:
            self._retire(r)
            latency = self._now() - r.submitted
            with self._lock:
                self._acct["completed"] += 1
                self._dispatched -= 1
            self._record_outcome("completed")
            self._finish_request(r, "completed")
            if _monitor.enabled():
                # same exemplar contract as the base engine's _distribute
                ex = r.span.trace_id \
                    if _monitor.telemetry_enabled() else None
                _monitor.histogram(
                    "serving_request_latency_seconds",
                    "submit-to-response latency of completed requests "
                    "(p50/p99 in the snapshot)").observe(
                    latency, exemplar=ex or None)
            r.future._settle(
                result=[np.asarray(r.out_tokens, dtype=np.int64)])

    def _retire(self, r: _GenRequest, ended: Optional[int] = None) -> None:
        """``r`` leaves its slot (if it still holds it: a request whose
        budget was counted out at a launch left then, and the slot may be
        another's by now). The slot's gate is cleared on the device before
        the next launch. ``ended``: the ``index`` of the dispatch it ended
        in (default: the one being settled, else the last launched)."""
        if 0 <= r.slot < len(self._slots) and self._slots[r.slot] is r:
            self._slots[r.slot] = None
            self._gates.add(r.slot)
            if ended is None:
                ended = self._decode_launches if self._cursor is None \
                    else self._cursor
            self._vacated[r.slot] = ended

    def _fail_group(self, reqs: List[_GenRequest], err: BaseException,
                    phase: str) -> None:
        logger.warning(
            "serving: %s dispatch of %d stream(s) failed (%s: %s) — "
            "failing those streams typed, engine continues",
            phase, len(reqs), type(err).__name__, err)
        if _monitor.enabled():
            _monitor.counter("serving_batches_total",
                             "dispatched batches by result").labels(
                result="failed").inc()
        for r in reqs:
            self._retire(r)
            e = BatchFailed(
                f"serving: {phase} batch failed for stream #{r.seq}: "
                f"{type(err).__name__}: {err}")
            e.__cause__ = err
            self._settle_error(r, "failed", e, dispatched=True)
        _trace.record_incident(
            "batch_failed", error=err,
            context=reqs[0].span if reqs else None,
            detail=f"generative {phase}, {len(reqs)} stream(s)")

    def _fail_all_resident(self, err: BaseException, phase: str,
                           failed: Optional[_Launched] = None) -> None:
        """A real failure, at a launch or at the fetch of ``failed``: every
        request the engine holds fails typed, once: the residents and those
        of every dispatch in flight (the successors ran on state the failed
        one produced), whose results are dropped unfetched; then the
        generation state is planted anew, once."""
        dropped, self._inflight = self._inflight, []
        for e in dropped:
            e.pending.drop()
            e.span.end(error=err)
        resident = self._held(
            also=dropped + ([failed] if failed is not None else []))
        logger.error(
            "serving: %s dispatch raised %s — generation state may hold "
            "consumed buffers; failing all %d resident stream(s) typed "
            "and resetting the generation state",
            phase, type(err).__name__, len(resident))
        self._fail_group(resident, err, phase)
        self._gates.clear()         # the state planted below has none open
        self.reset_generation_state()

    # -- observability ---------------------------------------------------
    def _program_steps(self, program) -> frozenset:
        """Identities of the executor-cached compiled steps belonging to
        ``program`` — run-path keys lead with the program fingerprint
        ``(serial, ...)``, chained keys with ``("chained", fingerprint,
        ...)``. Scoped per program so unrelated compiles on a SHARED
        executor (a trainer thread, a sibling engine) can never read as
        this engine's recompiles."""
        serial = getattr(program, "_serial", None)
        with self._exe._lock:
            return frozenset(
                id(step) for key, step in self._exe._cache.items()
                if (key[0] == "chained" and key[1][0] == serial)
                or (isinstance(key[0], tuple) and key[0]
                    and key[0][0] == serial))

    def _note_compiles(self, phase: str, bucket: int, program) -> None:
        """The bucketed-recompile watchdog: a (phase, bucket) whose
        executable already exists must NEVER compile again — positions
        move, shapes don't. A NEW compiled step appearing for this
        phase's program after its first compile is counted on
        ``serving_decode_recompiles_total`` and logged loudly; the
        ``load_check --decode`` gate fails on a non-zero total."""
        key = (phase, int(bucket))
        steps = self._program_steps(program)
        prev = self._compiled_buckets.get(key)
        if prev is None:
            self._compiled_buckets[key] = steps
            return
        if steps - prev:
            self.decode_recompiles += 1
            logger.error(
                "serving: RECOMPILE on warm (phase=%s, bucket=%s) — a new "
                "executable was compiled for a program that was already "
                "compiled; KV growth must never reshape a decode dispatch",
                phase, bucket)
            if _monitor.enabled():
                _monitor.counter(
                    "serving_decode_recompiles_total",
                    "executable compiles beyond one per (phase, bucket) — "
                    "always a bug; gated to zero in CI").labels(
                    phase=phase, bucket=str(bucket)).inc()
            self._compiled_buckets[key] = prev | steps

    def _gauge_kv_occupancy(self) -> None:
        if not _monitor.enabled():
            return
        # a layer's cache holds min(length, its rows) rows of a sequence
        # (a windowed layer keeps the last ones only)
        P = self._page_size
        used = 0
        for r in self._slots:
            if r is not None:
                length = len(r.prompt) + r.emitted
                used += sum(n * -(-min(length, rows) // P)   # ceil
                            for rows, n in self._cache_rows.items())
        pages = sum(n * (rows // P) for rows, n in self._cache_rows.items())
        _monitor.gauge(
            "serving_kv_page_occupancy",
            "fraction of KV cache pages held by resident sequences"
        ).set(used / (pages * len(self._slots)))

    # -- what the device counted -------------------------------------------
    def _prefill_fetches(self, bucket: int) -> List[str]:
        net = self._model["prefill"][bucket]
        first = [] if self._block else [net["first_token"].name]
        return first + [v.name for v, _ in net.get("counted", ())]

    def _decode_fetches(self) -> List[str]:
        return self._fetch_names + [
            v.name for v, _ in self._model["decode"].get("counted", ())]

    def _count(self, phase: str, net: dict, fetched) -> dict:
        """Hands what a dispatch counted to what counts it. A counting
        function may return numbers of this ONE dispatch (a dict): they go
        on the dispatch's settle span, where a reader ties them to the
        dispatch's device time."""
        noted = {}
        if _monitor.enabled():
            for (_, count), stats in zip(net.get("counted", ()), fetched):
                noted.update(count(phase, np.asarray(stats), self._sums)
                             or {})
        return noted

    def generation_stats(self) -> dict:
        """Decode-side snapshot for reports: resident slots, compiled
        (phase, bucket) executables, recompiles, prefix-cache and
        speculative-decoding counters."""
        resident = sorted(r.seq for r in self._held())
        pc = self._prefix_cache
        return {
            "slots": len(self._slots),
            "resident": resident,
            "compiled_buckets": sorted(
                f"{p}:{b}" for (p, b) in self._compiled_buckets),
            "decode_recompiles": self.decode_recompiles,
            "max_seq": self._max_seq,
            "page_size": self._page_size,
            "prompt_buckets": list(self._buckets),
            "prefill_chunk": self._prefill_chunk,
            "prefill_chunks": self.prefill_chunks,
            "prefix_cache": pc.stats() if pc is not None else None,
            "speculative": {
                "enabled": self._speculative,
                "k": self._spec_k if self._speculative else 0,
                "chunks": self.spec_chunks,
                "accepted_tokens": self.spec_accepted,
            },
        }
