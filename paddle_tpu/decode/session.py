"""DecodeSession: continuous batching at token granularity.

The session owns ``max_slots`` fixed batch lanes and runs ONE
fixed-shape decode step over all of them a tick — inactive lanes ride
along masked (their page tables point at the reserved null page), so
the compiled program's shapes never change as the batch composition
churns.  A tick (``step()``) over a model that steps in two halves
(``step_dispatch`` / ``step_collect``: the paged skeleton,
``PagedDecoderLM``) is ordered so that the host's part runs under the
device's step:

1. **collect**: wait for the ids of the step that is in flight, if one
   is.
2. **decide**: per live slot, from the ids: deadline, cancel, the next
   token, whether the sequence ends (EOS or token budget).  A slot that
   ends is cleared here — its lane's table row nulled, its pages back
   on the allocator's free list — so a sequence that ended at step *k*
   is never live in a step dispatched after that.  Nothing is emitted
   yet.
3. **sweep, admit, copy-on-write**: pending requests claim open slots
   while the page pool can hold their whole context (prompt + every
   token they may generate — reserved up front, so a running sequence
   can never hit mid-flight exhaustion).  Admission runs the model's
   prefill, on an idle device, and emits the first token at once.
4. **dispatch** step *k+1*.  Its inputs are what the device already
   holds (the last step's ids and lengths, the tables): a steady tick
   uploads nothing, and a tick in which the host changed a row uploads
   the arrays that row is in (``_StepInputs``).
5. **deliver**: emit step *k*'s tokens to their requests and finish the
   requests that ended in 2, while the device computes step *k+1*.

A model without the two halves (``PagedSeq2SeqModel``, a wrapper that
overrides ``decode``) runs ``decode`` whole where 4 is, followed by 1,
2 and 5 at once; so does a speculative tick with ``verify_chunk``.

**Up to two steps are in flight.**  Between the device's last op of
step *k+1* and the first of step *k+2* lie the ids' way to the host and
the host's decide and dispatch: a gap of 2-3 ms a tick in which the
chip does nothing.  So after 4 the tick dispatches step *k+2* behind
step *k+1*, fed by nothing but what that step hands on
(``StepInFlight.next``), whenever no row can change in between
(``_ahead_held``): every slot holds a live sequence that takes the
device's own token, none reaches its budget in the step in flight,
nothing the host wrote is missing on the device and no page the next
row lands in is shared.  No admission is possible before step *k+1*
lands then, queued step or not, so the step behind delays nobody.  The
next tick collects the OLDEST flight (1, 2), finds one still in flight,
skips 3 but for the sweep, which is the queue's (no prefill or page
copy runs under a step: the host's mirror of the lanes is exact only
with nothing in flight), dispatches the next one behind it if the
condition still holds, and delivers.
When the condition fails nothing is queued, the flights drain, and the
tick in which none is left is the tick above.  What the condition
cannot see is absorbed: a slot that ends by EOS, deadline or cancel
while a step is queued behind has one junk row computed into pages that
still held the room, its lane is skipped when that step is decided, the
cleared lane marks the arrays stale so nothing more is queued, and its
slot is seated again in the tick in which nothing is in flight.

**The tick keeps its own account, in every run** (``TickAccount``).
The statement that opens a phase's span (``observability.phase``, here
and in the model's ``step_collect`` / ``step_dispatch``) also adds its
seconds to the account, so a phase's counter and its span have the
same two edges; ``step()`` flushes the account to the registry once a
tick (seconds by phase, admissions a tick and by prefill bucket, the
slot-seconds an admission held the seated slots still) and a tick that
took far longer than its kind does is counted, kept (``slow_ticks``, in
``/health``) and logged with what it was doing.

The model behind the session is pluggable (``PagedSeq2SeqModel`` for
v1 beam_search specs, ``TinyDecoderLM`` for transformer self-attention
KV); ``generation.py``'s greedy path is the exact dense oracle the
parity tests pin this against.
"""

from __future__ import annotations

import collections
import gc
import itertools
import logging
import threading
import time
from typing import Callable, List, Optional

import numpy as np

from paddle_tpu.decode.paged_kv import PoolExhausted, PoolsLost, cow_split
from paddle_tpu.decode.spec import accept_greedy, observe_chunk
from paddle_tpu.generation import beam_select
from paddle_tpu.observability import metrics as _metrics
from paddle_tpu.observability.events import (PhaseAccount, phase, span,
                                             straddling_phase)

_LOG = logging.getLogger(__name__)

_M_ACTIVE = _metrics.gauge(
    "decode_active_slots", "sequences currently decoding in the session")
_M_WAITING = _metrics.gauge(
    "decode_waiting_requests", "admitted-but-queued generation requests")
_M_CACHE_ROWS = _metrics.gauge(
    "decode_cache_rows",
    "K/V rows resident for the seated sequences, summed over layers, by "
    "kind of cache: `full` = layers that keep every row, `window` = "
    "layers on bounded rings, `state` = recurrent layers' entries, one "
    "a sequence a layer whatever its length, `latent` = latent layers' "
    "rows, one compressed row a token a layer in the place of K and V "
    "heads (the model's `cache_rows`)")
_M_CACHE_BYTES = _metrics.gauge(
    "decode_cache_bytes",
    "bytes resident for the seated sequences by kind of cache (the "
    "model's `cache_bytes`): `full` = the K/V rows of layers that keep "
    "every row, `state` = the recurrent layers' state entries, `latent` "
    "= the latent rows as stored")
_M_STEPS = _metrics.counter(
    "decode_steps_total", "fixed-shape decode steps dispatched")
_M_SLOT_STEPS = _metrics.counter(
    "decode_active_slot_steps_total",
    "live slots summed over dispatched decode steps; over "
    "decode_steps_total x max_slots it is occupancy (a masked lane is "
    "computed and thrown away)")
_M_QUEUE_WAIT = _metrics.histogram(
    "decode_queue_wait_seconds",
    "submit to the admission that seats the request (its prefill "
    "follows), once per request")
_M_TOKENS = _metrics.counter(
    "decode_tokens_total", "tokens generated across all sequences")
_M_REFUSED = _metrics.counter(
    "decode_admission_refused_total",
    "generation requests refused at admission, by reason")
_M_STEP_SEC = _metrics.histogram(
    "decode_step_seconds",
    "one batched decode step from its dispatch to its ids on the host "
    "(over a model that steps in two halves that is two ticks' time: "
    "the delivery of the step before runs inside it); a step dispatched "
    "behind another from the landing of the one in front, so one step "
    "still")
_M_STEP_INPUTS = _metrics.counter(
    "decode_step_inputs_total",
    "decode and verify steps dispatched, by where their tables, lengths "
    "and tokens came from: `resident` = all three were what the device "
    "already held, `uploaded` = the host refreshed at least one")
_M_DELIVERIES = _metrics.counter(
    "decode_deliveries_total",
    "deliveries of one step's tokens to their requests, by what the "
    "device was doing meanwhile: `step` = the next step was in flight, "
    "`nothing` = no step was")
_M_DISPATCHES = _metrics.counter(
    "decode_step_dispatch_total",
    "decode steps dispatched over a model that steps in two halves, by "
    "what was in flight at the dispatch: `step` = the step before, not "
    "yet collected (the device runs the two back to back), `nothing` = "
    "no step was (the device waits for this dispatch)")
# `why` of decode_ahead_held_total, in the order the tick asks
AHEAD_HELD = ("draft", "free_slot", "budget", "stale", "host_choice", "cow")
_M_AHEAD_HELD = _metrics.counter(
    "decode_ahead_held_total",
    "ticks that had a step in flight and dispatched none behind it, by "
    "the first condition that failed: `draft` = a speculative session, "
    "`free_slot` = a slot holds no live sequence, `budget` = a slot's "
    "last token is the flight's, `stale` = the host changed a row the "
    "device lacks, `host_choice` = a slot samples or is a beam's, `cow` "
    "= the row after the flight's lands in a shared page")
_M_PREFILL_SEC = _metrics.histogram(
    "decode_prefill_seconds", "wall time per sequence prefill (admission)")
_M_TTFT = _metrics.histogram(
    "decode_ttft_seconds", "submit-to-first-token latency per sequence")
_M_REQ_SEC = _metrics.histogram(
    "decode_request_seconds", "submit-to-finish latency per sequence")
_M_CHOICE = _metrics.counter(
    "decode_token_choice_total",
    "tokens chosen at decode and verify steps, one per live slot per "
    "step (one per group for a beam), by where: `device` = taken from "
    "the ids the step chose, `host` = chosen on the host from the "
    "step's logits (sampling, beams, a model that hands out no ids)")
_M_STEP_FAIL = _metrics.counter(
    "decode_step_failures_total",
    "decode/verify dispatches that raised, and prefills or page copies "
    "that lost the pools (contained per-slot, stepper survives)")
_M_CANCELLED = _metrics.counter(
    "decode_cancelled_total",
    "generation requests cancelled by their consumer (pages freed)")


# -- the tick's account ------------------------------------------------------

# ``phase`` label of ``decode_tick_seconds_total`` -> the span whose
# statement charges it, in a tick's order.  ``prefill`` and
# ``first_token`` lie inside ``admit``, and ``prefill_wait`` inside
# ``prefill`` (the model's ``prefill`` writes it: the wait for the
# logits' row, so the program's run; ``prefill`` less it is the host's
# part of a prefill): all three are kept beside ``admit``, never summed
# with it; ``upload`` and ``dispatch`` lie inside ``decode.step``, which
# is no phase.  Two more labels have no statement: ``other`` is the
# ``decode.tick`` span less its top-level phases, and ``between`` (from
# the end of one tick to the start of the next while the session is not
# idle: the stepper waiting for the interpreter lock) lies outside the
# tick.
PHASE_SPANS = {
    "between": "decode.between",
    "collect": "decode.logits_to_host",
    "decide": "decode.sample",
    "sweep": "decode.sweep",
    "admit": "decode.admit",
    "prefill": "decode.prefill",
    "prefill_wait": "decode.prefill_wait",
    "first_token": "decode.first_token",
    "cow": "decode.cow",
    "upload": "decode.upload",
    "dispatch": "decode.dispatch",
    "deliver": "decode.deliver",
}
_NESTED = ("prefill", "prefill_wait", "first_token")

# A tick (with the ``between`` before it) is slow when it lasts longer
# than both: SLOW_TICK_FACTOR times the running mean of the ticks of its
# kind (plain, or admitting), and SLOW_TICK_FLOOR_S.  Set from the chip's
# runs (PERF.md, PR 37): the K-EXAONE cell's longest sound tick read
# 0.437 s (two long prompts seated in one tick, the longer admission
# 0.222 s) beside admitting ticks of ~0.09 s, so 8 x is 0.7 s there; its
# plain ticks and every tick of the other two cells (13-65 ms) are
# judged by the floor; the silences to be caught lasted 1.2-2.5 s.
SLOW_TICK_FACTOR = 8.0
SLOW_TICK_FLOOR_S = 0.6
SLOW_TICKS_KEPT = 16
_MEAN_OVER_TICKS = 64       # what a kind's running mean forgets over
_ADMISSIONS_TOP = 4         # decode_tick_admissions_total's last bucket, "4+"

_GC = [0.0, 0.0]    # seconds in garbage collections; start of the open one


def _on_gc(when, info):
    # a collection stops every thread of the interpreter: the seconds
    # of those that fell inside a slow tick go on its record
    if when == "start":
        _GC[1] = time.perf_counter()
    else:
        _GC[0] += time.perf_counter() - _GC[1]


gc.callbacks.append(_on_gc)


class TickAccount:
    """What one tick owes the registry, filled while the tick runs and
    flushed once at its end: seconds by phase (``phases``: charged by
    the ``phase`` statements that run while it is open on the thread),
    the admissions it seated, by prefill bucket (how many, the
    slot-seconds they held the seated slots still, on real rows and on
    padding), where its steps' inputs came from, behind what they were
    dispatched (or why none was, behind a flight) and under what
    its deliveries ran.  Also the judge of a slow tick.  The families
    live in ``registry`` (the process's unless given: the overhead probe
    gives its own).  ``packed_rows``: the model's
    ``packed_prefill_rows``, or None for a model without a bucket
    ladder."""

    def __init__(self, registry=None, packed_rows=None):
        reg = registry or _metrics.REGISTRY
        self._packed_rows = packed_rows
        self.labels = (*PHASE_SPANS, "other")
        self.phases = PhaseAccount(("decode.tick", *PHASE_SPANS.values()))
        self._top = [i for i, label in enumerate(PHASE_SPANS)
                     if label not in _NESTED + ("between",)]
        self._m_seconds = reg.counter(
            "decode_tick_seconds_total",
            "seconds of the stepper's ticks by phase (the span that bears "
            "the phase's name charges it; `prefill` and `first_token` are "
            "inside `admit`, `prefill_wait` inside `prefill`; `other` = "
            "the tick less its top-level phases; "
            "`between` = from one tick's end to the next one's start while "
            "the session is not idle), and by whether the tick seated a "
            "request (`admitting`)")
        self._m_ticks = reg.counter(
            "decode_ticks_total",
            "ticks of the stepper, by whether they seated a request")
        self._m_admissions = reg.counter(
            "decode_tick_admissions_total",
            "ticks by the number of requests they seated (`n`: 0, 1, 2, 3, "
            "4+): admissions a tick, and the depth of a convoy")
        # by `bucket`: the rows of the prefill program the admission
        # ran, "suffix" for a prefill over cached pages, "none" for one
        # that ran no bucketed program (a request sent back to the
        # queue; a model without a ladder)
        self._m_admitted = reg.counter(
            "decode_admissions_total",
            "requests seated, by the prefill `bucket` they ran")
        self._m_stalled = reg.counter(
            "decode_admit_stalled_slot_seconds_total",
            "for every admission, its seconds times the slots that were "
            "seated and live before it: slot-time in which a sequence "
            "produced nothing because another request was prefilled, "
            "by `bucket` and, if a prefill's seconds go by its rows, by "
            "the `kind` of rows they went on: `pad` a seated admission's "
            "slot-seconds times pad rows over bucket rows, `real` the "
            "rest")
        self._m_tick_rows = reg.counter(
            "decode_admit_tick_rows_total",
            "once an admitting tick, by `kind`: `run` the bucket rows "
            "its admissions computed, `packed` the rows the model's "
            "ladder would compute for the same prompts laid end to end, "
            "where those are fewer (a counterfactual: no program packs "
            "prompts so)")
        self._m_slot_seconds = reg.counter(
            "decode_slot_seconds_total",
            "every tick's seconds (with the `between` before it) times "
            "the slots live at its end")
        self._m_inputs = reg.counter(_M_STEP_INPUTS.name, _M_STEP_INPUTS.help)
        self._m_deliveries = reg.counter(_M_DELIVERIES.name,
                                         _M_DELIVERIES.help)
        self._m_behind = reg.counter(_M_DISPATCHES.name, _M_DISPATCHES.help)
        self._m_held = reg.counter(_M_AHEAD_HELD.name, _M_AHEAD_HELD.help)
        self._m_slow = reg.counter(
            "decode_slow_ticks_total",
            "ticks that lasted over SLOW_TICK_FACTOR times the running "
            "mean of their kind and over SLOW_TICK_FLOOR_S, by the phase "
            "that took most of them (each is in /health's "
            "generation.slow_ticks and in the log)")
        key = _metrics.label_key
        self._k_seconds = {a: [key(phase=label, admitting=a)
                               for label in self.labels] for a in "01"}
        self._k_ticks = {a: key(admitting=a) for a in "01"}
        self._k_admissions = [key(n=n) for n in ("0", "1", "2", "3", "4+")]
        self._k_inputs = [key(source="resident"), key(source="uploaded")]
        self._k_under = [key(under="step"), key(under="nothing")]
        self._k_behind = [key(behind="nothing"), key(behind="step")]
        self._k_held = {why: key(why=why) for why in AHEAD_HELD}
        # a bucket -> its keys: admissions, stalled on real rows, on pad
        self._k_bucket: dict = {}
        self._k_tick_rows = [key(kind="run"), key(kind="packed")]
        self.slow_ticks: collections.deque = collections.deque(
            maxlen=SLOW_TICKS_KEPT)
        self._mean = {"0": None, "1": None}     # seconds, by `admitting`
        self._reset()

    def _reset(self) -> None:
        self.admissions: List[dict] = []    # the seated ones' span args
        # this tick's lines by bucket, as `inc_many` takes them: the
        # requests seated, the slot-seconds their admissions held the
        # seated slots still
        self.seated: List[tuple] = []
        self.stalled: List[tuple] = []
        self.run = self.rows = 0    # the seated ones' buckets, prompts
        self.inputs = [0, 0]                # steps: resident, uploaded
        self.under = [0, 0]                 # deliveries: step, nothing
        self.behind = [0, 0]                # dispatches behind: nothing, step
        self.held: Optional[str] = None     # why none went behind a flight

    def admitted(self, admit: phase, live_before: int, seated: bool) -> None:
        """One ``decode.admit`` ended: whatever came of it, the slots
        that were seated stood still for its seconds."""
        args = admit.args
        bucket = args.get("bucket")
        label = "suffix" if args.get("cached_len") else bucket or "none"
        keys = self._k_bucket.get(label)
        if keys is None:
            key = _metrics.label_key
            keys = self._k_bucket[label] = (
                key(bucket=str(label)), key(bucket=str(label), kind="real"),
                key(bucket=str(label), kind="pad"))
        stood = admit.seconds * live_before
        if seated:
            self.admissions.append(args)
            self.seated.append((keys[0], 1))
            if bucket:
                rows = args["prompt_len"]
                self.run += bucket
                self.rows += rows
                if stood and rows < bucket:
                    on_pad = stood * (1 - rows / bucket)
                    self.stalled.append((keys[2], on_pad))
                    stood -= on_pad
        if stood:
            self.stalled.append((keys[1], stood))

    def flush(self, at: float, live: int, active: int, waiting: int,
              in_flight: bool, gc_seconds: float) -> None:
        """The tick that started at ``at`` (``perf_counter``) is over:
        its lines go to the registry, each family under one acquire of
        its lock, and the account starts anew."""
        tick, *secs = self.phases.take()
        secs.append(max(tick - sum(secs[i] for i in self._top), 0.0))
        total = tick + secs[0]              # with the `between` before it
        n = len(self.admissions)
        admitting = "1" if n else "0"
        self._m_seconds.inc_many(
            [kv for kv in zip(self._k_seconds[admitting], secs) if kv[1]])
        self._m_ticks.inc_many(((self._k_ticks[admitting], 1),))
        self._m_admissions.inc_many(
            ((self._k_admissions[min(n, _ADMISSIONS_TOP)], 1),))
        if n:
            self._flush_admissions()
        if self.stalled:
            self._m_stalled.inc_many(self.stalled)
        if live:
            self._m_slot_seconds.inc(total * live)
        for family, keys, counts in (
                (self._m_inputs, self._k_inputs, self.inputs),
                (self._m_deliveries, self._k_under, self.under),
                (self._m_behind, self._k_behind, self.behind)):
            if counts[0] or counts[1]:
                family.inc_many([kv for kv in zip(keys, counts) if kv[1]])
        if self.held is not None:
            self._m_held.inc_many(((self._k_held[self.held], 1),))
        mean = self._mean[admitting]
        if total > SLOW_TICK_FLOOR_S and (
                mean is None or total > SLOW_TICK_FACTOR * mean):
            self._slow(at, total, secs, active, waiting, in_flight,
                       gc_seconds)
        else:   # a slow tick is not what ticks of its kind take
            self._mean[admitting] = (total if mean is None else
                                     mean + (total - mean) / _MEAN_OVER_TICKS)
        self._reset()

    def _flush_admissions(self) -> None:
        """The seated requests by bucket, and what the bucketed ones
        ran beside what they would packed."""
        self._m_admitted.inc_many(self.seated)
        if self.run and self._packed_rows is not None:
            # a packer would leave alone a tick whose prompts, laid end
            # to end, land in a larger bucket than their own add up to
            k_run, k_packed = self._k_tick_rows
            self._m_tick_rows.inc_many((
                (k_run, self.run),
                (k_packed, min(self.run, self._packed_rows(self.rows)))))

    def _slow(self, at, total, secs, active, waiting, in_flight,
              gc_seconds) -> None:
        by = dict(zip(self.labels, secs))
        worst = max((label for label in by if label not in _NESTED),
                    key=by.get)
        self._m_slow.inc(phase=worst)
        record = {
            "at": at, "seconds": total, "phase": worst,
            "phases": {label: v for label, v in by.items() if v},
            "active": active, "waiting": waiting,
            "admissions": [{k: a.get(k) for k in
                            ("prompt_len", "bucket", "cached_len")}
                           for a in self.admissions],
            "in_flight": in_flight,
            "uploaded": bool(self.inputs[1]) if sum(self.inputs) else None,
            "gc_seconds": gc_seconds}
        self.slow_ticks.append(record)
        _LOG.warning(
            "decode.slow_tick at=%.6f seconds=%.6f phase=%s %s active=%d "
            "waiting=%d admitted=%d admissions=%s in_flight=%d uploaded=%s "
            "gc_s=%.6f", at, total, worst,
            " ".join(f"{label}_s={v:.6f}" for label, v in by.items() if v),
            active, waiting, len(record["admissions"]),
            ",".join("{prompt_len}/{bucket}/{cached_len}".format(**a)
                     for a in record["admissions"]) or "-",
            in_flight, "-" if record["uploaded"] is None
            else int(record["uploaded"]), gc_seconds)


_RIDS = itertools.count(1)


def next_rid() -> int:
    """A process-wide request id: every span of one request carries it
    (``rid``), from the serving handler down to the decode tick."""
    return next(_RIDS)


def _prompt_len(prompt) -> int:
    # a seq2seq prompt is one reader row wrapping the id list
    if len(prompt) == 1 and isinstance(prompt[0], (list, tuple)):
        return len(prompt[0])
    return len(prompt)


class AdmissionRefused(RuntimeError):
    """The session cannot take this request (pool exhausted / too long
    / queue full).  Serving maps this to 503 — graceful refusal, never
    a crash of live sequences."""

    def __init__(self, reason: str, message: str):
        super().__init__(message)
        self.reason = reason


class DecodeRequest:
    """One generation request: prompt in, streamed tokens out.

    ``temperature``/``top_k``/``seed`` opt into per-slot sampling:
    temperature scales the next-token distribution (0/None = greedy
    argmax), top_k keeps only the k most likely tokens, and seed pins
    the slot's own RNG so a request replays bit-identically regardless
    of what else shares the batch.  top_k/seed without temperature is
    rejected (ValueError) rather than silently decoded greedily."""

    def __init__(self, prompt, max_new_tokens: int = 32,
                 on_token: Optional[Callable[[int], None]] = None,
                 deadline: Optional[float] = None,
                 temperature: Optional[float] = None,
                 top_k: Optional[int] = None,
                 seed: Optional[int] = None,
                 rid: Optional[int] = None):
        self.rid = next_rid() if rid is None else rid
        self.prompt = prompt
        self.max_new_tokens = int(max_new_tokens)
        self.on_token = on_token
        self.deadline = deadline            # time.monotonic timestamp
        if (top_k or seed is not None) and not temperature:
            raise ValueError(
                "top_k/seed require temperature > 0; without it decoding "
                "is greedy argmax and they would be silently ignored")
        self.temperature = (None if not temperature
                            else float(temperature))
        self.top_k = None if not top_k else int(top_k)
        self.seed = seed
        self.tokens: List[int] = []
        self.error: Optional[BaseException] = None
        self.finish_reason: Optional[str] = None   # eos|length|deadline|error
        self.submitted_at = time.monotonic()
        self.admitted_at: Optional[float] = None   # first seating
        self.first_token_at: Optional[float] = None
        self.step_failures = 0         # decode steps that died under us
        self.cancelled = False         # consumer gone; evict next tick
        self._done = threading.Event()

    # -- waiter side --------------------------------------------------------

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self._done.wait(timeout)

    @property
    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: Optional[float] = None) -> List[int]:
        if not self.wait(timeout):
            raise TimeoutError("generation did not finish in time")
        if self.error is not None:
            raise self.error
        return list(self.tokens)

    # -- session side -------------------------------------------------------

    def _emit(self, token: int) -> None:
        now = time.monotonic()
        if self.first_token_at is None:
            self.first_token_at = now
            _M_TTFT.observe(now - self.submitted_at)
        self.tokens.append(int(token))
        if self.on_token is not None:
            try:
                self.on_token(int(token))
            except Exception:
                pass  # a dead stream consumer must not kill the batch

    def _finish(self, reason: str,
                error: Optional[BaseException] = None) -> None:
        if self._done.is_set():
            return
        self.finish_reason = reason
        self.error = error
        _M_REQ_SEC.observe(time.monotonic() - self.submitted_at)
        self._done.set()

    def expired(self, now: float) -> bool:
        return self.deadline is not None and now > self.deadline

    def cancel(self) -> None:
        """Consumer-side abandon (disconnected stream): flag the
        request; the stepper evicts the slot and frees its pages where
        its next step is decided, or drops it from the queue at the
        next tick (never cross-thread surgery on live slot state)."""
        self.cancelled = True


class BeamRequest(DecodeRequest):
    """Beam-search generation through the session: the beam's k
    hypotheses ride k sibling slots forked from one prefilled prompt
    (pages shared copy-on-write), selection reuses the exact host-side
    bookkeeping of the dense ``SequenceGenerator`` oracle
    (``generation.beam_select``).  ``result()`` returns the best
    hypothesis' ids; ``beams`` holds the full [(score, ids), ...]
    best-first."""

    def __init__(self, prompt, beam_size: int, max_new_tokens: int = 32,
                 deadline: Optional[float] = None,
                 rid: Optional[int] = None):
        super().__init__(prompt, max_new_tokens=max_new_tokens,
                         deadline=deadline, rid=rid)
        if beam_size < 1:
            raise ValueError(f"beam_size must be >= 1, got {beam_size}")
        self.beam_size = int(beam_size)
        self.beams: Optional[List[tuple]] = None


class _Slot:
    __slots__ = ("req", "pages", "ctx_len", "new_tokens", "group",
                 "member", "dead", "rng")

    def __init__(self, req: DecodeRequest, pages: List[int], ctx_len: int,
                 group: Optional["_BeamGroup"] = None, member: int = 0):
        self.req = req
        self.pages = pages
        self.ctx_len = int(ctx_len)
        self.new_tokens = 0
        self.group = group
        self.member = member
        self.dead = False               # beam member frozen (score kept)
        self.rng = (np.random.default_rng(req.seed)
                    if req.temperature else None)


class _BeamGroup:
    """Host-side beam state shared by k sibling slots (one request)."""

    __slots__ = ("req", "slot_idx", "k", "scores", "alive", "seqs",
                 "selects")

    def __init__(self, req: BeamRequest, slot_idx: List[int]):
        self.req = req
        self.slot_idx = slot_idx
        self.k = req.beam_size
        self.scores = np.full((self.k,), -np.inf, np.float32)
        self.scores[0] = 0.0            # identical beams start as one
        self.alive = np.ones((self.k,), bool)
        self.seqs: List[List[int]] = [[] for _ in range(self.k)]
        self.selects = 0                # beam_select calls consumed


class _StepInputs:
    """What a decode step reads of every lane — page tables, lengths,
    the token to feed — twice: the host's mirror (numpy, exact whenever
    no step is in flight: under one it lacks what that step will
    produce, so nothing reads it then) and, over a model that steps in
    two halves, what the device holds since the last dispatch
    (``resident``: the newest ``StepInFlight.next``, opaque here).
    ``stale`` names the arrays in which the host has changed a row that
    the device's copy lacks: every write goes through a method that
    says so, except what a step itself produces (its ids as the next
    tokens, the live lanes' lengths plus one: ``from_step``).  With
    nothing stale the next step's three inputs are ``resident`` as it
    stands, whether the step that hands them on has landed or not: that
    is what a step dispatched behind another is fed.  The
    device-resident slot state of the session is this object and
    nothing else."""

    NAMES = ("tokens", "tables", "lens")
    __slots__ = NAMES + ("stale", "resident", "_pad")

    def __init__(self, slots: int, pages_per_seq: int, pad_token: int):
        self.tokens = np.full((slots, 1), pad_token, np.int64)
        self.tables = np.zeros((slots, pages_per_seq), np.int32)  # null page
        self.lens = np.ones((slots,), np.int64)
        self.stale = set(self.NAMES)
        self.resident = None
        self._pad = pad_token

    def seat(self, i: int, table, length: int, token: int) -> None:
        """Lane ``i`` gets a sequence (an admission, a beam reorder)."""
        self.tables[i], self.lens[i], self.tokens[i, 0] = table, length, token
        self.stale.update(self.NAMES)

    def clear(self, i: int, token: Optional[int] = None) -> None:
        """Lane ``i`` rides along masked from the next step on."""
        self.seat(i, 0, 1, self._pad if token is None else token)

    def retable(self, i: int, table) -> None:
        self.tables[i] = table
        self.stale.add("tables")

    def token(self, i: int, token: int, from_step: bool = False) -> None:
        """Lane ``i``'s next input: ``from_step`` when it is the id the
        landed step itself chose, which the device then holds too; else
        the host chose it (sampling; a verified chunk's last token)."""
        self.tokens[i, 0] = token
        if not from_step:
            self.stale.add("tokens")

    def length(self, i: int, length: int, from_step: bool = False) -> None:
        """Lane ``i``'s length: ``from_step`` when it is the landed
        step's plus one for a live lane, which the device has added
        too; else only the host knows it (a verified chunk's accepted
        part)."""
        self.lens[i] = length
        if not from_step:
            self.stale.add("lens")

    def forget(self) -> None:
        """The device's copies are not to be trusted (a step failed, or
        was dropped in flight): the next dispatch uploads all three."""
        self.resident = None

    def for_dispatch(self):
        """-> (tokens, tables, lens, any uploaded): for each the mirror
        where the device's copy is stale or missing, else the
        device's."""
        if self.resident is None:
            self.stale.update(self.NAMES)
        picked = [getattr(self, n) if n in self.stale else self.resident[n]
                  for n in self.NAMES]
        return (*picked, bool(self.stale))

    def dispatched(self, handed_on) -> None:
        """A step took what ``for_dispatch`` gave; ``handed_on`` is what
        the device holds for the one after it."""
        self.resident = handed_on
        self.stale.clear()


class _Outbox:
    """What one tick's token choices owe the requests: filled where the
    tokens are decided, paid by ``_deliver``, in order.  ``events`` are
    ``(request, token, None)`` to emit and ``(request, None, (reason,
    error))`` to finish; the counts are the registry's, bumped at the
    delivery; ``decided`` says that a landed step's choices are in
    here."""

    __slots__ = ("events", "tokens", "on_device", "on_host", "decided")

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self.events: List[tuple] = []
        self.tokens = self.on_device = self.on_host = 0
        self.decided = False


class _Flight:
    """A step in flight: the model's handle, the lanes that were live
    when it was dispatched and when that was (``t0``; for a step
    dispatched behind another, when the one in front landed: what
    ``decode_step_seconds`` measures from).  The session holds at most
    two, oldest first, and no prefill or page copy runs while it holds
    any: the host changes a row under a step in
    flight only by ending a sequence at the decide of the step in front
    (EOS, a deadline, a cancel), which the step behind absorbs (the
    module's docstring)."""

    __slots__ = ("step", "lanes", "t0")

    def __init__(self, step, lanes: List[int], t0: float):
        self.step, self.lanes, self.t0 = step, lanes, t0


class DecodeSession:
    """Token-granularity continuous batching over a paged model.

    ``model`` contract (duck-typed; see seq2seq.PagedSeq2SeqModel and
    model.TinyDecoderLM):

    - ``allocator``/``page_size``/``pages_per_seq``: paging geometry
    - ``bos_id``/``eos_id``: token conventions
    - ``grows_kv``: True when each decode step appends one KV row
      (transformer self-attention) — the session then reserves pages
      for prompt+budget at admission and advances lengths per step
    - ``context_pages(prompt, max_new) -> int``: pages to reserve
    - ``prefill(prompt, pages) -> (ctx_len, state_rows, first_logits)``
      where ``state_rows`` is one row per state buffer and
      ``first_logits`` (or None) scores the first generated token
    - ``state_specs -> [(row_shape, dtype), ...]``
    - ``decode(tokens (S,1), states, page_tables (S,P), lens (S,))
      -> (logits (S,V), new_states)``

    Sharing extensions (all optional, duck-typed):

    - ``copy_page(src, dst)``: device copy of one page — required for
      copy-on-write splits (beam forks / prefix-cache donors)
    - ``supports_prefix_cache`` + ``prefill(..., cached_len=)``: resume
      a prefill after ``cached_len`` rows already paged by the cache
    - ``verify_chunk(tokens (S,k), states, tables, lens) -> (logits
      (S,k,V), new_states)``: score k tokens per slot in one step —
      enables speculative decoding
    - ``emits_probs``: decode returns distributions, not raw logits
      (affects sampling/beam log-prob handling)
    - ``vocab``: the ids the model holds are ``0..vocab - 1`` (a slice
      of the vocabulary where the rest is on other chips); ``submit``
      raises ValueError (a 400 at the front) for a prompt id outside
    - ``supports_fork`` False: a sequence's pages cannot be aliased (a
      model that keeps window layers on per-sequence rings); a beam
      request is refused as ``beam_unsupported``
    - ``cache_rows(lens) -> {kind: rows}``: K/V rows resident for
      sequences of those lengths, by kind of cache; gauged every tick
      as ``decode_cache_rows{kind}``; ``cache_bytes(lens)`` likewise
      as ``decode_cache_bytes{kind}``
    - ``allocator`` may be a ``CacheManager``: a sequence's
      reservation (``context_pages`` units) is then its pages and one
      state entry, taken and given back together; a request waits
      while either is short
    - ``supports_verify`` False: the model cannot roll a speculative
      chunk back (a recurrent state); a draft is not used
    - ``prefill_bucket(prompt_len) -> int``: rows the full-prompt
      prefill pads to; it and the pad go on the ``decode.prefill`` span
    - logits from ``decode`` / ``verify_chunk`` that carry ``ids``
      (``model.StepLogits``: the step's own greedy choice, on the
      host, the logits still on the device): a greedy slot's token is
      taken from them, and the logits are read only when a live slot
      samples or belongs to a beam; logits without ``ids`` are chosen
      from on the host, slot by slot
    - ``step_dispatch(tokens, states, tables, lens) -> step`` and
      ``step_collect(step) -> (logits, new_states)``, defined by the
      model's class: ``decode`` in two halves, the first returning
      before the device is done.  The tick then dispatches step *k+1*
      before it delivers step *k*'s tokens (the order in this module's
      docstring).  ``step.next`` maps ``tokens`` / ``tables`` /
      ``lens`` to what the device holds of them for the step after; the
      session hands those back in place of its numpy arrays wherever it
      has changed no row since, so a steady tick uploads nothing.
      Looked up on the type, as Python looks up its own protocols: a
      wrapper that forwards attributes and overrides ``decode`` is
      served through its ``decode``, whole, in the old order
    """

    def __init__(self, model, max_slots: int = 8,
                 max_waiting: Optional[int] = None,
                 prefix_cache=None, spec_draft=None, spec_k: int = 4):
        self.model = model
        self.max_slots = int(max_slots)
        self.max_waiting = max_waiting
        # prefix cache: only meaningful when the model can resume a
        # prefill mid-prompt (supports_prefix_cache)
        self._prefix = (prefix_cache
                        if getattr(model, "supports_prefix_cache", False)
                        else None)
        # speculative mode: draft proposes spec_k - 1 tokens, the model
        # verifies the whole chunk in one step (needs verify_chunk)
        self._spec_draft = (spec_draft
                            if hasattr(model, "verify_chunk")
                            and getattr(model, "supports_verify", True)
                            and getattr(model, "grows_kv", False)
                            else None)
        self.spec_k = int(spec_k)
        if self._spec_draft is not None and self.spec_k < 2:
            raise ValueError("speculative decoding needs spec_k >= 2")
        self._lock = threading.Lock()
        self._pending: List[DecodeRequest] = []
        self._slots: List[Optional[_Slot]] = [None] * self.max_slots
        S = self.max_slots
        self._inputs = _StepInputs(S, model.pages_per_seq, model.bos_id)
        self._states = [np.zeros((S,) + tuple(shape), dtype)
                        for shape, dtype in model.state_specs]
        self._two_halves = all(
            callable(getattr(type(model), half, None))
            for half in ("step_dispatch", "step_collect"))
        self._flights: List[_Flight] = []       # at most two, oldest first
        self._outbox = _Outbox()
        self._account = TickAccount(
            packed_rows=getattr(model, "packed_prefill_rows", None))
        self._between: Optional[phase] = None   # open from a tick's end on
        self._gc_mark = _GC[0]

    @property
    def prefix_cache(self):
        return self._prefix

    # -- submission ---------------------------------------------------------

    def submit(self, req: DecodeRequest) -> DecodeRequest:
        """Queue a request; raises AdmissionRefused when it can never
        run (too long for the pool) or the wait queue is full."""
        if self._spec_draft is not None and (
                req.temperature or isinstance(req, BeamRequest)):
            _M_REFUSED.inc(reason="spec_mode")
            raise AdmissionRefused(
                "spec_mode", "a speculative session verifies greedy "
                "chunks; sampling and beam search are not available")
        vocab = getattr(self.model, "vocab", None)
        if vocab is not None and not all(
                0 <= t < vocab for t in req.prompt
                if isinstance(t, (int, np.integer))):
            # a 400 at the front: the ids this model holds are 0..vocab-1
            # (a slice of the vocabulary where the rest is on other chips)
            raise ValueError(
                f"prompt holds a token id outside 0..{vocab - 1}, the "
                "ids this model holds")
        if isinstance(req, BeamRequest) and not getattr(
                self.model, "supports_fork", True):
            _M_REFUSED.inc(reason="beam_unsupported")
            raise AdmissionRefused(
                "beam_unsupported",
                "beam search forks a sequence's pages; this model keeps "
                "a cache beside them that is one sequence's (window "
                "layers' rings, recurrent layers' state), which a fork "
                "would have to copy (not supported yet)")
        if isinstance(req, BeamRequest) and req.beam_size > self.max_slots:
            _M_REFUSED.inc(reason="beam_too_wide")
            raise AdmissionRefused(
                "beam_too_wide",
                f"beam_size {req.beam_size} exceeds the session's "
                f"{self.max_slots} slots")
        need = self.model.context_pages(req.prompt, req.max_new_tokens)
        usable = self.model.allocator.num_pages - 1
        if need > min(usable, self.model.pages_per_seq):
            _M_REFUSED.inc(reason="too_long")
            raise AdmissionRefused(
                "too_long",
                f"request needs {need} pages; a sequence may hold at most "
                f"{min(usable, self.model.pages_per_seq)}")
        with self._lock:
            if (self.max_waiting is not None
                    and len(self._pending) >= self.max_waiting):
                _M_REFUSED.inc(reason="queue_full")
                raise AdmissionRefused(
                    "queue_full",
                    f"admission queue is full ({self.max_waiting} waiting)")
            self._pending.append(req)
            _M_WAITING.set(len(self._pending))
        return req

    # -- introspection ------------------------------------------------------

    @property
    def active(self) -> int:
        with self._lock:
            return sum(1 for s in self._slots if s is not None)

    @property
    def waiting(self) -> int:
        with self._lock:
            return len(self._pending)

    def idle(self) -> bool:
        with self._lock:
            return (not self._pending and not self._flights
                    and all(s is None for s in self._slots))

    @property
    def slow_ticks(self) -> List[dict]:
        """The last ``SLOW_TICKS_KEPT`` slow ticks, oldest first, each
        with when it started (``at``, on ``time.perf_counter``), its
        seconds by phase and what it was doing (``TickAccount._slow``)."""
        return list(self._account.slow_ticks)

    # -- scheduler tick -----------------------------------------------------

    def step(self) -> int:
        """One tick: collect -> decide -> sweep, admit, copy-on-write ->
        dispatch -> deliver (the module's docstring; a model without
        the two halves decodes whole where the dispatch is and is
        collected, decided and delivered at once; with a second step
        still in flight after the collect only the queue is swept,
        nothing is seated or split).  Returns the number of slots that
        were live in the step this tick dispatched last (0 = nothing
        dispatched).  A step that *raises*, at its dispatch or at its
        collect, is contained (``_contain_step_failure``): the slots
        that were in the batch are evicted — first offense
        requeued to retry from scratch, second offense quarantined with
        503 ``step_failed`` — and the stepper thread lives on.  So is a
        copy-on-write split that lost the model's pools (``PoolsLost``;
        a step or a prefill that did contains it where it is called)."""
        account = self._account
        account.phases.open()
        if self._between is None:       # idle since the last tick
            self._gc_mark = _GC[0]
        self._close_between()
        live = [s.req.rid for s in self._slots if s is not None]
        waiting, in_flight = len(self._pending), bool(self._flights)
        tick = phase("decode.tick", active=len(live), waiting=waiting,
                     rids=",".join(map(str, live)))
        try:
            with tick:
                try:
                    return self._tick()
                except PoolsLost as exc:
                    self._contain_step_failure([], exc)
                    return 0
                finally:
                    self._deliver()
                    _M_ACTIVE.set(self.active)
                    self._gauge_cache_rows()
        finally:
            if not self.idle():
                self._between = straddling_phase("decode.between")
                self._between.__enter__()
            # collections since the `between` before this tick opened
            gc_mark, self._gc_mark = self._gc_mark, _GC[0]
            account.flush(tick.t0, self._live(), len(live), waiting,
                          in_flight, self._gc_mark - gc_mark)
            account.phases.close()

    def _close_between(self) -> None:
        """The ``decode.between`` span that the last tick left open ends
        here: at the next tick's start (it then charges that tick's
        account), or when the session is failed."""
        between, self._between = self._between, None
        if between is not None:
            between.__exit__(None, None, None)

    def _live(self) -> int:
        """Slots that hold a sequence which still produces tokens."""
        return sum(1 for s in self._slots if s is not None and not s.dead)

    def _gauge_cache_rows(self) -> None:
        rows_of = getattr(self.model, "cache_rows", None)
        if rows_of is not None:
            lens = [s.ctx_len for s in self._slots if s is not None]
            for kind, rows in rows_of(lens).items():
                _M_CACHE_ROWS.set(rows, kind=kind)
            bytes_of = getattr(self.model, "cache_bytes", None)
            if bytes_of is not None:
                for kind, n in bytes_of(lens).items():
                    _M_CACHE_BYTES.set(n, kind=kind)
            gauge = getattr(self.model.allocator, "gauge_entries", None)
            if gauge is not None:
                gauge()

    def _tick(self) -> int:
        flights = self._flights
        if flights:
            self._collect()
        with phase("decode.sweep"):     # the queue's: it touches no row
            self._sweep_cancelled()
            self._sweep_expired()
        n = 0
        if not flights:
            n = self._step_on_settled_rows()
        if flights:
            # one step is in flight, left by the collect or dispatched
            # just now: the next goes behind it if no row can change
            # before it lands
            lanes = flights[-1].lanes
            why = self._ahead_held()
            if why is not None:
                self._account.held = why
            elif self._dispatch_step(lanes):
                n = len(lanes)
        return n

    def _step_on_settled_rows(self) -> int:
        """With no step in flight: admit, copy-on-write, and the step
        over what is seated then (dispatched; whole for a model without
        the halves and for a verify chunk) -> its live slots."""
        self._admit()
        active_idx = [i for i, s in enumerate(self._slots) if s is not None]
        if not active_idx:
            return 0
        if self._spec_draft is not None and self._spec_ready(active_idx):
            self._deliver()         # the draft reads what was emitted
            return self._spec_step(active_idx)
        if self.model.grows_kv:
            # the step writes each live slot's next KV row: split any
            # page shared with a fork / the prefix cache first
            with phase("decode.cow"):
                for i in active_idx:
                    if (self._slots[i] is not None
                            and not self._slots[i].dead):
                        self._ensure_private(i, rows=1)
            active_idx = [i for i in active_idx
                          if self._slots[i] is not None]
            if not active_idx:
                return 0
        if self._two_halves:
            self._dispatch_step(active_idx)
            return len(active_idx)
        inputs = self._inputs
        t0 = time.perf_counter()
        try:
            with span("decode.step"):
                landed = self.model.decode(
                    inputs.tokens, self._states, inputs.tables, inputs.lens)
        except BaseException as exc:  # noqa: BLE001 - contained per slot
            self._contain_step_failure(active_idx, exc)
            return len(active_idx)
        self._account.inputs[1] += 1
        self._landed(active_idx, *landed, t0=t0)
        return len(active_idx)

    def _dispatch_step(self, lanes: List[int]) -> bool:
        """``step_dispatch`` over what the device holds, with what is
        stale uploaded: the flight joins the others.  False when the
        dispatch raised (contained: every flight is dropped)."""
        inputs = self._inputs
        behind = bool(self._flights)
        t0 = time.perf_counter()
        try:
            with span("decode.step"):
                tokens, tables, lens, uploaded = inputs.for_dispatch()
                step = self.model.step_dispatch(
                    tokens, self._states, tables, lens)
        except BaseException as exc:  # noqa: BLE001 - contained per slot
            self._contain_step_failure(lanes, exc)
            return False
        self._account.inputs[bool(uploaded)] += 1
        self._account.behind[behind] += 1
        inputs.dispatched(step.next)
        self._flights.append(_Flight(step, lanes, t0))
        return True

    def _ahead_held(self) -> Optional[str]:
        """Why no step may be dispatched behind the one in flight, the
        first reason of ``AHEAD_HELD`` that holds, or None: then no row
        can change before the flight lands, and the step after it reads
        exactly what the flight hands on.  All of it is the session's
        own state at this moment.

        - A slot is seated only when one is free, and a slot comes free
          by its budget, which the host counts ahead (``new_tokens``
          lacks the flight's token): with every slot live and none at
          its budget no admission can fall before the flight lands.
        - Nothing stale and ``resident`` the flight's ``next``: the
          tables are the device's, the lengths ``lens + live`` and the
          tokens the flight's ids, none of which the host has to see.
        - No slot's token is chosen on the host from the logits.
        - The row after the flight's (``ctx_len`` lacks the flight's)
          lands in a page that is the sequence's alone; its pages hold
          the room, the budget not being reached.

        EOS, a deadline and a cancel show only when the flight is
        decided: the step behind absorbs them (the module's
        docstring)."""
        if self._spec_draft is not None:
            return "draft"
        slots = self._slots
        if any(s is None or s.dead for s in slots):
            return "free_slot"
        if any(s.new_tokens + 1 >= s.req.max_new_tokens for s in slots):
            return "budget"
        inputs = self._inputs
        handed_on = self._flights[-1].step.next
        if (inputs.stale or handed_on is None
                or inputs.resident is not handed_on):
            return "stale"
        if any(s.group is not None or s.req.temperature for s in slots):
            return "host_choice"
        if self.model.grows_kv:
            ps, shared = self.model.page_size, self.model.allocator.is_shared
            for s in slots:
                pi = (s.ctx_len + 1) // ps
                if pi < len(s.pages) and shared(s.pages[pi]):
                    return "cow"
        return None

    def _collect(self) -> None:
        """The oldest step in flight lands: its ids reach the host and
        its token choices go into the outbox.  A failure on the device
        shows here, and is contained over the lanes the step was
        dispatched with; a step queued behind it, which ran on the
        failed one's pools and over the same lanes, is dropped with
        it."""
        flight = self._flights.pop(0)
        try:
            landed = self.model.step_collect(flight.step)
        except BaseException as exc:  # noqa: BLE001 - contained per slot
            self._contain_step_failure(flight.lanes, exc)
            return
        if self._flights:       # the step behind is the device's from here
            self._flights[0].t0 = time.perf_counter()
        self._landed(flight.lanes, *landed, t0=flight.t0)

    def _landed(self, lanes: List[int], logits, new_states, *, t0: float,
                drafts: Optional[dict] = None) -> None:
        """A step's results are on the host: count it, take its states
        and decide its tokens."""
        _M_STEP_SEC.observe(time.perf_counter() - t0)
        _M_STEPS.inc()
        _M_SLOT_STEPS.inc(len(lanes))
        for buf, new in zip(self._states, new_states):
            buf[...] = np.asarray(new)
        if self.model.grows_kv and drafts is None:
            for i in lanes:
                slot = self._slots[i]
                # None: ended at the decide of the step in front, while
                # this one was queued behind it
                if slot is not None and not slot.dead:
                    slot.ctx_len += 1
                    self._inputs.length(i, slot.ctx_len, from_step=True)
        self._outbox.decided = True
        with phase("decode.sample"):
            self._decide(lanes, logits, drafts)

    def _decide(self, lanes: List[int], logits,
                drafts: Optional[dict] = None) -> None:
        """The token choice of one landed step, lane by lane: expiry
        and cancel, the next token(s), and whether the sequence ends; a
        slot that ends is cleared here, before the next dispatch.  Emission and
        the requests' finish wait in the outbox for ``_deliver``.  A
        greedy slot takes the token the step chose on the device where
        the logits carry ``ids``; a slot that samples and a beam group
        index the logits, which brings them to the host, and choose
        there (as every slot does without ``ids``).  With ``drafts`` (a
        verified chunk) a slot emits its accepted draft tokens and the
        target's correction: rows of [prev] + accepted drafts are real,
        later rows are speculative garbage the length mask never
        reaches."""
        now = time.monotonic()
        out = self._outbox
        ids = getattr(logits, "ids", None)
        if ids is None:
            logits = np.asarray(logits)
        elif drafts is None:
            ids = ids.tolist()
        groups_seen = set()
        for i in lanes:
            slot = self._slots[i]
            if slot is None:
                continue
            if slot.group is not None:
                g = slot.group
                if id(g) in groups_seen:
                    continue
                groups_seen.add(id(g))
                if g.req.expired(now):
                    self._finish_group(g, "deadline", TimeoutError(
                        "generation deadline expired"), out)
                    continue
                if g.req.cancelled:
                    _M_CANCELLED.inc()
                    self._finish_group(g, "cancelled", out=out)
                    continue
                self._group_select(
                    g, logits[np.asarray(g.slot_idx, np.intp)], out)
                out.on_host += 1
                continue
            if slot.req.expired(now):
                self._evict(i, "deadline",
                            TimeoutError("generation deadline expired"), out)
                continue
            if slot.req.cancelled:     # the consumer is gone
                _M_CANCELLED.inc()
                self._evict(i, "cancelled", out=out)
                continue
            on_device = ids is not None and not slot.req.temperature
            if drafts is not None:
                target = (ids[i] if on_device
                          else np.argmax(logits[i], axis=-1))       # (k,)
                toks, accepted = accept_greedy(drafts[i], target)
                observe_chunk(len(drafts[i]), accepted, len(drafts[i]) + 1)
                slot.ctx_len += 1 + accepted
                self._inputs.length(i, slot.ctx_len)
            elif on_device:
                toks = [ids[i]]
            else:
                toks = [self._choose(slot, logits[i])]
            if on_device:
                out.on_device += 1
            else:
                out.on_host += 1
            for tok in toks:
                self._emit_token(i, tok, out,
                                 fed_by_step=on_device and drafts is None)
                if self._slots[i] is not slot:          # eos / budget
                    break

    def _deliver(self) -> None:
        """Pay what the outbox holds of a decided step, in order: each
        token to its request (``on_token`` wakes an HTTP handler, which then
        wants the interpreter lock), each finish, and the registry's
        counts.  Over a model that steps in two halves this runs after
        the next step's dispatch, under the device's step."""
        out = self._outbox
        if not out.decided:
            return
        with phase("decode.deliver"):
            for req, tok, finish in out.events:
                if finish is not None:
                    req._finish(*finish)
                elif not req.done:      # swept since the token was chosen
                    req._emit(tok)
            if out.tokens:
                _M_TOKENS.inc(out.tokens)
            if out.on_device:
                _M_CHOICE.inc(out.on_device, where="device")
            if out.on_host:
                _M_CHOICE.inc(out.on_host, where="host")
            self._account.under[not self._flights] += 1
        out.reset()

    def run(self, max_steps: Optional[int] = None) -> None:
        """Drive the session until every queued request finishes (the
        offline / benchmark entry; serving uses a background thread
        around ``step``)."""
        steps = 0
        while not self.idle():
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                raise RuntimeError(
                    f"decode loop did not drain in {max_steps} steps")

    # -- internals ----------------------------------------------------------

    def _choose(self, slot: _Slot, row: np.ndarray) -> int:
        """Next token for one slot: argmax unless the request opted
        into sampling (temperature/top_k under the slot's seeded RNG)."""
        req = slot.req
        if not req.temperature:
            return int(np.argmax(row))
        row = np.asarray(row, np.float64).reshape(-1)
        if getattr(self.model, "emits_probs", False):
            logp = np.log(np.maximum(row, 1e-20))
        else:
            logp = row - row.max()
            logp = logp - np.log(np.exp(logp).sum())
        logp = logp / req.temperature
        if req.top_k and req.top_k < logp.size:
            kth = np.partition(logp, -req.top_k)[-req.top_k]
            logp = np.where(logp >= kth, logp, -np.inf)
        p = np.exp(logp - logp.max())
        p = p / p.sum()
        return int(slot.rng.choice(p.size, p=p))

    def _ensure_private(self, i: int, rows: int) -> bool:
        """Copy-on-write gate before the decode step appends ``rows``
        KV rows to slot ``i``: any owned page those rows land in that is
        still shared (beam sibling, prefix cache) gets split to a
        private copy.  On pool exhaustion the prefix cache gives pages
        back first; failing that the slot (or its whole beam group) is
        evicted.  Returns False when the slot was evicted."""
        slot = self._slots[i]
        ps = self.model.page_size
        alloc = self.model.allocator
        first = slot.ctx_len // ps
        last = min((slot.ctx_len + rows - 1) // ps, len(slot.pages) - 1)
        changed = False
        for pi in range(first, last + 1):
            while alloc.is_shared(slot.pages[pi]):
                try:
                    cow_split(alloc, slot.pages, pi,
                              [self.model.copy_page])
                    changed = True
                except PoolExhausted:
                    if (self._prefix is not None
                            and self._prefix.evict_for_pages(1)):
                        continue
                    err = AdmissionRefused(
                        "pool_exhausted",
                        "no free page for a copy-on-write split")
                    self._evict(i, "error", err)
                    return False
        if changed:
            self._inputs.retable(i, self.model.pool_table(slot.pages))
        return True

    # -- beam groups --------------------------------------------------------

    def _group_select(self, g: _BeamGroup, dist: np.ndarray,
                      out: Optional[_Outbox] = None) -> None:
        """One beam bookkeeping step for a group: run the shared oracle
        selection over the members' distributions, then reorder the
        sibling slots — each surviving hypothesis forks its parent's
        pages (CoW) and inherits its states; dropped hypotheses release
        theirs.  With ``out`` the request's finish and the token count
        wait there for the delivery."""
        dist = np.asarray(dist, np.float64)
        if not getattr(self.model, "emits_probs", False):
            # beam_select scores log-probabilities: raw logits must be
            # softmaxed per row first (mirrors _choose), or every
            # negative logit clamps to the same log floor and the
            # rankings are garbage
            dist = dist - dist.max(axis=-1, keepdims=True)
            dist = np.exp(dist)
            dist = dist / dist.sum(axis=-1, keepdims=True)
        sel = beam_select(dist, g.scores,
                          g.alive, g.seqs, self.model.eos_id, g.k)
        if sel is None:
            self._finish_group(g, "eos", out=out)
            return
        g.scores, g.seqs, g.alive, rows, toks = sel
        g.selects += 1
        if out is None:
            _M_TOKENS.inc(int(g.alive.sum()))
        else:
            out.tokens += int(g.alive.sum())
        slots = [self._slots[si] for si in g.slot_idx]
        old_pages = [s.pages for s in slots]
        ctx_snap = [s.ctx_len for s in slots]
        state_snap = [buf[np.asarray(g.slot_idx, np.intp)].copy()
                      for buf in self._states]
        alloc = self.model.allocator
        # fork every survivor's parent pages BEFORE releasing anything:
        # fork only bumps refcounts, so this can never exhaust the pool
        new_pages = [alloc.fork(old_pages[rows[j]]) if g.alive[j] else []
                     for j in range(g.k)]
        for pages in old_pages:
            if pages:
                alloc.free(pages)
        for j, si in enumerate(g.slot_idx):
            slot = slots[j]
            slot.pages = new_pages[j]
            slot.dead = not bool(g.alive[j])
            if slot.dead:
                slot.ctx_len = 1
                self._inputs.clear(si, self.model.eos_id)
            else:
                slot.ctx_len = ctx_snap[rows[j]]
                self._inputs.seat(si, self.model.pool_table(slot.pages),
                                  slot.ctx_len, toks[j])
            for bi, buf in enumerate(self._states):
                buf[si] = state_snap[bi][rows[j]]
        if not g.alive.any() or g.selects >= g.req.max_new_tokens:
            self._finish_group(g, "eos" if not g.alive.any() else "length",
                               out=out)

    def _vacate(self, i: int) -> None:
        """Lane ``i`` loses its sequence: masked from the next step on,
        its pages back with the allocator.  The request is not told."""
        slot, self._slots[i] = self._slots[i], None
        self._inputs.clear(i)
        if slot.pages:
            self.model.allocator.free(slot.pages)
            slot.pages = []

    def _finish(self, req: DecodeRequest, reason: str,
                error: Optional[BaseException],
                out: Optional[_Outbox]) -> None:
        """Now, or — from the token choice — with the delivery."""
        if out is None:
            req._finish(reason, error)
        else:
            out.events.append((req, None, (reason, error)))

    def _finish_group(self, g: _BeamGroup, reason: str,
                      error: Optional[BaseException] = None,
                      out: Optional[_Outbox] = None) -> None:
        for si in g.slot_idx:
            if self._slots[si] is not None:
                self._vacate(si)
        if error is None:
            order = np.argsort(-g.scores)
            g.req.beams = [(float(g.scores[i]), list(g.seqs[i]))
                           for i in order if np.isfinite(g.scores[i])]
            g.req.tokens = (list(g.req.beams[0][1])
                            if g.req.beams else [])
        self._finish(g.req, reason, error, out)

    # -- speculative decoding -----------------------------------------------

    def _spec_ready(self, active_idx: List[int]) -> bool:
        """The whole tick runs one (S, k) verify chunk only when every
        live slot has k rows of page capacity left; otherwise this tick
        falls back to the plain one-token step (fixed shapes both
        ways)."""
        k = self.spec_k
        cap = self.model.page_size * self.model.pages_per_seq
        if 1 + k >= cap:
            return False
        for i in active_idx:
            slot = self._slots[i]
            if slot.ctx_len + k > len(slot.pages) * self.model.page_size:
                return False
        return True

    def _spec_step(self, active_idx: List[int]) -> int:
        """One speculative tick: the draft proposes k-1 tokens per live
        slot, one chunked verify step scores all of them, and each slot
        emits the accepted prefix + the target's correction token —
        token-identical to the greedy path.  Rejected rows stay in the
        pages but ``lens`` never reaches them (rollback = truncation).
        ``verify_chunk`` is one whole call on the host's arrays, so the
        step is collected, decided and delivered in this tick."""
        k = self.spec_k
        S = self.max_slots
        inputs = self._inputs
        tokens = np.full((S, k), self.model.bos_id, np.int64)
        drafts = {}
        for i in list(active_idx):
            slot = self._slots[i]
            if not self._ensure_private(i, rows=k):
                continue
            ids = [int(t) for t in slot.req.prompt] + slot.req.tokens
            d = [int(t) for t in self._spec_draft.propose(ids, k - 1)]
            drafts[i] = d
            tokens[i, 0] = inputs.tokens[i, 0]
            tokens[i, 1:] = d
        active_idx = [i for i in active_idx if i in drafts]
        if not active_idx:
            return 0
        t0 = time.perf_counter()
        try:
            with span("decode.step", chunk=k):
                landed = self.model.verify_chunk(
                    tokens, self._states, inputs.tables, inputs.lens)
        except BaseException as exc:  # noqa: BLE001 - contained per slot
            self._contain_step_failure(active_idx, exc)
            return len(active_idx)
        self._account.inputs[1] += 1
        self._landed(active_idx, *landed, t0=t0, drafts=drafts)
        return len(active_idx)

    def _emit_token(self, i: int, tok: int, out: Optional[_Outbox] = None,
                    fed_by_step: bool = False) -> None:
        """Slot ``i`` produced ``tok``: it goes to the request (now, or
        with ``out``'s delivery), and the slot ends or takes it as its
        next input — ``fed_by_step`` when it is the id the step itself
        chose, which the device then holds too."""
        slot = self._slots[i]
        if out is None:
            slot.req._emit(tok)
            _M_TOKENS.inc()
        else:
            out.events.append((slot.req, tok, None))
            out.tokens += 1
        slot.new_tokens += 1
        if tok == self.model.eos_id:
            self._evict(i, "eos", out=out)
        elif slot.new_tokens >= slot.req.max_new_tokens:
            self._evict(i, "length", out=out)
        else:
            self._inputs.token(i, tok, from_step=fed_by_step)

    def _contain_step_failure(self, active_idx: List[int],
                              exc: BaseException) -> None:
        """A decode/verify dispatch raised.  One fused step covers every
        live slot, so the offender can't be attributed from here — every
        slot that was in the batch is a suspect.  First offense: the
        slot is evicted and its request requeued to retry from a fresh
        prefill (innocent batchmates lose only latency).  Second
        offense: the request has now killed two dispatches and is
        quarantined with 503 ``step_failed`` — the decode-plane mirror
        of the replica pool's poison-batch rule.  Queued requests and
        the stepper thread are untouched.

        ``PoolsLost`` (the program had consumed the donated pools; the
        model made them anew, empty) widens the batch to every seated
        sequence, whichever program failed, and drops the prefix index:
        no page holds the rows it was filled with."""
        _M_STEP_FAIL.inc()
        # a step still in flight ran on the failed one's pools, or was
        # fed by it: dropped, its results never read
        self._flights.clear()
        # what a landed step chose goes out before its requests are
        # sent back to start again
        self._deliver()
        self._inputs.forget()
        if isinstance(exc, PoolsLost):
            active_idx = [i for i, s in enumerate(self._slots)
                          if s is not None]
            if self._prefix is not None:
                self._prefix.clear()
        requeue: List[DecodeRequest] = []
        groups_seen = set()
        for i in list(active_idx):
            slot = self._slots[i]
            if slot is None:
                continue
            if slot.group is not None:
                g = slot.group
                if id(g) in groups_seen:
                    continue
                groups_seen.add(id(g))
                # beam hypotheses share one request: no per-member
                # retry semantics, the group fails as a unit
                self._finish_group(g, "error", AdmissionRefused(
                    "step_failed",
                    f"decode step failed with this beam in the batch: "
                    f"{type(exc).__name__}: {exc}"))
                continue
            req = slot.req
            req.step_failures += 1
            if req.step_failures >= 2:
                self._evict(i, "error", AdmissionRefused(
                    "step_failed",
                    f"decode step failed {req.step_failures} times with "
                    f"this request in the batch; quarantined "
                    f"({type(exc).__name__}: {exc})"))
                continue
            # evict without finishing: the request restarts from an
            # empty generation at its next admission
            self._vacate(i)
            req.tokens = []
            requeue.append(req)
        if requeue:
            with self._lock:
                self._pending[0:0] = requeue
                _M_WAITING.set(len(self._pending))
        _M_ACTIVE.set(self.active)

    def _sweep_cancelled(self) -> None:
        """Drop the waiters whose consumer abandoned them (dead
        streaming socket): queue capacity comes back at once.  A seated
        one leaves where its step is decided, as one past its deadline
        does (``_decide``): pages come back after that step instead of
        after max_new_tokens."""
        with self._lock:
            live, dead = [], []
            for req in self._pending:
                (dead if req.cancelled else live).append(req)
            if dead:
                self._pending = live
                _M_WAITING.set(len(live))
        for req in dead:
            _M_CANCELLED.inc()
            req._finish("cancelled")

    def _sweep_expired(self) -> None:
        """Fail queued requests whose deadline passed.  Runs every tick
        — even with zero free slots — so dead waiters release their
        max_waiting capacity instead of causing spurious queue_full
        refusals while they wait for an eviction."""
        now = time.monotonic()
        with self._lock:
            live, dead = [], []
            for req in self._pending:
                (dead if req.expired(now) else live).append(req)
            self._pending = live
            _M_WAITING.set(len(live))
        for req in dead:
            req._finish("deadline", TimeoutError(
                "generation deadline expired while queued"))

    def _free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self._slots) if s is None]

    def _requeue_head(self, req: DecodeRequest) -> None:
        with self._lock:
            self._pending.insert(0, req)
            _M_WAITING.set(len(self._pending))

    def _prefill_with_cache(self, req: DecodeRequest, need: int,
                            admit_span):
        """Allocate + prefill one prompt, reusing cached prefix pages
        when the cache has them.  Returns (pages, ctx_len, state_rows,
        first_logits) or None when the pool cannot host the fresh part
        right now (caller requeues).  Exceptions propagate with nothing
        left allocated."""
        alloc = self.model.allocator
        cached_pages: List[int] = []
        cached_len = 0
        if self._prefix is not None:
            cached_pages, cached_len = self._prefix.match(req.prompt)
        fresh_need = need - len(cached_pages)
        if not alloc.can_alloc(fresh_need):
            if self._prefix is not None:
                self._prefix.evict_for_pages(
                    fresh_need - alloc.free_pages)
            if not alloc.can_alloc(fresh_need):
                if cached_pages:
                    alloc.free(cached_pages)
                return None
        t0 = time.perf_counter()
        pages = cached_pages + alloc.alloc(fresh_need)
        admit_span.set(cached_len=cached_len, pages=len(pages))
        try:
            args = {}
            if not cached_len and hasattr(self.model, "prefill_bucket"):
                n = _prompt_len(req.prompt)
                bucket = self.model.prefill_bucket(n)
                args = {"bucket": bucket, "pad": bucket - n}
                admit_span.set(bucket=bucket)
            with phase("decode.prefill", rid=req.rid, **args):
                if cached_len:
                    ctx_len, state_rows, first_logits = self.model.prefill(
                        req.prompt, pages, cached_len=cached_len)
                else:
                    ctx_len, state_rows, first_logits = self.model.prefill(
                        req.prompt, pages)
        except BaseException:
            alloc.free(pages)
            raise
        _M_PREFILL_SEC.observe(time.perf_counter() - t0)
        if self._prefix is not None:
            # stats only count now that the admission committed — a
            # requeued request re-matches every retry and must not
            # inflate hits/tokens_saved for prefills that never ran
            self._prefix.commit_match(cached_len)
            self._prefix.insert(req.prompt, pages)
        return pages, ctx_len, state_rows, first_logits

    def _place(self, i: int, slot: _Slot, ctx_len: int,
               state_rows) -> None:
        self._slots[i] = slot
        self._inputs.seat(i, self.model.pool_table(slot.pages), ctx_len,
                          self.model.bos_id)
        for buf, row in zip(self._states, state_rows):
            buf[i] = row

    def _admit(self) -> None:
        while True:
            frees = self._free_slots()
            if not frees:
                return
            with self._lock:
                req = self._pending.pop(0) if self._pending else None
                _M_WAITING.set(len(self._pending))
            if req is None:
                return
            live = self._live()
            with phase("decode.admit", rid=req.rid,
                       prompt_len=_prompt_len(req.prompt)) as admit_span:
                seated = self._admit_one(req, frees, admit_span)
            self._account.admitted(admit_span, live, bool(seated))
            if seated is None:
                return

    def _admit_one(self, req: DecodeRequest, frees: List[int],
                   admit_span) -> Optional[bool]:
        """Seat one popped request: prefill, place, first token -> True.
        None when slots or pages are busy with live sequences and the
        request went back to the head of the queue (an evict next tick
        frees them: not a refusal, that happens at submit); False for a
        request that failed: it is finished, and the next may be
        tried."""
        popped_at = time.monotonic()
        if isinstance(req, BeamRequest) and len(frees) < req.beam_size:
            self._requeue_head(req)
            return None
        need = self.model.context_pages(req.prompt, req.max_new_tokens)
        try:
            got = self._prefill_with_cache(req, need, admit_span)
        except PoolExhausted as e:   # raced with another allocator user
            _M_REFUSED.inc(reason="pool_exhausted")
            req._finish("error", AdmissionRefused("pool_exhausted",
                                                  str(e)))
            return False
        except BaseException as e:
            if isinstance(e, PoolsLost):
                self._contain_step_failure([], e)
            req._finish("error", e)
            return False
        if got is None:
            self._requeue_head(req)
            return None
        if req.admitted_at is None:
            # a request that a failed step sent back is seated again,
            # and has waited once
            req.admitted_at = popped_at
            _M_QUEUE_WAIT.observe(popped_at - req.submitted_at)
        pages, ctx_len, state_rows, first_logits = got
        if isinstance(req, BeamRequest):
            self._admit_beam(req, frees[:req.beam_size], pages,
                             ctx_len, state_rows, first_logits)
        else:
            self._place(frees[0], _Slot(req, pages, ctx_len),
                        ctx_len, state_rows)
            if first_logits is not None:
                with phase("decode.first_token", rid=req.rid):
                    tok = self._choose(self._slots[frees[0]],
                                       np.asarray(first_logits))
                    self._emit_token(frees[0], tok)
        _M_ACTIVE.set(self.active)
        return True

    def _admit_beam(self, req: BeamRequest, slot_idx: List[int],
                    pages: List[int], ctx_len: int, state_rows,
                    first_logits) -> None:
        """Seat one beam group: the prefilled prompt pages back member
        0; every sibling *forks* them (refcount bump, zero copies) and
        diverges later through copy-on-write writes."""
        g = _BeamGroup(req, slot_idx)
        alloc = self.model.allocator
        for j, si in enumerate(slot_idx):
            member_pages = pages if j == 0 else alloc.fork(pages)
            self._place(si, _Slot(req, member_pages, ctx_len,
                                  group=g, member=j),
                        ctx_len, state_rows)
        if first_logits is not None:
            # the prompt's own logits drive the first selection (all
            # members share them; dead starting scores mask duplicates)
            row = np.asarray(first_logits).reshape(1, -1)
            self._group_select(g, np.repeat(row, g.k, axis=0))

    def _evict(self, i: int, reason: str,
               error: Optional[BaseException] = None,
               out: Optional[_Outbox] = None) -> None:
        slot = self._slots[i]
        if slot is not None and slot.group is not None:
            # a beam member never leaves alone: the hypotheses share
            # one request, so the whole group goes
            self._finish_group(slot.group, reason, error, out)
            return
        self._vacate(i)
        self._finish(slot.req, reason, error, out)

    def fail_all(self, exc: BaseException) -> None:
        """Shutdown: fail every live and queued request; the steps in
        flight are dropped, their results never read."""
        self._flights.clear()
        self._close_between()
        self._inputs.forget()
        with self._lock:
            pending, self._pending = self._pending, []
        for req in pending:
            req._finish("error", exc)
        for i, slot in enumerate(self._slots):
            if slot is not None:
                self._evict(i, "error", exc)
