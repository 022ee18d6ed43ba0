"""GenerationEngine: continuous-batching decode over a paged KV arena.

The PR-2 ServingEngine batches whole requests into fixed buckets — a
finished sequence holds its rows until the whole bucket drains. This
engine schedules at ITERATION granularity (Orca, OSDI'22): a fixed batch
of S slots is stepped once per model iteration through ONE compiled
``[S, 1]`` decode executable; finished sequences retire between
iterations and admitted prompts prefill into free slots mid-flight, so
occupancy tracks offered load instead of the slowest batchmate.

Storage is a block-granular paged arena, and it is `kvstate.py`'s: where a
sequence's K/V rows are, how they are written, read, spilled, shared and
given back (`KVStore`, one per entry; `SeqKV`, one per sequence). This
module decides who runs; it sees block COUNTS and never a row.

Scheduling modes, all bit-identical to the offline whole-sequence
reference for any admission order (tested, not asserted by construction
alone):

* **decode** — the ``[S, 1]`` hot path. The step program chooses each
  slot's greedy token itself; a step whose slots are all greedy brings
  those S integers to the host, any other (a sampled slot, a beam group, a
  grammar masked on the host, a model without ``token_fetch``) the whole
  ``[S, 1, V]`` logits: decided per step from the slots' own policies
  (`_tokens_suffice`). A step that needs its tokens alone is left on the
  device and the next iteration launches its successor, fed those tokens
  as the device array they are, BEFORE it fetches them: depth one, the
  same tokens in either order (`_iterate` has the two orders and what
  drains, ``serving_decode_drains_total{why=}``). From the host a step
  takes ONE array, put once (``dec_step``: `_step_feeds`); a put costs
  this host ~0.25 ms whatever its size (PERF.md §6, PR 39).
* **one-shot prefill** — a prompt the chunk budget covers (every prompt
  of a model without a chunk program) runs the whole-prompt prefill
  program once, and its admission moves no bulk bytes across the host
  link: the program's K/V outputs are the inject program's feeds, the
  host takes one logits row and, in one fetch, the rows such a prompt can
  fill, and all of it is launched before the host waits.
* **chunked prefill** — a longer prompt streams through the ``[1, C]``
  chunk program ONE chunk per engine iteration, interleaved with decode
  steps, so a 32k-token admission never stalls in-flight generations for
  more than one chunk's compute; chunks that radix-shared blocks cover
  are skipped. The whole admission runs in the launch-ahead order: every
  chunk is a launch under the step in flight, and the last chunk's one
  logits row is fetched behind the NEXT step's launch.
* **speculative** — a draft model (just another ``(model, version)``
  registry entry) greedily proposes k tokens; the target verifies all
  of them in ONE batch-prefill forward and emits the longest matching
  prefix plus its own correction token: BIT-IDENTICAL to target-only
  decode, at fewer target steps per emitted token.

State of a second kind: a model may keep **per-slot recurrent state**
(``DecodeModel.slot_states``), or K/V rows per (pass, layer) of a looped
stack; either has no prefill and inject programs. The scheduler names the
slot to the chunk program, sends EVERY prompt of a model without a prefill
program through chunked prefill from its first token (the chunk at
position 0 starts the slot from zero: ``decode::state_reset``), and asks
the store what such a state lets it do (`KVStore.check_carries`): it parks
no session of it and refuses beam search and speculation at ``submit``.
Launch-ahead stays safe: the one step an ``eos_id`` wastes dirties only a
state the next admission resets. A step's integer counts
(``counts_fetch``) come back in the same fetch as its tokens.

**Admission by reservation** (`KVStore.acquire`): where the store promises
a request its whole block chain or nothing, a tenant's head request whose
chain the pool cannot cover stays in the QUEUE (``_pick(fits=)``) and
takes no slot; nothing parks, nothing fails mid-generation, and an arrival
whose chain is covered joins the launch-ahead order without a drain.

**Answers filled a block at a time** (``DecodeModel.fills_blocks``: a
block-diffusion decoder). The decode program is a BLOCK PASS over each
stepping slot's current block of ``block_len`` positions, and a slot's
block costs one FILL pass a position not yet decided, each deciding one
more position in the order the model's confidence gives, then a COMMIT
pass that leaves the block's final K/V rows and opens the next block. The
host knows without a fetch which pass of which block a slot is in
(`_Slot.bpass` of `_Slot.fills`), so launch-ahead stands as it is: the
block's state (tokens, decided bits) is the pass's device output and the
next launch's feed, and the one fetch brings each slot's decided position
and token. A delivered pass hands a slot 0 to ``block_len`` tokens: the
answer grows in POSITION order (`_sample_blocks`), a token decided ahead of
its turn waits on the host; the cursor moves by ``block_len`` when a block
is committed; a prompt's first ``len - len % block_len`` tokens go through
the chunk program under the block mask (`DecodeModel.chunk_bias`'s rule) and the
rest open the first block as positions already decided, so the last
chunk's logits row is never fetched. ``result()`` carries ``decided_at``
beside ``tokens``: the pass of its block that decided each token.
Sampling, beams, grammars, speculation, a prefix cache and a host tier are
refused for such a model (`submit`, `KVStore.check_carries`): a block's
rows are not final until it is committed.

Multi-tenancy: one engine hosts N ``(model, version)`` entries, each with
its own slot batch, queue, and scheduler thread. Admission applies
per-tenant quotas (queued rows reject at the door; in-flight caps make
the picker skip, not reject) and WEIGHTED-FAIR selection layered over the
queue's strict priority lanes (stride scheduling).

Cold start: the executables per entry lower through ``core/lowering.py``
into the content-addressed compile cache. With a populated cache
directory, a fresh replica (or the circuit breaker's relaunched
replacement) restores them from the ``jax.export`` disk tier with ZERO
traces — subprocess-asserted in tests/test_decode.py. An arena that does
not fit the HBM budget fails before anything compiles (`_check_hbm`).

Measured from inside (nothing while tracing is off): one
``decode::iterate`` span per scheduler iteration holds a span per phase,
each carrying its request's id where it has one; a launch span says what it
put (`_run`), ``decode::step`` and ``decode::chunk`` whether they were
launched ahead, a ``decode::step_fetch`` with nothing launched over it why
(``drain``); and, where the capture asks (``tracing(path, lanes=True)``),
the engine keeps a lane of the DEVICE's own (``lane.py``: when the device
had finished each launch of `_run`, so busy and idle seconds by cause,
``serving_device_*``, and a ``device::<kind>`` event a launch on the track
``device:<id>``). Always on: a time stamp per token on the ``Response``,
the bytes that cross the device boundary, the steps launched ahead and the
drains by reason (``DecodeMetrics``).
"""

import threading
import time

import numpy as np

from paddle_tpu import profiler
from paddle_tpu.observability import lockdep
from paddle_tpu.observability.tracer import instant as _instant
from paddle_tpu.observability.tracer import lanes_enabled as _lanes
from paddle_tpu.observability.tracer import span as _span
from paddle_tpu.resilience import faults
from paddle_tpu.serving.decode.generate import (
    BeamParams,
    CompiledGrammar,
    GrammarConstraint,
    SamplingParams,
    offline_beam_decode,
    sample_token,
)
from paddle_tpu.serving.decode.generate.beam import (
    finished_ranking as beam_finished_ranking,
)
from paddle_tpu.serving.decode.generate.beam import select as beam_select
from paddle_tpu.serving.brownout import BrownoutController
from paddle_tpu.serving.decode.kvstate import (
    ArenaInvalidError,
    KVStore,
    SlotPool,
)
from paddle_tpu.serving.decode.lane import DeviceLane
from paddle_tpu.serving.decode.metrics import DecodeMetrics
from paddle_tpu.serving.decode.model import NEG_INF, DecodeModel
from paddle_tpu.serving.engine import _ReplicaBreaker
from paddle_tpu.serving.queue import RequestQueue
from paddle_tpu.serving.request import (
    DeadlineExceededError,
    Priority,
    RejectedError,
    ReplicaLostError,
    RequestError,
    Response,
)

__all__ = ["GenerationEngine", "GenerationRequest"]

# The scheduler takes the queue lock, then the tenant table inside it
# (_admit_free_slots -> _pick); PR 10's ABBA fix (quota rejects estimate
# retry-after OUTSIDE _tenant_lock) exists precisely to preserve this.
# Declared so a future inversion names the RULE, not just the cycle.
lockdep.declare_order("serving.queue", "decode.tenant")
# Draft-KV speculation: a TARGET entry's scheduler thread takes the draft
# entry's decode.draft lock, then allocates from the draft's block pool
# inside it (catch-up / proposal appends) — the draft lock is strictly
# OUTSIDE the pool lock, never the reverse.
lockdep.declare_order("decode.draft", "decode.blocks")


class GenerationRequest:
    """One admitted generation request. `response.result()` yields
    ``{"tokens": int64 array}`` — the generated tokens, including the
    stop token when eos fired (beam requests add ``"beams"``: every
    finished hypothesis with its score, best first). ``draft_key`` (a
    registry ``(name, version)``) opts the request into speculative
    decoding with ``spec_k`` proposals per verify cycle; ``rows`` is the
    slot footprint — 1 for everything except beam search, whose live
    hypotheses each hold a batch slot."""

    __slots__ = ("id", "prompt", "max_new", "tenant", "priority", "deadline",
                 "submit_time", "dispatch_time", "response", "rows",
                 "draft_key", "spec_k", "sampling", "beam", "grammar",
                 "draft_kv", "held_back")

    def __init__(self, rid, prompt, max_new, tenant, priority, deadline,
                 draft_key=None, spec_k=0, sampling=None, beam=None,
                 grammar=None, draft_kv=False):
        self.id = rid
        self.prompt = list(prompt)
        self.max_new = int(max_new)
        self.tenant = str(tenant)
        self.priority = priority
        self.deadline = deadline
        self.submit_time = time.perf_counter()
        self.dispatch_time = None
        self.response = Response()
        self.sampling = sampling      # SamplingParams or None (greedy)
        self.beam = beam              # BeamParams or None
        self.grammar = grammar        # CompiledGrammar or None
        self.draft_kv = bool(draft_kv)
        self.rows = beam.width if beam is not None else 1
        self.draft_key = draft_key
        self.spec_k = int(spec_k)
        self.held_back = False  # the block pool made it wait (counted once)

    def expired(self, now=None):
        if self.deadline is None:
            return False
        return (now if now is not None else time.perf_counter()) > self.deadline


class _DeferAdmission(Exception):
    """Raised out of ``_acquire_blocks`` when the arena is exhausted and
    the request cannot be admitted right now, but WILL fit later (parked
    sessions hold its blocks, or victims could not be preempted safely).
    The admission loop parks the request on ``_pending`` and retries
    every iteration — never a hard failure."""


class _TenantState:
    __slots__ = ("weight", "max_in_flight", "max_queued", "in_flight",
                 "queued", "vtime")

    def __init__(self, weight=1.0, max_in_flight=None, max_queued=None):
        self.weight = float(weight)
        self.max_in_flight = max_in_flight
        self.max_queued = max_queued
        self.in_flight = 0
        self.queued = 0
        self.vtime = 0.0


class _Slot:
    """Host-side state of one live batch slot.

    ``mode`` is "decode" (stepping through the [S,1] program),
    "prefill" (a long prompt streaming through the chunk program),
    "spec" (speculative verify cycles — holds no TARGET arena blocks),
    or "beam" (one live beam hypothesis; its group coordinates via
    ``beam``). ``kv`` is where the sequence's K/V rows are on this entry
    (a `kvstate.SeqKV`; None for a speculative slot, which holds none).
    A speculative slot with a draft-KV footprint has ``draft_kv``, the
    same ON THE DRAFT ENTRY ``d_entry``, in its batch slot ``d_slot``,
    and ``d_cursor``, the next draft arena position without a committed
    KV row. ``ahead`` counts the slot's tokens that a launched decode step
    has produced on the device and the host has not read yet: ``cursor``
    already counts their rows, ``generated`` and ``last_token`` do not
    hold them.

    A slot of a model that fills blocks (module docstring): ``cursor`` is
    the first position of the block whose next pass is to be LAUNCHED,
    ``fills`` the fill passes that block needs (the positions not decided
    when it opened) and ``bpass`` how many of its passes are launched
    (``bpass == fills``: the next is its commit pass); ``plen`` is the
    prompt's whole blocks, which the chunk program lands. Of the passes
    DELIVERED: ``block`` is the current block's tokens (-1: not decided),
    ``pending`` the answer tokens decided ahead of their turn (answer index
    -> token, delivery time, pass) and ``decided_at`` the pass of its block
    that decided each token of ``generated``."""

    __slots__ = ("request", "mode", "cursor", "last_token", "generated",
                 "kv", "plen", "done", "toks", "sampling", "grammar", "beam",
                 "score", "seq", "ahead", "d_entry", "d_slot", "draft_kv",
                 "d_cursor", "block", "fills", "bpass", "pending",
                 "decided_at")

    def __init__(self, request, mode="decode", seq=0):
        self.request = request
        self.mode = mode
        self.cursor = 0
        self.last_token = None
        self.generated = []
        self.kv = None          # SeqKV on this entry
        self.seq = seq          # admission order (default victim policy)
        self.ahead = 0          # tokens launched, not yet on the host
        self.plen = len(request.prompt)
        self.done = 0           # chunked prefill: prompt positions landed
        self.toks = None        # spec mode: prompt + emitted so far
        self.sampling = None    # SamplingParams (committed-stream sampling)
        self.grammar = None     # per-hypothesis GrammarConstraint
        self.beam = None        # _BeamGroup this slot belongs to
        self.score = 0.0        # beam: cumulative float64 log-prob
        self.d_entry = None     # draft-KV: the draft _ModelEntry
        self.d_slot = None
        self.draft_kv = None    # SeqKV on the draft entry
        self.d_cursor = 0
        self.block = None       # block filling: set by `_start_blocks`
        self.fills = self.bpass = 0
        self.pending = None
        self.decided_at = None


class _BeamGroup:
    """One beam request's shared state across its live hypothesis slots.
    ``order`` is the live slot ids in REFERENCE hypothesis order — the
    rank order of the last selection — which is what makes the
    incremental engine's tie-breaking (by parent index) bit-identical
    to ``offline_beam_decode``'s live-list order."""

    __slots__ = ("request", "width", "finished", "order", "spare")

    def __init__(self, request):
        self.request = request
        self.width = request.beam.width
        self.finished = []      # [(token list, float64 score), ...]
        self.order = []         # live slot ids, hypothesis order
        # the group RESERVES width slots for its lifetime (that is what
        # request.rows promised admission): pruned hypotheses park their
        # slot here for later forks instead of returning it to the pool,
        # so a fork can never lose its slot to a concurrent admission
        self.spare = []


class _LaunchedStep:
    """One decode step on the device whose fetch the host has not made
    yet. ``states`` are the ``_Slot`` objects of ``active`` at launch: a
    slot that was retired or rejected since (its id may serve another
    request by now) gets no token. ``host_s`` is the wall time of the
    ``_step`` body that launched it, when that body delivered nothing:
    the step's share of ``serving_decode_step_seconds`` that its
    delivery still owes. ``launch`` is the number its ``decode::step``
    span carries, for the ``decode::step_fetch`` that lands it (None
    when the launch was not traced). ``blocks`` is, for a model that fills
    blocks, each of ``states``' ``(block's first position, pass of the
    block, whether it is the commit pass)`` at the launch."""

    __slots__ = ("fetches", "active", "states", "groups", "tokens_only",
                 "host_s", "launch", "blocks")

    def __init__(self, fetches, active, states, groups, tokens_only,
                 launch=None, blocks=None):
        self.fetches = fetches
        self.active = active
        self.states = states
        self.groups = groups
        self.tokens_only = tokens_only
        self.host_s = 0.0
        self.launch = launch
        self.blocks = blocks


class _LaunchedChunk:
    """A prompt's LAST chunk on the device, with the row picker behind
    it, whose one ``[V]`` logits row (``row``, a device array) the host
    has not fetched yet. ``state`` is the ``_Slot`` of ``slot`` at the
    launch, still in mode ``"prefill"`` with every position done: a slot
    rejected since (a deadline, a lost arena) gets no token."""

    __slots__ = ("slot", "state", "row")

    def __init__(self, slot, state, row):
        self.slot = slot
        self.state = state
        self.row = row


class _ParkedSession:
    """One preempted in-flight session waiting off-device. ``states``
    holds the live ``_Slot`` objects (host state — sampling stream,
    grammar cursor, committed tokens — travels with them untouched);
    ``keys`` the host-tier keys of each hypothesis's spilled KV rows
    (empty for spec mode, which holds no target arena rows). Resume is
    FIFO: re-acquire slots + blocks, re-inject (or recompute) the rows,
    and the session continues byte-identically."""

    __slots__ = ("request", "mode", "states", "keys", "group", "parked_at")

    def __init__(self, request, mode, states, keys, group=None):
        self.request = request
        self.mode = mode
        self.states = states
        self.keys = keys
        self.group = group
        self.parked_at = time.perf_counter()


def _stopwatch_ns():
    """Nanoseconds since this call, at each call of what it returns: what
    ``trace_scope.elapsed_ns`` is to a live span, for a launch that is
    timed while tracing is off."""
    t0 = time.perf_counter_ns()
    return lambda: time.perf_counter_ns() - t0


def _pick_row(logits, index):
    """Row ``index`` of ``[1, N, V]`` logits, on the device."""
    import jax

    return jax.lax.dynamic_index_in_dim(logits[0], index, axis=0,
                                        keepdims=False)


class _ModelEntry:
    """One hosted (model, version): programs + executables + slot batch +
    block pool + its scheduler thread. All slot/arena/block mutation
    happens on the loop thread; admission hand-off goes through the
    queue."""

    def __init__(self, engine, model, queue_depth, breaker_threshold,
                 breaker_cooldown_s, prefix_cache_size):
        self._engine = engine
        self._model = model
        self._queue = RequestQueue(queue_depth)
        self._cond = threading.Condition(self._queue.lock)
        self._pool = SlotPool(model.slots)
        self._slots = [None] * model.slots
        self._metrics = DecodeMetrics(
            engine_label=f"{engine.label}:{model.label}")
        # where every sequence's K/V rows are kept: block pool, host tier,
        # prefix cache. It launches and fetches through this entry (tests
        # wrap `_run`), and finds the arenas in its scope
        self.kv = KVStore(
            model, engine._host_tier_bytes, prefix_cache_size, self._metrics,
            run=lambda *a: self._run(*a), fetch=lambda v: self._fetch(v),
            scope=lambda: self._scope, device=engine.device)
        # graceful degradation (r18): parked sessions, deferred
        # admissions, and the brownout severity ladder
        self._parked = []       # [_ParkedSession] FIFO
        self._pending = []      # [GenerationRequest] deferred admissions
        self._brownout = BrownoutController()
        self._bt_seen = 0       # brownout transitions already counted
        self._iteration = 0     # decode::iterate spans opened (traced only)
        self._launched = None   # the _LaunchedStep in flight, if any
        self._chunk_rows = []   # [_LaunchedChunk] last chunks not landed
        self._lane_slept = 0.0  # the loop's sleep at its last stamped launch
        self._admit_seq = 0
        self._chunk_throttle = False
        self.victim_policy = None   # callable([slot ids]) -> slot id
        self._breaker = (
            _ReplicaBreaker(breaker_threshold, breaker_cooldown_s)
            if breaker_threshold and breaker_threshold > 0 else None
        )
        self.compile_sources = {"trace": 0, "disk": 0, "memory": 0}
        self._entries = {}      # kind -> (LoweredStep, executable)
        self._thread = None
        self._stop = False
        self._scope = None
        self._rng0 = None
        self._pref_rr = 0       # round-robin cursor over prefilling slots
        # half-open relaunch latch: one rebuild per breaker episode
        self._probe_relaunched = False
        # draft-KV speculation, when THIS entry serves as the draft:
        # every draft-side device call from a target's scheduler thread
        # serializes under _draft_lock; _draft_pinned closes the entry to
        # primary submissions (its own loop then never touches the arena,
        # so the donated draft decode/inject calls cannot race it);
        # _draft_ok poisons the entry after a failed donated draft call —
        # users fall back to replay proposals instead of reading an
        # undefined arena
        self._draft_lock = lockdep.named_lock("decode.draft", rlock=True)
        self._draft_pinned = False
        self._draft_ok = True

    # -- build / warmup ---------------------------------------------------
    def build(self):
        """Run startup (weights + zeroed arenas into the scope), then
        lower + AOT-compile the executables. With a warm compile cache
        nothing here traces (`compile_sources` says so)."""
        import paddle_tpu as fluid
        from paddle_tpu.core.lowering import zero_rng_key

        self._scope = fluid.Scope()
        exe = fluid.Executor(self._engine.place)
        with fluid.scope_guard(self._scope):
            exe.run(self._model.startup_program)
        self._rng0 = zero_rng_key(self._engine.device)
        self._lower_all()
        self._compile_admission_helpers()
        return self

    def _lower_all(self):
        from paddle_tpu.core import lowering

        m = self._model
        # the logits stay at index 0; the device-chosen tokens follow
        # where the program has them (a hand-built model may not)
        step_fetches = [m.logits_fetch]
        if m.token_fetch is not None:
            step_fetches.append(m.token_fetch)
            if m.counts_fetch is not None:
                step_fetches.append(m.counts_fetch)
        plans = [("step", m.decode_program, m.decode_feed_sig(),
                  step_fetches, True)]
        # a model with per-slot recurrent state has neither: every prompt
        # streams through the chunk program
        if m.prefill_program is not None:
            plans.append(
                ("prefill", m.prefill_program, m.prefill_feed_sig(),
                 [m.prefill_logits_fetch] + [n for kv in m.prefill_kv_fetches
                                             for n in kv], False))
        if m.inject_program is not None:
            plans.append(
                ("inject", m.inject_program, m.inject_feed_sig(), [], True))
        if m.chunk_program is not None:
            plans.append(("chunk", m.chunk_program, m.chunk_feed_sig(),
                          [m.chunk_logits_fetch], True))
        sources = dict(self.compile_sources)
        with profiler.RecordEvent("decode::warmup"):
            for kind, prog, feed_sig, fetches, donate in plans:
                entry, source = lowering.lower_step(
                    prog, self._scope, feed_sig, fetches, donate=donate,
                    label=f"decode:{m.label}:{kind}",
                )
                sources[source] = sources.get(source, 0) + 1
                executable = entry.aot_compile(
                    lowering.abstract_signature(entry, feed_sig,
                                                self._scope))
                self._entries[kind] = (entry, executable)
        # atomic rebind, not in-place mutation: a breaker relaunch runs
        # this on the loop thread while stats() dict-copies concurrently
        self.compile_sources = sources

    def _compile_admission_helpers(self):
        """What an admission needs on the device besides the programs,
        made once here so that nothing compiles and nothing constant is
        put after registration. ``_pickers[kind]`` takes the float32
        ``[1, N, V]`` logits of the prefill or chunk program and a
        position (an OPERAND: one executable for every prompt length)
        and gives that one ``[V]`` row. ``_stack_live`` takes the prefill
        program's K/V outputs (``[1, L, H]`` float32 each, what the inject
        program is fed) and gives their first P rows as ONE
        ``[2 * layers, P, H]`` array, the host copy the prefix cache and
        copy-on-write keep; P is ``chunk_tokens`` where a chunk program
        takes every longer prompt, else ``max_len``. ``_causal_bias`` is
        the prefill program's ``[1, L, L]`` bias, the same for every
        prompt. ``_no_tokens`` is the step program's ``dec_token`` on a
        step whose tokens come from the host (they ride in ``dec_step``;
        a launched-ahead step's is the output of the step before).
        Shapes are the model's contract, not a relaunch's: the rebuilt
        programs are content-identical, so these outlive it."""
        import jax

        m = self._model
        L, V = m.max_len, m.vocab_size
        self._no_tokens = jax.device_put(
            np.zeros((m.slots, m.step_state_width),
                     jax.dtypes.canonicalize_dtype(np.int64)),
            self._engine.device)
        chunked = bool(m.chunk_tokens) and m.chunk_program is not None

        def sds(*shape, dtype=np.float32):
            return jax.ShapeDtypeStruct(shape, dtype)

        def picker(n):
            return jax.jit(_pick_row).lower(
                sds(1, n, V), sds(dtype=np.int32)).compile()

        self._pickers = {}
        if chunked:
            self._pickers["chunk"] = picker(m.chunk_tokens)
        if m.prefill_program is None:
            return
        self._pickers["prefill"] = picker(L)
        rows = m.chunk_tokens if chunked else L

        def stack_live(*kv):    # the device module's name, as the lane's
            return jax.numpy.stack([a[0, :rows] for a in kv])

        self._stack_live = jax.jit(stack_live).lower(
            *[sds(1, L, m.hidden)] * (2 * len(m.prefill_kv_fetches))
        ).compile()
        self._causal_bias = jax.device_put(
            np.triu(np.full((L, L), NEG_INF, "float32"), k=1)[None],
            self._engine.device)

    def _run(self, kind, feeds, span=None):
        """Execute one lowered program against the entry scope; written
        persistables (the arenas — donated, updated in place on device)
        re-enter the scope for the next call. ``span`` is the caller's
        live launch span, or None while tracing is off: it is told the
        bytes fed, in how many host arrays (``puts``: one a decode step,
        its ``dec_step``), and the nanoseconds the host spent inside
        ``jax.device_put`` (from the span's opening) and inside the
        executable's call — two clock reads, no child span, so the device
        module still belongs to the caller's span. The step program's two
        halves are also observed, tracing on or off, in
        ``serving_decode_step_put_seconds`` / ``..._call_seconds``: from
        the span's pair of reads where there is a span, else from this
        call's start. A feed that is a
        device array already (a launched-ahead step's tokens: the
        previous step's own output) is handed over as it is: nothing is
        put, nothing counted as fed, and the host does not wait for it;
        so are a one-shot prefill's K/V outputs fed to the inject program,
        and the prefill program's constant causal bias.

        While the tracer's lanes are on (one test, else) the launch also
        goes to the device's lane (``lane.py``): with a live span and an
        output that no later launch donates (the last of ``fetches``, never
        an arena), the ready watcher is handed the span's two reads as times
        on the tracer's clock, what the loop slept since its launch before
        and that output; a launch with no span or no output (the inject
        program) is named to the lane and rides with the next stamped
        one."""
        import jax

        entry, executable = self._entries[kind]
        dev = self._engine.device
        elapsed_ns = (span.elapsed_ns if span is not None
                      else _stopwatch_ns() if kind == "step" else None)
        fed = puts = 0
        feed_vals = []
        for n in entry.feed_names:
            a = feeds[n]
            if not isinstance(a, jax.Array):
                a = np.ascontiguousarray(a)
                fed += a.nbytes
                puts += 1
                a = jax.device_put(a, dev)
            feed_vals.append(a)
        if elapsed_ns is not None:
            put_ns = elapsed_ns()
        donated = tuple(self._scope.find_var(n) for n in entry.donated)
        readonly = tuple(self._scope.find_var(n) for n in entry.readonly)
        fetches, updates = executable(tuple(feed_vals), donated, readonly,
                                      self._rng0)
        if elapsed_ns is not None:
            call_ns = elapsed_ns() - put_ns
            if span is not None:
                span.set(bytes=fed, puts=puts, put_ns=put_ns,
                         call_ns=call_ns)
            if kind == "step":
                self._metrics.observe_step_launch(put_ns * 1e-9,
                                                  call_ns * 1e-9)
        self._metrics.count_launch(kind, fed)
        for n, u in zip(entry.written, updates):
            self._scope.set(n, u)
        if _lanes():
            if span is not None and fetches:
                t_call0 = span.opened_ns() + put_ns
                slept = self._metrics.slept_seconds()
                self._engine.lane.launched(
                    self._metrics, kind, self._metrics.launches(kind),
                    t_call0, t_call0 + call_ns,
                    int((slept - self._lane_slept) * 1e9), fetches[-1])
                self._lane_slept = slept
            else:
                self._engine.lane.rode(kind, program=True)
        return fetches

    def _fetch(self, value):
        """One fetch brought to the host (here the host waits for the
        device), counted in ``serving_fetched_bytes_total``. An output
        nobody passes here stays on the device: a greedy decode step
        fetches its ``[S, 1]`` tokens and leaves the logits there."""
        a = np.asarray(value)
        self._metrics.incr("fetched_bytes", a.nbytes)
        return a

    def _reset_arenas(self):
        """Zero the KV pool and drop all slot/block state (relaunch
        path: a failed donated call leaves the old arena buffers
        invalid)."""
        import jax
        import jax.numpy as jnp

        m = self._model
        for n, shape, dtype in m.slot_states:
            self._scope.set(n, jax.device_put(
                jnp.zeros(shape, dtype), self._engine.device))
        self.kv.reset()
        self._pool.reset()
        self._slots = [None] * m.slots
        # a step in flight read the lost arena: it is never delivered,
        # nor is a last chunk's row
        self._launched = None
        self._chunk_rows = []

    def relaunch(self):
        """The circuit breaker's replacement replica: rebuild programs
        from the model's builder (content-identical by construction),
        re-lower — every entry should come from the compile cache, not a
        trace — and reset the arena. Weights stay; queued requests are
        served by the relaunched replica."""
        if self._model.builder is not None:
            self._model = self._model.builder()
        self._lower_all()
        self._reset_arenas()
        self._metrics.incr("relaunches")

    # -- lifecycle --------------------------------------------------------
    def start(self):
        if self._thread is not None:
            return
        self._stop = False
        self._queue.reopen()
        self._thread = threading.Thread(
            target=self._loop, name=f"decode-{self._model.label}",
            daemon=True)
        self._thread.start()

    def shutdown(self, timeout=60.0):
        self._queue.close()
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None

    def release_states(self):
        """Once the loop has stopped, drop the K/V arenas and the per-slot
        states from the scope (nothing reads them again; the weights
        stay): what a caller does that needs the device's memory for
        something else after serving. False, and nothing dropped, while
        the loop runs."""
        if self._thread is not None:
            return False
        self._scope.erase(
            [n for kv in self._model.all_state_names for n in kv]
            + [n for n, _shape, _dtype in self._model.slot_states])
        return True

    def notify(self):
        with self._cond:
            self._cond.notify()

    # -- scheduler loop ---------------------------------------------------
    def _loop(self):
        while not self._iterate():
            pass

    def _iterate(self):
        """ONE scheduler iteration. Extracted so tests can hand-step the
        interleaving deterministically. Returns True when the loop
        should exit.

        The phases, in this order: expire, (breaker), resume parked
        sessions, admit up to the free slots, advance AT MOST ONE
        prefill chunk, run one verify cycle per speculative slot, then
        `_step`: feeds, launch, and — when a slot's policy needs the
        logits — the fetch and the host half at once. A step whose slots
        need only their tokens is left IN FLIGHT instead, its cursors
        already advanced, and the next iteration runs in one of two
        orders:

        * **ahead**: nothing of this iteration has to see the device or
          change who steps. An arrival whose admission is a block
          acquisition and a new prefilling slot is admitted, a
          prefilling slot gets its chunk, the prompt's LAST one too (a
          launch and the row picker's, no fetch), and `_step` launches
          step N+1 for the slots that already step, fed step N's tokens
          as the device array they are, BEFORE it fetches and delivers
          step N and then the last chunk's one logits row, which the
          device finished before N+1 began: the host's waits hide under
          N+1. The slot that row starts joins step N+2 with its token
          from the host beside the others' on the device.
        * **drain**: step N is fetched and delivered first, with nothing
          launched over it (`_drain`, which says why and counts it), and
          the iteration then runs as if no step had been in flight. Kept
          for what has to see the device or changes who steps: a brownout
          move, and what `_drain_reason` and, for a picked request,
          `_admission_waits` list.

        The device runs what it is given in launch order, which is what
        keeps the first order sound: a step launched before a slot
        retired writes its wasted row before the next owner's chunk,
        launched later, overwrites it, and a recurrent slot's chunk at
        position 0 resets its state after the wasted step. Depth one: at
        most one step is on the device unread, and the two orders share
        every line but the place of the fetches."""
        with _span("decode::iterate") as sp:
            if sp is not None:
                self._iteration += 1
                sp.set(iteration=self._iteration,
                       active=self._pool.active_count,
                       queued=self._queue.depth())
            return self._iterate_phases()

    def _iterate_phases(self):
        with self._cond:
            for r in self._queue.expire():
                self._reject_expired(r)
            # shutdown drains parked sessions and deferred admissions
            # too: capacity frees as slots retire, so they resume and
            # finish rather than abandoning their futures
            if (self._stop and self._queue.empty()
                    and self._pool.active_count == 0
                    and not self._parked and not self._pending):
                return True
        moved = self._brownout_tick()
        if self._launched is not None:
            why = "brownout" if moved else self._drain_reason()
            if why is not None:
                self._drain(why)
        if self._breaker is not None and not self._stop:
            verdict, wait_s = self._breaker.gate()
            if verdict == "wait":
                with self._cond:
                    for r in self._queue.expire():
                        self._reject_expired(r)
                    if not self._stop:
                        self._wait("breaker", min(wait_s, 0.1))
                return False
            if verdict == "probe" and not self._probe_relaunched:
                # re-admission probe IS a relaunch: fresh programs,
                # zeroed arena, executables from the compile cache —
                # ONCE per half-open episode (the flag); the probe
                # STEP's outcome then closes or reopens the breaker,
                # so an idle engine doesn't rebuild every loop tick
                self._metrics.incr("breaker_probes")
                try:
                    self.relaunch()
                    self._probe_relaunched = True
                except Exception:
                    self._breaker_event(self._breaker.record_failure())
                    return False
        # parked sessions and deferred admissions get first claim on
        # freed capacity — FIFO, before any new pick from the queue (with
        # a step in flight there is neither: `_drain_reason`). Pick
        # first, then decide: the picked requests say whether their
        # admission may run under the step in flight.
        admitted = self._service_parked()
        picked = self._pick_free_slots()
        if self._launched is not None and self._admission_waits(picked):
            self._drain("admission")
        admitted += self._admit_picked(picked)
        progressed = self._advance_prefills() + self._advance_spec()
        if not self._any_stepping():
            # nothing decodable AND this round moved nothing — either
            # the queue is empty, or everything queued is blocked on a
            # tenant cap held by another entry's in-flight work; poll,
            # don't spin
            if not admitted and not progressed:
                with self._cond:
                    if not self._stop:
                        self._wait("idle", 0.02)
            return False
        self._step()
        return False

    def _any_stepping(self):
        """Whether some slot is fed to decode steps."""
        return any(st is not None and st.mode in ("decode", "beam")
                   for st in self._slots)

    def _wait(self, why, timeout):
        """Sleep on the condition (held by the caller) until a submit or
        a shutdown notifies it, or ``timeout`` runs out: the one place the
        loop is asleep. Every sleep is observed in
        ``serving_decode_wait_seconds`` and is a ``decode::wait`` span
        that says why (``"idle"``: nothing decodable and this round moved
        nothing; ``"breaker"``: the open breaker's cooldown) and what the
        loop left waiting when it chose to sleep (``queued`` rows,
        ``parked`` sessions, ``pending`` deferred admissions): a wait
        taken with work queued is visible as such."""
        with _span("decode::wait") as sp:
            if sp is not None:
                sp.set(why=why, queued=self._queue.depth(),
                       parked=len(self._parked), pending=len(self._pending))
            t0 = time.perf_counter()
            self._cond.wait(timeout=timeout)
            self._metrics.observe_wait(time.perf_counter() - t0)

    def _steps_again(self, st):
        """Whether a decode slot is fed to the next step, from what the
        cursor shows: its launched tokens, read or not, leave room under
        ``max_new`` and ``max_len``. Only a slot with a token in flight
        can say no (any other was retired when its last token landed);
        an ``eos_id`` is not known yet and costs one wasted row. A slot
        that fills blocks steps until every fill pass of the block that
        holds its answer's last position is launched (that block needs no
        commit pass: nothing is generated after it)."""
        m = self._model
        if m.fills_blocks:
            req = st.request
            last = len(req.prompt) + req.max_new - 1
            return (st.cursor + m.block_len <= last
                    or st.bpass < st.fills)
        return (len(st.generated) + st.ahead < st.request.max_new
                and st.cursor < m.max_len)

    def _drain_reason(self):
        """Why the step in flight has to be fetched and delivered before
        this iteration goes on, or None when the iteration may run under
        it and launch the next step ahead of its fetch. From state that
        is there: a phase of this iteration has to see the device or
        changes who steps beyond one slot (a parked or deferred session,
        a speculative slot's verify, the last chunk of a BEAM request's
        prompt, whose first selection forks slots and copies arena rows),
        the engine is stopping or its breaker is not closed, or no slot
        of the step in flight steps again. An arrival is no reason
        (`_admission_waits` decides for the requests picked), nor is the
        last chunk of any other prompt (`_advance_prefills`)."""
        if self._stop:
            return "shutdown"
        if self._breaker is not None and self._breaker.state != "closed":
            return "breaker"
        if self._parked or self._pending:
            return "parked"
        steps = False
        for st in self._slots:
            if st is None:
                continue
            if st.mode == "spec":
                return "spec"
            if st.mode == "prefill":
                if (st.request.beam is not None and 0 < st.plen - st.done
                        <= self._model.chunk_tokens):
                    return "prefill"
            elif self._steps_again(st):
                steps = True
        return None if steps else "idle"

    def _reject_expired(self, request):
        self._metrics.incr("deadline_missed")
        self._engine._tenant_unqueue(request.tenant)
        request.response._complete(error=DeadlineExceededError(
            "deadline expired after "
            f"{time.perf_counter() - request.submit_time:.3f}s in queue"))
        self._metrics.observe_request(request)

    def _breaker_event(self, event):
        if event:
            self._metrics.incr(event)

    # -- admission (blocks + prefill/inject into a free slot) -------------
    def _admit_free_slots(self):
        return self._admit_picked(self._pick_free_slots())

    def _pick_free_slots(self):
        """Take from the queue what the free slots can hold. A picked
        request is committed to this entry (its tenant's in-flight
        reservation is held): `_admit_picked` has to follow."""
        picked = []
        # brownout L3+: LOW-lane dispatch quota drops to zero — queued
        # LOW requests wait out the pressure episode instead of landing
        # on an oversubscribed arena
        lanes = (Priority.LANES if self._brownout.level < 3
                 else tuple(p for p in Priority.LANES if p != Priority.LOW))
        with self._cond:
            rows = blocks = 0
            # admission by reservation: a tenant's head request whose chain
            # the pool cannot cover now waits in the queue (its turn comes
            # back; FIFO within the tenant), and no slot is spent on it
            fits = ((lambda req: self.kv.covers(req, blocks))
                    if self.kv.reserves else None)
            while self._pool.free_count - rows > 0:
                # budget in ROWS, not requests: a beam admission claims
                # width slots (seed + first-selection forks) before the
                # next pick runs
                req = self._engine._pick(
                    self._queue, max_rows=self._pool.free_count - rows,
                    lanes=lanes, fits=fits)
                if req is None:
                    break
                picked.append(req)
                rows += req.rows
                need = self.kv.chain(req)
                if need <= self._model.num_blocks:
                    blocks += need
            # the round's picks are ONE drain event for the rate EWMA
            self._queue.note_drained()
        return picked

    def _admission_waits(self, picked):
        """Whether admitting ``picked`` has to see the device or changes
        who steps, so that a step in flight is drained first. It does
        not when every one of them is a block acquisition from the free
        list and a new slot in mode ``"prefill"`` (`_takes_chunks`):
        host work, under which the step in flight goes on. It does for a
        one-shot prompt (its ``decode::prefill_fetch`` waits for the
        device: a produced token never waits behind an admission's
        prefill), for a speculative or beam request, and for blocks the
        free list cannot cover (an eviction's write-back reads the
        arenas, an exhausted pool parks a victim; under reservation they
        are a request's whole chain)."""
        blocks = 0
        for req in picked:
            if (req.draft_key is not None or req.beam is not None
                    or not self._takes_chunks(req)):
                return True
            blocks += self.kv.admission_blocks(req)
        return blocks > self.kv.free_blocks

    def _takes_chunks(self, req):
        """Whether a prompt streams through the chunk program: one the
        chunk budget does not cover, and every prompt, from its first
        token, of a model that has no one-shot prefill (a recurrent
        model's chunks build the slot's state as they go, and no block of
        it was ever registered, so nothing is shared)."""
        m = self._model
        return bool(m.chunk_tokens and "chunk" in self._entries
                    and (len(req.prompt) > m.chunk_tokens
                         or "prefill" not in self._entries))

    def _admit_picked(self, picked):
        for req in picked:
            self._engine._tenant_unqueue(req.tenant)
            if self._admit_one(req) == "deferred":
                # lockdep: ok(single writer: the scheduler thread; submit-side readers only probe emptiness (GIL-atomic) and tolerate staleness)
                self._pending.append(req)
        return len(picked)

    def _admit_one(self, req):
        """Admit one request (freshly picked or retried from
        ``_pending``) into a free slot. The caller's pick-time tenant
        in-flight reservation is held throughout; it is released here on
        every terminal outcome and KEPT on "deferred" (the request is
        still committed to this entry — it just waits for arena
        capacity). Returns "admitted" | "deferred" | "done"."""
        with _span("decode::admit") as sp:
            outcome = self._admit_into_slot(req)
            if sp is not None:
                sp.set(request=req.id, outcome=outcome,
                       reserved=self.kv.chain(req) if outcome == "admitted"
                       else 0, free=self.kv.free_blocks)
            return outcome

    def _admit_into_slot(self, req):
        if req.expired():
            # picked but dead: release the pick-time in-flight
            # reservation; no slot to free
            self._reject_in_flight(req, DeadlineExceededError(
                "deadline expired before prefill"))
            return "done"
        slot = self._pool.acquire()
        if slot is None:
            # only reachable on a _pending retry (fresh picks are
            # budgeted against free_count): wait for a retirement
            return "deferred"
        try:
            self._prefill_into(req, slot)
        except _DeferAdmission:
            self._pool.release(slot)
            self._slots[slot] = None
            return "deferred"
        except ArenaInvalidError as e:
            # donated inject failed: like a step failure, every
            # in-flight sequence is lost (failed loudly), the
            # outcome drives the breaker, and the arena resets
            self._slots[slot] = None
            self._reject_in_flight(req, RequestError(
                f"request {req.id} failed in inject: {e}"))
            self._arena_lost(f"arena failure during admission: {e}")
            # the reset arena is valid (zeroed): the REMAINING picked
            # requests still admit — dropping them would abandon
            # their futures and leak their tenants' queued counters
            return "done"
        except Exception as e:  # request-attributed, not replica health
            self._pool.release(slot)
            self._slots[slot] = None
            self._reject_in_flight(req, RequestError(
                f"request {req.id} failed in prefill: {e}"))
            return "done"
        return "admitted"

    def _acquire_blocks(self, req):
        """The prompt's K/V footing (`KVStore.acquire`), parking victims
        instead of hard-failing under exhaustion. Loud failure is the
        store's, for the one unfixable case: a chain no pool could hold.
        Otherwise victims are preempted (spilled to the host tier, to
        resume byte-identically) until the prompt fits; if that is not
        possible right now, ``_DeferAdmission`` sends the request to
        ``_pending`` with its tenant reservation intact. Under admission
        by reservation nobody is parked: the request's whole chain is
        promised by the pool or the request waits."""
        kv = self.kv.acquire(req)
        if kv is None and not self.kv.reserves:
            self._metrics.incr("blocks_exhausted")
            # don't preempt on behalf of NEW work while earlier preempted
            # sessions are still waiting — they have first claim on capacity
            while kv is None and not self._parked and self._park_victim(req):
                kv = self.kv.acquire(req)
            self._metrics.incr("blocks_parked_total")
            if kv is None:
                self._metrics.incr("admissions_deferred")
        if kv is None:
            raise _DeferAdmission()
        return kv

    # -- preemption / host-tier spill / resume ----------------------------
    def _park_victim(self, req):
        """Pick and park one decode-mode victim to free blocks for
        ``req``. Policy is a seam (tests shuffle it); the default preempts
        the most recently admitted session — oldest work is closest to
        finishing and freeing everything anyway."""
        cands = [s for s in range(self._model.slots)
                 if self._slots[s] is not None
                 and self._slots[s].mode == "decode"
                 and self._slots[s].request is not req]
        if not cands:
            return False
        if self.victim_policy is not None:
            pick = self.victim_policy(cands)
        else:
            pick = max(cands, key=lambda s: self._slots[s].seq)
        return self._park_slot(pick)

    def _park_slot(self, s):
        """Preempt one live slot: spill its private KV rows ``[0:cursor)``
        to the host tier, free its blocks + slot (+ draft footprint), and
        queue the session for FIFO resume. Host state (sampling stream,
        grammar cursor, committed tokens) stays on the parked ``_Slot``
        untouched — resume is byte-identical by construction. Returns
        False when the session cannot be parked (host tier exhausted, or
        it can never be resumed because its lifetime footprint exceeds
        the whole pool)."""
        st = self._slots[s]
        if (st is None or st.mode not in ("decode", "spec") or st.ahead
                or not self.kv.restores):
            # a session whose last token is still on the device cannot
            # be spilled: its rows are known, its tokens are not. Nor can
            # one whose rows the store could never put back
            return False
        req = st.request
        m = self._model
        # a spec session holds no target arena rows: its park is pure
        # host state. Its draft-KV footprint (if any) is released; resume
        # falls back to replay proposals — same committed tokens either way
        if (st.mode == "decode" and -(-(st.plen + req.max_new)
                                      // m.block_size) > m.num_blocks):
            return False
        keys = self._spill(req, [st])
        if keys is None:
            return False
        self._vacate(s)
        # lockdep: ok(single writer: the scheduler thread; submit-side readers only probe emptiness (GIL-atomic) and tolerate staleness)
        self._parked.append(_ParkedSession(req, st.mode, [st], keys))
        self._metrics.incr("sessions_parked")
        return True

    def _spill(self, req, states):
        """Every state's rows ``[0:cursor)`` to the host tier, rank-keyed,
        under one ``decode::spill`` span: the keys, or None (and nothing
        kept) where the tier cannot take them."""
        keys, spilled = [], 0
        with profiler.RecordEvent("decode::spill") as ev:
            faults.fire("decode.spill")
            for rank, st in enumerate(states):
                if st.kv is None:
                    continue
                key, nbytes = self.kv.spill(st.kv, st.cursor, req.id, rank,
                                            self._committed(st))
                spilled += nbytes
                if ev.span is not None:
                    ev.span.set(bytes=spilled)
                if key is None:
                    self.kv.drop_spilled(keys)
                    return None
                keys.append(key)
        return keys

    def _vacate(self, slot):
        """Empty a batch slot: the slot, its sequence's blocks (with what
        is left of its reservation) and its draft footprint given back.
        Returns the state that held it."""
        st = self._slots[slot]
        self._slots[slot] = None
        self._pool.release(slot)
        if st is not None:
            self.kv.release(st.kv)
            self._release_draft(st)
        return st

    def _park_group(self, group):
        """Preempt a whole beam group: every live hypothesis spills its
        rows (rank-keyed), the group releases ALL its slots (spares
        included), and resume rebuilds ``order`` in the same rank order —
        selection tie-breaking stays bit-identical."""
        m = self._model
        states = [self._slots[sid] for sid in group.order]
        if sum(-(-st.cursor // m.block_size) for st in states) > m.num_blocks:
            return False
        keys = self._spill(group.request, states)
        if keys is None:
            return False
        for sid in group.order + group.spare:
            self._vacate(sid)
        group.spare = []
        group.order = []
        # lockdep: ok(single writer: the scheduler thread; submit-side readers only probe emptiness (GIL-atomic) and tolerate staleness)
        self._parked.append(
            _ParkedSession(group.request, "beam", states, keys, group=group))
        self._metrics.incr("sessions_parked")
        return True

    def _service_parked(self):
        """Resume parked sessions (FIFO, stop at the first that does not
        fit yet), then retry deferred admissions. Runs at the top of
        every iteration, before new picks — preempted work has first
        claim on freed capacity."""
        progressed = 0
        while self._parked:
            ps = self._parked[0]
            if ps.request.expired():
                # lockdep: ok(single writer: the scheduler thread; submit-side readers only probe emptiness (GIL-atomic) and tolerate staleness)
                self._parked.pop(0)
                self._drop_parked(ps, DeadlineExceededError(
                    "deadline expired while parked under arena pressure"))
                continue
            if not self._resume_session(ps):
                break
            # lockdep: ok(single writer: the scheduler thread; submit-side readers only probe emptiness (GIL-atomic) and tolerate staleness)
            self._parked.pop(0)
            progressed += 1
        if not self._parked and self._pending:
            pend, self._pending = self._pending, []
            for req in pend:
                if self._admit_one(req) == "deferred":
                    # lockdep: ok(single writer: the scheduler thread; submit-side readers only probe emptiness (GIL-atomic) and tolerate staleness)
                    self._pending.append(req)
                else:
                    progressed += 1
        return progressed

    def _drop_parked(self, ps, error):
        self.kv.drop_spilled(ps.keys)
        self._reject_in_flight(ps.request, error)

    def _resume_session(self, ps):
        """Re-admit one parked session. Returns False when capacity is
        still insufficient (caller retries next iteration); True when the
        session left the parked list — resumed, or terminally failed via
        an arena loss during re-injection."""
        if ps.mode == "spec":
            s = self._pool.acquire()
            if s is None:
                return False
            with profiler.RecordEvent("decode::resume"):
                faults.fire("decode.resume")
                self._slots[s] = ps.states[0]
            self._metrics.incr("sessions_resumed")
            return True
        # every live hypothesis comes back together, in rank order (a
        # decode session is one): slots and fresh chains for all, or none
        got = []
        for st in ps.states:
            s = self._pool.acquire()
            kv = self.kv.acquire_rows(st.cursor) if s is not None else None
            if kv is None:
                if s is not None:
                    self._pool.release(s)
                for s, st in got:
                    self._pool.release(s)
                    self.kv.release(st.kv)
                return False
            st.kv = kv
            got.append((s, st))
        for s, st in got:
            self._slots[s] = st
        group = ps.group
        if group is not None:
            group.order = [s for s, _st in got]
            self._claim_spares(group)
        with profiler.RecordEvent("decode::resume"):
            faults.fire("decode.resume")
            for (_s, st), key in zip(got, ps.keys):
                try:
                    self.kv.restore(
                        st.kv, key, st.cursor,
                        lambda: self.run_prefill(self._committed(st))[1:])
                except ArenaInvalidError as e:
                    # the session was rejected with every other slot
                    self._arena_lost(f"resume inject failure: {e}")
                    self.kv.drop_spilled(ps.keys)
                    return True
        self._metrics.incr("sessions_resumed")
        return True

    @staticmethod
    def _committed(st):
        """The tokens whose K/V rows a session holds: ``[0:cursor)``."""
        return (list(st.request.prompt) + list(st.generated))[:st.cursor]

    # -- brownout ----------------------------------------------------------
    def _brownout_tick(self):
        """One severity evaluation per scheduler iteration. Occupancy
        saturates while anything is parked or deferred — the arena is
        over-subscribed even if the instantaneous row count dipped.
        Returns whether the ladder moved."""
        occ = self.kv.occupancy()
        if self._parked or self._pending:
            occ = 1.0
        qp = self._queue.pressure()
        self._brownout.step(occupancy=occ,
                            queue_seconds=qp["queue_seconds"],
                            deadline=qp["deadline"])
        n = len(self._brownout.transitions)
        moved = n > self._bt_seen
        if moved:
            self._metrics.incr("brownout_transitions", n - self._bt_seen)
            for t in self._brownout.stamp(self._bt_seen,
                                          time.perf_counter()):
                _instant("brownout::transition", **{
                    k: t[k] for k in ("from", "to", "trigger", "value")})
            self._bt_seen = n
        return moved

    def _shed_confirmed(self):
        """Live pressure re-check guarding the two REJECT gates (L4
        shed, L3 beam cap). Severity is sampled by the scheduler tick
        and decays hysteretically, so right after a burst clears it can
        overstate the instantaneous state — degrading quality on a
        stale reading is harmless, but turning a request away is not.
        Read-only: no controller mutation, safe from the submit
        thread."""
        if self._parked or self._pending:
            return True
        try:
            occ = self.kv.occupancy()
        except Exception:
            occ = 0.0
        qp = self._queue.pressure()
        live = max(occ, qp["queue_seconds"], qp["deadline"])
        return live >= self._brownout.exit[self._brownout.level - 1]

    def _prefill_into(self, req, slot):
        req.dispatch_time = time.perf_counter()
        self._admit_seq += 1
        # brownout L1/L2: shed OUTPUT-INVISIBLE quality first — committed
        # tokens are identical with or without speculation/draft-KV, only
        # the step count changes
        severity = self._brownout.level
        if req.draft_key is not None and severity < 2:
            # speculative: no TARGET arena footprint — verification
            # re-derives every KV it needs inside the (stateless) batch
            # prefill. With draft_kv the proposals get their own slot +
            # blocks on the DRAFT entry (O(1) per proposed token);
            # admission failure there degrades to replay proposals.
            st = _Slot(req, "spec", self._admit_seq)
            st.toks = list(req.prompt)
            st.sampling = req.sampling
            if req.grammar is not None:
                st.grammar = GrammarConstraint(req.grammar)
            self._slots[slot] = st
            if req.draft_kv and severity < 1:
                draft = self._engine._entries.get(req.draft_key)
                if draft is not None:
                    self._admit_draft_kv(st, draft)
            self._metrics.incr("admitted")
            self._metrics.tenant_incr("admitted", req.tenant)
            return
        prompt = req.prompt
        plen = len(prompt)
        if self._takes_chunks(req):
            st = _Slot(req, "prefill", self._admit_seq)
            st.kv = self._acquire_blocks(req)
            if self._model.fills_blocks:
                # the prompt's whole blocks are the chunk program's; what
                # is left opens the first block, and no row is fetched
                st.plen -= plen % self._model.block_len
                if not st.plen:
                    self._start_blocks(st)
            else:
                # the FINAL chunk always runs (it produces the
                # last-position logits), even when the radix served every
                # block, or the host tier the blocks past them
                st.done = min(max(st.kv.shared_len, self.kv.restore_prefix(
                    st.kv, prompt, req.id)), plen - 1)
            self._slots[slot] = st
            self._metrics.incr("admitted")
            self._metrics.tenant_incr("admitted", req.tenant)
            return
        key, cached = self.kv.prefix_get(prompt)
        fetches = None
        if cached is not None:
            # hit/miss totals live on PrefixCache (one source, surfaced
            # by stats()); only the per-tenant series is a counter here
            self._metrics.tenant_incr("prefix_hits", req.tenant)
        else:
            # a miss moves no bulk bytes across the host link: the
            # prefill program's outputs stay on the device, where the
            # inject program, the row picker and the stack-and-trim read
            # them, all launched before the host waits for anything
            t0 = time.perf_counter()
            with profiler.RecordEvent("decode::prefill") as ev:
                faults.fire("decode.prefill")
                if ev.span is not None:
                    ev.span.set(request=req.id, prompt_len=plen)
                fetches = self.run_prefill(prompt, ev.span)
        try:
            kv = self._acquire_blocks(req)
        except _DeferAdmission:
            if fetches is not None:
                # the retry finds the prompt in the prefix cache
                self._prefill_to_host(req, key, fetches)
            raise
        st = _Slot(req, "decode", self._admit_seq)
        st.kv = kv
        if kv.shared_len < plen:
            # inject ONLY the non-shared suffix: shared blocks already
            # hold byte-identical rows (same tokens -> same prefix ->
            # same KV bytes). A miss's rows never leave the device
            self.kv.write_rows(
                kv, kv.shared_len, plen,
                fetches[1:] if fetches is not None else cached[0],
                "decode::inject", fault="decode.inject", request=req.id)
            if fetches is not None:
                self._metrics.incr("prefill_device_injects")
        if fetches is not None:
            cached = self._prefill_to_host(req, key, fetches)
            self._metrics.observe_prefill(time.perf_counter() - t0)
        live, logits_row = cached
        self.kv.register(kv, prompt, live)
        st.cursor = plen
        self._slots[slot] = st
        self._metrics.incr("admitted")
        self._metrics.tenant_incr("admitted", req.tenant)
        if req.beam is not None:
            st.mode = "beam"
            self._begin_beam(slot, logits_row)
            return
        self._first_token(slot, st, logits_row)

    def _first_token(self, slot, st, logits_row):
        """A decode slot's first token, off its prompt's last logits row:
        chosen, stamped and counted, and the slot retired where that token
        ends the request. Returns the stamp, or None when it retired."""
        req = st.request
        st.sampling = req.sampling
        if req.grammar is not None:
            st.grammar = GrammarConstraint(req.grammar)
        first = self._choose_token(st, logits_row, device_masked=False)
        st.last_token = first
        st.generated = [first]
        now = time.perf_counter()
        req.response.token_times.append(now)
        # the prefill's first token: counted apart from generated_tokens
        # so tokens_per_step stays a decode-step quantity (<= S)
        self._metrics.incr("prefill_tokens")
        self._metrics.tenant_incr("tokens", req.tenant)
        if self._finished(st):
            self._retire(slot)
            return None
        return now

    def _start_blocks(self, st):
        """A block-filling slot whose prompt's whole blocks are launched
        starts stepping: its first block opens at the end of them, the
        prompt's last ``len % block_len`` tokens in it as positions
        already decided. Nothing is fetched: its first token comes out of
        a block pass."""
        B = self._model.block_len
        left = [int(t) for t in st.request.prompt[st.plen:]]
        st.mode = "decode"
        st.cursor = st.plen
        st.block = left + [-1] * (B - len(left))
        st.fills, st.bpass = B - len(left), 0
        st.pending, st.decided_at = {}, []
        st.sampling = st.request.sampling

    def _prefill_feeds(self, prompt):
        m = self._model
        toks = np.zeros((1, m.max_len), "int64")
        toks[0, :len(prompt)] = prompt
        pos = np.arange(m.max_len, dtype="int64")[None]
        return {DecodeModel.PRE_TOKENS: toks,
                DecodeModel.PRE_POSITIONS: pos,
                DecodeModel.PRE_BIAS: self._causal_bias}

    def prefill_logits(self, tokens, event=None):
        """The ``[L, V]`` logits of `run_prefill` over ``tokens``, on the
        host; the launch alone under a span named ``event``."""
        if event is None:
            fetches = self.run_prefill(tokens)
        else:
            with profiler.RecordEvent(event):
                fetches = self.run_prefill(tokens)
        return self._fetch(fetches[0])[0]

    def run_prefill(self, tokens, span=None):
        """The stateless whole-sequence forward over ``tokens``, launched:
        its device outputs, the ``[1, L, V]`` logits first, then a ``[1,
        L, H]`` K and V per state pair. Another entry's scheduler calls it
        too, on its draft (replay proposals, a draft-KV admission)."""
        return self._run("prefill", self._prefill_feeds(tokens), span)

    def _prefill_to_host(self, req, key, fetches):
        """The host's copy of a one-shot prefill, in two fetches: the
        ``[V]`` logits row at the prompt's last position and the
        ``[2 * layers, P, H]`` live K/V rows (``_compile_admission_helpers``),
        both cut on the device from the prefill program's outputs, which
        stay there. Both launches precede both fetches. The pair is the
        prefix cache's entry, and the rows back the copy-on-write of a
        shared partial block."""
        with _span("decode::prefill_fetch") as sp:
            row = self._pickers["prefill"](
                fetches[0], np.int32(len(req.prompt) - 1))
            live = self._stack_live(*fetches[1:])
            if _lanes():
                self._engine.lane.rode("pick_row")
                self._engine.lane.rode("stack_live")
            logits_row = self._fetch(row)
            live = self._fetch(live)
            self.kv.prefix_put(key, live, logits_row)
            if sp is not None:
                sp.set(request=req.id,
                       bytes=logits_row.nbytes + live.nbytes)
        return live, logits_row

    # -- chunked prefill ---------------------------------------------------
    def _advance_prefills(self):
        """Process ONE budgeted chunk for ONE prefilling slot
        (round-robin): the per-iteration prompt work is bounded by
        ``chunk_tokens``, which is the fairness contract — in-flight
        decode slots stall for at most one chunk's compute per admitted
        long prompt.

        A chunk is a launch and no fetch, the prompt's LAST one too: the
        row picker is launched behind it and the ``[V]`` row it cuts
        stays on the device in ``self._chunk_rows`` until `_step` has
        launched the next step over it (`_land_chunks`); the slot stays
        in mode ``"prefill"`` till then. With a step in flight the chunk
        is a launch AHEAD. Where no step is in flight and no slot steps
        there is nothing to launch over it, and the row is fetched at
        once; so is a beam request's, whose first selection forks slots
        and copies arena rows (`_drain_reason` drained for it)."""
        m = self._model
        pref = [s for s, st in enumerate(self._slots)
                if st is not None and st.mode == "prefill"
                and st.done < st.plen]
        if not pref:
            return 0
        # brownout L2+: halve the chunk budget (one chunk every OTHER
        # iteration) — admitted long prompts land later, but in-flight
        # decode slots keep their step cadence under pressure
        if self._brownout.level >= 2:
            self._chunk_throttle = not self._chunk_throttle
            if self._chunk_throttle:
                return 0
        s = pref[self._pref_rr % len(pref)]
        self._pref_rr += 1
        st = self._slots[s]
        req = st.request
        if req.expired():
            self._reject_in_flight(req, DeadlineExceededError(
                f"deadline expired during chunked prefill after "
                f"{st.done}/{st.plen} tokens"), slot=s)
            return 1
        C = m.chunk_tokens
        start = st.done
        stop = min(start + C, st.plen)
        real = stop - start
        last = stop == st.plen
        ahead = self._launched is not None
        toks = np.zeros((1, C), "int64")
        toks[0, :real] = req.prompt[start:stop]
        pos = np.zeros((1, C), "int64")
        pos[0, :real] = np.arange(start, stop)
        self._window_chunk(st.kv, start, stop)
        if m.index_names:
            self._metrics.observe_sparse_chunk(start, stop, m.index_topk,
                                               len(m.index_names))
        t0 = time.perf_counter()
        try:
            with profiler.RecordEvent("decode::chunk") as ev:
                faults.fire("decode.chunk")
                if ev.span is not None:
                    # context: the rows behind the chunk; live_blocks: the
                    # blocks its attention reads (through its own rows)
                    ev.span.set(request=req.id, tokens=real, ahead=ahead,
                                last=last, passes=m.passes,
                                block_mask=m.fills_blocks, context=start,
                                live_blocks=-(-stop // m.block_size))
                # a group's span, row map and write rows (which never
                # rewrite radix-shared rows)
                feeds = {
                    DecodeModel.CHU_TOKENS: toks,
                    DecodeModel.CHU_POSITIONS: pos,
                    **m.chunk_feeds(start, real, st.kv.groups),
                }
                if m.recurrent:
                    # the chunk at position 0 resets the slot's state
                    # (``decode::state_reset``: whatever a retired or
                    # wasted step left there), every chunk advances it
                    feeds[DecodeModel.CHU_SLOT] = np.array([s], "int64")
                    if start == 0:
                        _instant("decode::state_reset", request=req.id,
                                 slot=s)
                fetches = self._run("chunk", feeds, ev.span)
        except Exception as e:
            # with a step in flight the slots of both are lost, and that
            # step is not delivered
            self._arena_lost(f"chunk-prefill failure: {e}")
            return 1
        self._metrics.observe_chunk(real, time.perf_counter() - t0, ahead,
                                    context=start)
        st.done = stop
        if not last:
            return 1
        if m.fills_blocks:
            self._start_blocks(st)
            return 1
        # the one row a prompt's last chunk is run for, [V] of the
        # [1, C, V] that stay on the device
        self._chunk_rows.append(_LaunchedChunk(s, st, self._pickers["chunk"](
            fetches[0], np.int32(real - 1))))
        if _lanes():
            self._engine.lane.rode("pick_row")
        if req.beam is not None or not (ahead or self._any_stepping()):
            self._land_chunks(deferred=False)
        return 1

    def _window_chunk(self, kv, start, stop):
        """Before the chunk ``[start, stop)`` is launched: in every group
        that has a window, what lies behind the window of the chunk's
        first query is given back (`KVStore.release_behind`) and what the
        group's layers will read is counted beside what their context
        holds; then the chunk's own blocks are opened there."""
        for g in self.kv.windowed:
            with _span("decode::window_release") as sp:
                given = self.kv.release_behind(kv, start, g)
                if sp is not None:
                    sp.set(blocks=given, chunk=True)
            self._metrics.observe_window_chunk(start, stop, kv.groups[g],
                                               given)
        self.kv.open_windows(kv, stop)

    def _land_chunks(self, deferred):
        """Fetch every last chunk's logits row that is still on the
        device and start its slot: the prompt's blocks registered, the
        cursor at the prompt's end, the first token chosen and stamped
        (a beam request's first selection), the slot in mode
        ``"decode"`` with its token on the HOST, or retired where that
        token ends the request, or rejected where its deadline ran out
        meanwhile (finished wins over expired, as in `_sample`).
        ``deferred`` says whether a launch was made over the row since
        its chunk's (``decode::chunk_fetch`` carries it): the buffer is
        then ready, or nearly."""
        if not self._chunk_rows:
            return
        landing, self._chunk_rows = self._chunk_rows, []
        for rec in landing:
            s, st = rec.slot, rec.state
            if self._slots[s] is not st:
                continue    # rejected since the launch: the row is dropped
            req = st.request
            with _span("decode::chunk_fetch") as sp:
                logits_row = self._fetch(rec.row)
                if sp is not None:
                    sp.set(request=req.id, bytes=logits_row.nbytes,
                           deferred=deferred)
            self.kv.register(st.kv, req.prompt)
            st.cursor = st.plen
            if req.beam is not None:
                st.mode = "beam"
                try:
                    self._begin_beam(s, logits_row)
                except ArenaInvalidError as e:
                    self._arena_lost(f"beam fork inject failure: {e}")
                continue
            st.mode = "decode"
            now = self._first_token(s, st, logits_row)
            if now is not None and req.expired(now):
                self._reject_in_flight(req, DeadlineExceededError(
                    "deadline expired mid-generation after 1 tokens"),
                    slot=s)

    # -- speculative decoding ----------------------------------------------
    def _advance_spec(self):
        """One draft-propose + target-verify cycle per speculative slot.
        The draft greedily proposes up to ``spec_k`` tokens (one
        stateless draft-prefill forward each); the target verifies ALL
        of them in ONE batch-prefill forward — logits at position
        ``n-1+j`` depend only on tokens ``<= n-1+j`` (causal mask,
        exact-zero padding), so each emitted token equals what
        target-only greedy decode would emit: bit-identical by
        construction, fewer target steps per token by measurement."""
        m = self._model
        progressed = 0
        for s in range(m.slots):
            st = self._slots[s]
            if st is None or st.mode != "spec":
                continue
            progressed += 1
            req = st.request
            if req.expired():
                self._reject_in_flight(req, DeadlineExceededError(
                    "deadline expired mid-speculation after "
                    f"{len(st.generated)} tokens"), slot=s)
                continue
            draft = self._engine._entries.get(req.draft_key)
            if draft is None:
                self._reject_in_flight(req, RequestError(
                    f"draft model {'@'.join(req.draft_key)} left the "
                    "registry mid-generation"), slot=s)
                continue
            n = len(st.toks)
            k = min(req.spec_k, req.max_new - len(st.generated),
                    m.max_len - n, draft.model.max_len - n)
            k = max(k, 0)
            # both forwards are STATELESS prefills (donation off): a
            # failure loses nothing but this cycle, so it is a
            # request-attributed failure — never a dead scheduler
            # thread, never an arena loss. (This also contains the
            # cross-entry read: draft.run_prefill from this thread may race
            # a draft-side breaker relaunch, whose builder contract makes
            # any observed executable content-identical — and any torn
            # state it could still surface lands here, on one request.)
            try:
                props = None
                if st.d_slot is not None and k > 0:
                    props = self._draft_propose_kv(st, draft, k)
                if props is None:
                    props = []
                    dtoks = list(st.toks)
                    for _ in range(k):
                        nxt = int(np.argmax(draft.prefill_logits(
                            dtoks, "decode::spec_draft")[len(dtoks) - 1]))
                        props.append(nxt)
                        dtoks.append(nxt)
                    self._metrics.incr("spec_draft_steps", k)
                else:
                    dtoks = list(st.toks) + props
                self._metrics.incr("spec_proposed_tokens", k)
                t0 = time.perf_counter()
                with profiler.RecordEvent("decode::spec_verify"):
                    faults.fire("decode.verify")
                    fetches = self.run_prefill(dtoks)
            except Exception as e:
                self._reject_in_flight(req, RequestError(
                    f"request {req.id} failed in speculative cycle: "
                    f"{e}"), slot=s)
                continue
            self._metrics.incr("spec_target_steps")
            self._metrics.observe_prefill(time.perf_counter() - t0)
            logits = self._fetch(fetches[0])         # [1, L, V]
            now = time.perf_counter()
            finished = False
            accepted_n = 0
            for j in range(k + 1):
                # COMMITTED COUPLING: the target always derives ITS OWN
                # token from its (masked, sampled) committed stream at
                # this position; a proposal is accepted iff it equals
                # that token. The realized stream is therefore
                # bit-identical to target-only decode in EVERY policy —
                # greedy acceptance is the temperature-0 special case.
                t = self._choose_token(st, logits[0, n - 1 + j],
                                       device_masked=False)
                st.generated.append(t)
                st.toks.append(t)
                st.last_token = t
                req.response.token_times.append(now)
                self._metrics.incr("spec_emitted_tokens")
                self._metrics.tenant_incr("tokens", req.tenant)
                if j < k and props[j] == t:
                    self._metrics.incr("spec_accepted_tokens")
                    accepted_n += 1
                    accepted = True
                else:
                    accepted = False
                if (len(st.generated) >= req.max_new
                        or (m.eos_id is not None and t == m.eos_id)
                        or len(st.toks) >= m.max_len):
                    finished = True
                    break
                if not accepted:
                    break   # t was the correction token: later positions
                            # saw the wrong draft prefix
            st.cursor = len(st.toks)
            if st.d_slot is not None:
                # roll the draft cursor back to the first position whose
                # written KV row may disagree with the committed tokens
                # (the rejected proposal's slot onward); the next
                # cycle's catch-up rewrites from there
                st.d_cursor = min(st.d_cursor, n + accepted_n)
            if finished:
                self._retire(s)
        return progressed

    # -- draft-KV speculative slots ---------------------------------------
    def _admit_draft_kv(self, st, draft):
        """Give a speculative slot its own KV slot + blocks on the DRAFT
        entry and prefill the prompt into them ONCE; every later
        proposal is then one [S,1] draft decode step instead of a
        whole-prompt replay. Draft blocks are never radix-registered.
        Any failure falls back to replay proposals (counted), never
        fails the request."""
        if not draft._draft_ok or not draft._draft_pinned:
            return
        prompt = st.request.prompt
        seat = None
        try:
            with draft._draft_lock:
                seat = draft.draft_seat(st.request)
                if seat is None:
                    self._metrics.incr("spec_draft_kv_fallbacks")
                    return
                with profiler.RecordEvent("decode::spec_draft_prefill"):
                    outs = draft.run_prefill(prompt)
                draft.kv.write_rows(
                    seat[1], 0, len(prompt),
                    draft.kv.host_rows(outs[1:], len(prompt)),
                    "decode::spec_draft_inject")
                st.d_entry, (st.d_slot, st.draft_kv) = draft, seat
                st.d_cursor = len(prompt)
                self._metrics.incr("spec_draft_kv_prefills")
        except Exception:
            # the inject is DONATED on the draft arena: poison the entry
            # (all draft-KV users revert to replay) rather than trusting
            # an undefined arena
            draft._draft_ok = False
            if seat is not None:
                draft.draft_unseat(*seat)
            self._metrics.incr("spec_draft_kv_fallbacks")

    def _release_draft(self, st):
        """Return a spec slot's draft-side footprint (the draft lock is
        re-entrant: a proposal cycle that holds it may call this)."""
        draft = st.d_entry
        if draft is not None:
            with draft._draft_lock:
                draft.draft_unseat(st.d_slot, st.draft_kv)
            st.d_entry = st.d_slot = st.draft_kv = None
            st.d_cursor = 0

    def _draft_propose_kv(self, st, draft, k):
        """Greedy draft proposals in O(1) decode steps per token from
        the draft's own arena slot. Catch-up first feeds every committed
        token whose draft KV row is not yet written (at most the last
        cycle's correction + bonus positions) — the final catch-up
        step's logits ARE the first proposal — then each further
        proposal is one more draft decode step. Returns the k proposals
        (bit-identical to replay-prefill proposals by the decode ≡
        prefill invariant applied to the draft entry), or None to make
        the caller fall back to replay."""
        if not draft._draft_ok:
            self._release_draft(st)
            self._metrics.incr("spec_draft_kv_fallbacks")
            return None
        n = len(st.toks)
        props = []
        with draft._draft_lock:
            cur = None
            for p in range(min(st.d_cursor, n - 1), n):
                cur = self._draft_step_kv(st, draft, st.toks[p], p,
                                          write=p >= st.d_cursor)
                if cur is None:
                    return None
                st.d_cursor = max(st.d_cursor, p + 1)
            props.append(int(np.argmax(cur)))
            for j in range(1, k):
                cur = self._draft_step_kv(st, draft, props[j - 1],
                                          n + j - 1, write=True)
                if cur is None:
                    return None
                st.d_cursor = max(st.d_cursor, n + j)
                props.append(int(np.argmax(cur)))
        return props

    def _draft_step_kv(self, st, draft, token, p, write):
        """ONE draft decode step: feed ``token`` at position ``p`` into
        the spec slot's draft arena slot (writing KV row p when asked;
        rewriting an already-correct row is a byte-identical no-op) and
        return the [V] logits row. Returns None after releasing the
        draft footprint when the draft pool is exhausted or the draft
        arena died — the caller reverts to replay proposals."""
        try:
            opened = not write or draft.kv.open_block(st.draft_kv, p)
            if opened:
                row = draft.draft_step(st.d_slot, st.draft_kv, p, token,
                                       write)
        except Exception:
            # donated call on the DRAFT arena failed: poison the draft
            # for every user; this request reverts to replay proposals
            draft._draft_ok = False
            opened = False
        if not opened:
            self._release_draft(st)
            self._metrics.incr("spec_draft_kv_fallbacks")
            return None
        self._metrics.incr("spec_draft_kv_steps")
        return row

    # -- as a draft: called from a target's scheduler thread, under
    # `_draft_lock`, while this entry is pinned (its own loop is idle) ------
    def draft_seat(self, req):
        """A batch slot and the prompt's blocks for a speculative request
        of another entry: ``(slot, SeqKV)``, or None when either is out."""
        slot = self._pool.acquire()
        if slot is None:
            return None
        try:
            kv = self.kv.acquire(req)
        except RuntimeError:    # a prompt this pool can never hold
            kv = None
        if kv is None:
            self._pool.release(slot)
            return None
        return slot, kv

    def draft_unseat(self, slot, kv):
        self.kv.release(kv)
        self._pool.release(slot)

    def draft_step(self, slot, kv, p, token, write):
        """ONE decode step of this entry for ``slot`` alone: ``token`` at
        position ``p``, its K/V row written where ``write`` (else the
        row is right already). Returns the ``[V]`` logits row."""
        m = self._model
        step = m.step_feed()
        m.fill_step(step, slot, p, kv.groups, int(token), write)
        feeds = {DecodeModel.DEC_STEP: step,
                 DecodeModel.DEC_TOKEN: self._no_tokens}
        if m.logits_mask:
            feeds[DecodeModel.DEC_MASK] = np.zeros(
                (m.slots, 1, m.vocab_size), "float32")
        with profiler.RecordEvent("decode::spec_draft_kv"):
            fetches = self._run("step", feeds)
        if write:
            self.kv.note_append(kv, p)
        return self._fetch(fetches[0])[slot, 0]

    # -- the decode iteration ---------------------------------------------
    def _arena_lost(self, why):
        """A donated call failed: the arena is undefined. Fail every
        in-flight sequence loudly, drive the breaker, reset."""
        self._metrics.incr("step_failures")
        self._probe_relaunched = False
        if self._breaker is not None:
            self._breaker_event(self._breaker.record_failure())
        self._reject_all_slots(lambda r: ReplicaLostError(
            f"request {r.id} lost to {why}"))
        self._reset_arenas()

    def _reject_all_slots(self, make_error):
        """Fail every in-flight sequence loudly — ONE completion per
        request, even when a beam request holds several slots."""
        groups = []
        for s, st in enumerate(list(self._slots)):
            if st is None:
                continue
            if st.beam is not None:
                if st.beam not in groups:
                    groups.append(st.beam)
                continue
            self._reject_in_flight(st.request, make_error(st.request),
                                   slot=s)
        for g in groups:
            self._reject_beam_group(g, make_error(g.request))

    # -- generation policy (host-side selection over fetched logits) ------
    def _choose_token(self, st, logits_row, device_masked):
        """The ONE token-selection point for non-beam paths, wherever a
        logits ROW becomes a token on the host: grammar mask
        (host-applied unless the decode program already added the
        DEC_MASK feed — bit-identical either way, float32 add on both
        sides), then the committed-stream sampler or plain argmax. The
        step index is the absolute emitted-token index, so the sampled
        stream replays bit-exactly for ANY admission order, batchmates,
        or slot assignment. A decode step whose slots are all greedy has
        no row to pass: its tokens are the step program's own argmax
        over the same float32 rows (`_tokens_suffice`, `_sample`), the
        first index of the maximum on both sides."""
        row = np.asarray(logits_row, dtype=np.float32).reshape(-1)
        if st.grammar is not None and not device_masked:
            row = row + st.grammar.mask()
        if st.sampling is not None and not st.sampling.greedy:
            faults.fire("decode.sample")
            t = sample_token(row, st.sampling, len(st.generated))
            self._metrics.incr("sampled_tokens")
        else:
            t = int(np.argmax(row))
        if st.grammar is not None:
            st.grammar.advance(t)
            self._metrics.incr("grammar_steps")
        return t

    # -- beam search (COW forks over the block arena) ----------------------
    def _begin_beam(self, s, logits_row):
        """First selection of a freshly prefilled beam request: the seed
        hypothesis (empty continuation, score 0) expands into up to
        ``width`` live beams — the seed slot hosts the top survivor in
        place, the rest fork from it."""
        st = self._slots[s]
        req = st.request
        group = _BeamGroup(req)
        st.beam = group
        st.score = 0.0
        if req.grammar is not None:
            st.grammar = GrammarConstraint(req.grammar)
        group.order = [s]
        # the admission round budgeted width rows for this pick
        self._claim_spares(group)
        self._metrics.incr("beam_requests")
        try:
            row = np.asarray(logits_row, dtype=np.float32).reshape(-1)
            if st.grammar is not None:
                row = row + st.grammar.mask()
            self._commit_beam_selection(group, [row], time.perf_counter())
        except ArenaInvalidError:
            raise               # admission's arena handler owns cleanup
        except Exception as e:
            self._reject_beam_group(group, RequestError(
                f"request {req.id} failed in first beam selection: {e}"))

    def _claim_spares(self, group):
        """The rest of the group's width reservation, best-effort: forks
        need spares, and admission must not take them first."""
        while len(group.order) + len(group.spare) < group.width:
            sid = self._pool.acquire()
            if sid is None:
                break
            group.spare.append(sid)

    def _commit_beam_selection(self, group, rows, now):
        """ONE beam step's bookkeeping: run the committed selection rule
        over the live hypotheses' (masked) logits rows, divert EOS and
        length-exhausted continuations to ``finished``, release pruned
        parents, keep each parent's top continuation in its slot, fork
        the rest (refcount++ + private tail copy), and re-assert block
        row conservation. Returns False when the group retired or
        failed (its slots are gone). ``now`` stamps the selection: token
        ``j`` of EVERY hypothesis is chosen by the group's ``j``-th
        selection."""
        m = self._model
        req = group.request
        req.response.token_times.append(now)
        live_ids = list(group.order)
        live = [self._slots[s] for s in live_ids]
        room = group.width - len(group.finished)
        sel_live, sel_fin = beam_select(
            [b.score for b in live], rows, room, m.eos_id)
        for p, t, sc in sel_fin:
            group.finished.append((live[p].generated + [t], sc))
        survivors = []
        for p, t, sc in sel_live:
            n2 = len(live[p].generated) + 1
            if n2 >= req.max_new or live[p].plen + n2 >= m.max_len:
                group.finished.append((live[p].generated + [t], sc))
            else:
                survivors.append((p, t, sc))
        keep = {p for p, _t, _s in survivors}
        for i, sid in enumerate(live_ids):
            if i not in keep:
                self._release_beam_slot(sid, to_spare=True)
                self._metrics.incr("beam_prunes")
        # slot assignment preserves RANK order in group.order; children
        # fork BEFORE their parent's in-place update (deferred) so every
        # fork sees the parent's pre-step tokens/grammar/score
        new_order = []
        taken = set()
        deferred = []
        for p, t, sc in survivors:
            if p not in taken:
                taken.add(p)
                new_order.append(live_ids[p])
                deferred.append((live[p], t, sc))
            else:
                try:
                    child = self._fork_beam(group, live[p], t, sc)
                except ArenaInvalidError:
                    raise
                except Exception as e:
                    self._reject_beam_group(group, RequestError(
                        f"request {req.id} beam fork failed: {e}"))
                    return False
                new_order.append(child)
                self._metrics.incr("beam_forks")
        for st, t, sc in deferred:
            st.generated = st.generated + [t]
            st.last_token = t
            st.score = sc
            if st.grammar is not None:
                st.grammar.advance(t)
        group.order = new_order
        self.kv.pool.check_conservation()
        if len(group.finished) >= group.width or not new_order:
            self._retire_beam(group)
            return False
        return True

    def _fork_beam(self, group, parent, token, score):
        """COW-fork one live hypothesis: a fresh slot carrying the forked
        host state, over a second owner of the parent's rows
        (`KVStore.fork`)."""
        slot = group.spare.pop() if group.spare else self._pool.acquire()
        if slot is None:
            raise RuntimeError("slot pool exhausted forking a beam")
        st = _Slot(group.request, mode="beam")
        try:
            st.kv = self.kv.fork(parent.kv, parent.cursor)
        except Exception:
            group.spare.append(slot)    # the group's, till it is rejected
            raise
        st.beam = group
        st.plen = parent.plen
        st.cursor = parent.cursor
        st.last_token = int(token)
        st.generated = parent.generated + [int(token)]
        st.score = score
        if parent.grammar is not None:
            st.grammar = parent.grammar.fork().advance(token)
        self._slots[slot] = st
        return slot

    def _release_beam_slot(self, sid, to_spare=False):
        st = self._slots[sid]
        self._slots[sid] = None
        if to_spare and st is not None and st.beam is not None:
            st.beam.spare.append(sid)   # keep the group's reservation
        else:
            self._pool.release(sid)
        self.kv.release(st.kv)

    def _release_group_slots(self, group):
        for sid, st in enumerate(self._slots):
            if st is not None and st.beam is group:
                self._release_beam_slot(sid)
        for sid in group.spare:
            self._pool.release(sid)
        group.spare = []

    def _retire_beam(self, group):
        self._release_group_slots(group)
        req = group.request
        ranked = beam_finished_ranking(group.finished)
        if not ranked:
            self._reject_in_flight(req, RequestError(
                f"request {req.id}: beam search finished no hypothesis"))
            return
        # the best hypothesis may have finished selections ago: its
        # tokens' stamps are the first len(tokens) selections'
        del req.response.token_times[len(ranked[0][0]):]
        self._metrics.incr("beam_finished", len(ranked))
        self._complete(req, {
            "tokens": np.asarray(ranked[0][0], dtype="int64"),
            "beams": [{"tokens": np.asarray(t, dtype="int64"),
                       "score": float(sc)} for t, sc in ranked],
        })

    def _reject_beam_group(self, group, error):
        """Fail one beam request as a UNIT: release every slot the group
        still holds, then complete its single response once. (The
        arena-failure path may already have completed it through the
        admitting request's handler — the done() guard keeps the
        write-once future honest.)"""
        self._release_group_slots(group)
        if not group.request.response.done():
            self._reject_in_flight(group.request, error)

    def _tokens_suffice(self, active, groups):
        """Whether this step's host half needs nothing but the
        device-chosen tokens: every stepping slot is greedy and its
        grammar mask, if it has one, was added on the device. A beam
        group ranks whole rows, a sampler draws from one and a
        host-applied grammar masks one, so any of them in the step
        brings the whole ``[S, 1, V]`` logits over, as does a model with
        no ``token_fetch``. Decided per step from the slots themselves:
        no option selects it."""
        m = self._model
        if m.token_fetch is None or groups:
            return False
        for s in active:
            st = self._slots[s]
            if st.sampling is not None and not st.sampling.greedy:
                return False
            if st.grammar is not None and not m.logits_mask:
                return False
        return True

    def _rides_ahead(self, st):
        """Whether a stepping slot needs nothing of a step but its
        token, so that the step may stay in flight: greedy, no grammar
        (its next mask follows from the token's VALUE), no beam
        hypothesis. `_tokens_suffice` asks the same of a built step."""
        return (st.mode == "decode" and st.grammar is None
                and (st.sampling is None or st.sampling.greedy))

    def _step(self):
        """One decode iteration: feeds and launch, then the ONE fetch of
        a step and its host half, in one of `_iterate`'s two orders, then
        the logits row of a prompt's last chunk that was launched before
        it (`_land_chunks`: the device ran that chunk BEFORE the step just
        launched, so the fetch finds it ready or nearly, and the slot it
        starts steps with the NEXT launch, its token from the host).

        The cursor half of the host's work (`_advance_cursors`) runs as
        soon as the launch has returned. A step that `_tokens_suffice`
        and that steps no grammar is then left IN FLIGHT; if one was in
        flight already, it is fetched and delivered (`_land`) only now,
        under the step just launched, whose token feed is its ``[S, 1]``
        output without leaving the device. Any other step lands in this
        same body, as every step once did. A step in flight that this one
        cannot follow (`_step_feeds` names why), or that nothing follows,
        is drained first. Nothing is sliced out of a device array: that
        would dispatch, and could compile, inside a serving window."""
        built = self._traced_feeds()
        if isinstance(built, str):
            self._drain(built)
            built = self._traced_feeds()
        prev = self._launched
        if built is None:
            if prev is not None:
                self._drain("idle")
            else:
                self._land_chunks(deferred=False)
            return
        feeds, active, groups = built
        states = [self._slots[s] for s in active]
        blocks = ([(st.cursor, st.bpass, st.bpass == st.fills)
                   for st in states] if self._model.fills_blocks else None)
        t0 = time.perf_counter()
        launch = None
        try:
            with profiler.RecordEvent("decode::step") as ev:
                faults.fire("decode.step")
                fetches = self._run("step", feeds, ev.span)
                if ev.span is not None:
                    launch = self._metrics.count("step_launches")
                    ev.span.set(ahead=prev is not None, launch=launch,
                                passes=self._model.passes,
                                **self._block_attrs(blocks))
        except Exception as e:
            # a failed donated call leaves the arena undefined: every
            # in-flight sequence is lost (failed loudly; with a step in
            # flight, the slots of both, and that step is not delivered),
            # the batch-level outcome drives the breaker, and the arena
            # resets
            self._arena_lost(f"decode-step failure: {e}")
            return
        if self._breaker is not None:
            self._breaker_event(self._breaker.record_success())
        step = _LaunchedStep(fetches, active, states, groups,
                             self._tokens_suffice(active, groups), launch,
                             blocks)
        self._advance_cursors(step.states)
        if prev is not None:
            self._metrics.incr("decode_steps_ahead")
            self._launched = step
            self._land(prev, t0)
        elif step.tokens_only and all(map(self._rides_ahead, step.states)):
            step.host_s = time.perf_counter() - t0
            self._launched = step
        else:
            # a grammar's next mask follows from the token's VALUE, even
            # where the device adds it: such a step lands here too
            self._land(step, t0)
        self._land_chunks(deferred=True)

    def _traced_feeds(self):
        with _span("decode::feeds") as sp:
            built = self._step_feeds()
            if sp is not None and isinstance(built, tuple):
                sp.set(active=len(built[1]), beam_groups=len(built[2]))
        return built

    def _advance_cursors(self, states):
        """The half of a step's host work that follows from the cursor
        alone, done once the step is launched: the K/V append committed
        and the cursor moved, so the next `_step_feeds` builds from
        positions that count this step whether or not its tokens have
        been read. Who steps again follows (`_steps_again`). A beam
        group's cursors move with its selection, in `_sample`. A slot that
        fills blocks counts the pass; its cursor and its appended rows
        move by a block when the pass launched was the block's commit."""
        note_append = self.kv.note_append
        B = self._model.block_len
        for st in states:
            st.ahead += 1
            if B == 1:
                note_append(st.kv, st.cursor)
                st.cursor += 1
                continue
            st.bpass += 1
            if st.bpass > st.fills:
                for p in range(st.cursor, st.cursor + B):
                    note_append(st.kv, p)
                st.cursor += B
                st.fills, st.bpass = B, 0

    def _block_attrs(self, blocks):
        """What a launch or fetch span says of a block pass: the block's
        length, the slots whose pass is a commit and those that decide a
        token; nothing for a model that steps a token a slot."""
        if blocks is None:
            return {}
        commit = sum(1 for _p0, _k, last in blocks if last)
        return {"block_len": self._model.block_len, "commit": commit,
                "decided": len(blocks) - commit}

    def _drain(self, why):
        """Fetch and deliver the step in flight with nothing launched
        over it, then every last chunk's row: the host waits for the
        device here, ``decode::step_fetch`` says why (``drain=``), and
        ``serving_decode_drains_total{why=}`` counts it, tracing on or
        off."""
        step, self._launched = self._launched, None
        self._metrics.count_drain(why)
        self._land(step, time.perf_counter(), drain=why)
        self._land_chunks(deferred=False)

    def _land(self, step, t0, drain=None):
        """The ONE fetch of a launched step and the half of the host's
        work that needs the tokens' values (`_sample`). Observes the
        step in ``serving_decode_step_seconds``: the wall time since
        ``t0`` (the `_step` body, or the drain) plus what the step's
        launch cost in a body that delivered nothing."""
        m = self._model
        with _span("decode::step_fetch") as sp:
            tokens = counts = None
            if m.counts_fetch is not None:
                # the S tokens (a block pass: each slot's decided position,
                # then its token) and the step's counts in ONE vector
                both = self._fetch(step.fetches[2])
                if m.fills_blocks:
                    tokens, counts = both[:2 * m.slots], both[2 * m.slots:]
                else:
                    tokens, counts = (both[:m.slots].reshape(m.slots, 1),
                                      both[m.slots:])
            if not step.tokens_only:
                fetched = self._fetch(step.fetches[0])       # [S, 1, V]
                self._metrics.incr("decode_logits_fetch_steps")
            elif tokens is None:
                fetched = self._fetch(step.fetches[1])       # [S, 1] int
            else:
                fetched = tokens
            if sp is not None:
                sp.set(bytes=fetched.nbytes,
                       rows="tokens" if step.tokens_only else "logits")
                if drain is not None:
                    sp.set(drain=drain)
                if step.launch is not None:
                    sp.set(launch=step.launch)
                sp.set(**self._block_attrs(step.blocks))
        # what the device did in this step, wasted slots included
        if counts is not None:
            for name, n in zip(m.count_names, counts):
                self._metrics.incr(name, int(n))
        # a slot retired or rejected since the launch (an ``eos_id``, a
        # deadline: seen only when the step before this one landed) was
        # stepped for nothing; its token is dropped
        active = [s for s, st in zip(step.active, step.states)
                  if self._slots[s] is st]
        now = time.perf_counter()
        with _span("decode::sample") as sp:
            if m.fills_blocks:
                stepped, new = self._sample_blocks(fetched, step, now)
            else:
                stepped = new = self._sample(fetched, active, step.groups,
                                             now, step.tokens_only)
            if sp is not None:
                sp.set(tokens=new)
        if stepped is not None:
            self._metrics.observe_step(
                stepped, new, time.perf_counter() - t0 + step.host_s)

    def _step_feeds(self):
        """The decode step's feeds from the live slots: ``(feeds, active
        slot ids, beam groups with a live slot)``, or None when there is
        nothing to step (or the arena was lost making a cursor
        writable). ONE host array, ``dec_step`` (`DecodeModel.step_feed`
        / `fill_step`): a stepping slot's token, cursor, length, write
        row and block table; the step program makes the bias and the row
        map of them on the device. ``dec_token`` is a device array either
        way: zeros that nobody reads, or with a step in flight (the
        cursors count it already, a slot it finishes is left out) that
        step's ``[S, 1]`` output itself, every stepping slot's token -1;
        rows of slots that do not step are ignored as ever. A slot that
        was NOT in that step (new since, ``ahead`` 0) is fed its token
        from the host in the same array. That holds only if such a slot
        needs nothing but its token (`_rides_ahead`) and no slot has to be
        parked; else nothing is built and the reason to drain comes back
        as a string: ``"slots"`` for a new slot that samples, masks a
        grammar or is a beam hypothesis, ``"park"``. What the loop has
        done before is done again unchanged after the drain: a block
        opened stays opened."""
        m = self._model
        S = m.slots
        step = m.step_feed()
        dmask = (np.zeros((S, 1, m.vocab_size), "float32")
                 if m.logits_mask else None)
        active = []
        groups = []     # beam groups with a live slot this step
        live_blocks = copy_units = copy_blocks = run_blocks = 0
        launched = self._launched
        self._window_step()
        for s in range(S):
            st = self._slots[s]
            if st is None or st.mode not in ("decode", "beam"):
                continue
            if launched is not None:
                if not st.ahead:
                    # new since that launch: its last token is on the host
                    if not self._rides_ahead(st):
                        return "slots"
                elif not self._steps_again(st):
                    continue
            # make the cursor position writable
            try:
                opened = self.kv.open_block(st.kv, st.cursor)
            except ArenaInvalidError as e:
                # the COW re-inject is a DONATED call: its failure
                # invalidates the whole arena, not one request
                self._arena_lost(f"copy-on-write inject failure: {e}")
                return None
            except RuntimeError as e:
                # pool invariant violation: loud per-request failure,
                # never a dead scheduler thread
                if st.mode == "beam":
                    self._reject_beam_group(st.beam, RequestError(
                        f"request {st.request.id} failed: {e}"))
                else:
                    self._reject_in_flight(st.request, RequestError(
                        f"request {st.request.id} failed: {e}"), slot=s)
                continue
            if not opened:
                # mid-generation exhaustion: park the session (spill to
                # the host tier, resume byte-identically later) instead
                # of failing; loud only when the host tier cannot absorb
                # it or the session can never be resumed
                if launched is not None:
                    return "park"   # spill what the session has produced
                self._metrics.incr("blocks_exhausted")
                parked = (self._park_group(st.beam) if st.mode == "beam"
                          else self._park_slot(s))
                if parked:
                    self._metrics.incr("blocks_parked_total")
                    continue
                self._metrics.incr("blocks_failed_total")
                err = RequestError(
                    f"request {st.request.id} failed: block pool "
                    "exhausted mid-generation and the host KV tier "
                    "cannot absorb the session")
                if st.mode == "beam":
                    self._reject_beam_group(st.beam, err)
                else:
                    self._reject_in_flight(st.request, err, slot=s)
                continue
            if st.mode == "beam":
                if st.beam not in groups:
                    groups.append(st.beam)
            else:
                active.append(s)
            if m.fills_blocks:
                # the block from the device where a pass of it is in
                # flight, else as the host knows it
                m.fill_block(step, s, st.cursor, st.kv.table,
                             st.kv.row_of(st.cursor),
                             None if st.ahead else st.block)
            else:
                m.fill_step(step, s, st.cursor, st.kv.groups,
                            -1 if st.ahead else st.last_token)
                for g in self.kv.windowed:
                    self._metrics.observe_window_rows(st.cursor + 1,
                                                      st.kv.groups[g])
                if m.index_names:
                    self._metrics.observe_sparse_step(
                        st.cursor + 1, m.index_topk, len(m.index_names))
            reads = (st.cursor + m.block_len - 1) // m.block_size + 1
            live_blocks += reads
            if self.kv.copy_unit:
                copy_units += -(-reads // self.kv.copy_unit)
                copy_blocks += reads
                run_blocks += st.kv.run_blocks(reads)
            if dmask is not None and st.grammar is not None:
                # the grammar's next-token constraint rides in as DATA —
                # same compiled program for every request, zero retraces
                dmask[s, 0] = st.grammar.mask()
        if not active and not groups:
            return None
        self._metrics.observe_blocks(live_blocks, S * m.blocks_per_slot,
                                     copy_units, copy_blocks, run_blocks)
        feeds = {DecodeModel.DEC_STEP: step,
                 DecodeModel.DEC_TOKEN: (self._no_tokens if launched is None
                                         else launched.fetches[1])}
        if dmask is not None:
            feeds[DecodeModel.DEC_MASK] = dmask
        return feeds, active, groups

    def _window_step(self):
        """Before a step's feeds are built: in every group that has a
        window, every decoding slot gives back what lies behind the window
        of its NEXT token (its cursor; a step in flight stands before it
        and was launched already), under ONE span a step and group, and
        the group's pool is observed."""
        for g in self.kv.windowed:
            with _span("decode::window_release") as sp:
                given = sum(self.kv.release_behind(st.kv, st.cursor, g)
                            for st in self._slots
                            if st is not None and st.mode == "decode")
                if sp is not None:
                    sp.set(blocks=given, chunk=False)
            self._metrics.observe_pools(self.kv.pools, g, given)

    def _sample(self, fetched, active, groups, now, tokens_only):
        """The half of a decode step's host work that needs what it
        fetched (`_advance_cursors` did the other at launch): each
        active slot's token, stamped ``now`` — when the host HAS it,
        however long ago the device chose it — then the slot retired or
        expired; then one selection per beam group.
        ``fetched`` is the ``[S, 1, V]`` logits, from which
        `_choose_token` takes every token, or with ``tokens_only`` the
        ``[S, 1]`` tokens the step program chose itself (no beam group
        steps then): a slot takes its integer, and a device-masked
        grammar still advances on it. Returns the slot-steps done, or
        None when a beam fork lost the arena."""
        m = self._model
        stepped = len(active)
        for s in active:
            st = self._slots[s]
            st.ahead -= 1
            if tokens_only:
                nxt = int(fetched[s, 0])
                if st.grammar is not None:
                    st.grammar.advance(nxt)
                    self._metrics.incr("grammar_steps")
            else:
                nxt = self._choose_token(st, fetched[s, 0],
                                         device_masked=m.logits_mask)
            st.generated.append(nxt)
            st.last_token = nxt
            st.request.response.token_times.append(now)
            self._metrics.tenant_incr("tokens", st.request.tenant)
            # finished wins over expired: the device already paid for a
            # COMPLETE generation, deliver it (the prefill fast path
            # retires without an expiry check — same policy)
            if self._finished(st):
                self._retire(s)
            elif st.request.expired(now):
                self._reject_in_flight(st.request, DeadlineExceededError(
                    "deadline expired mid-generation after "
                    f"{len(st.generated)} tokens"), slot=s)
        for group in groups:
            if group.request.response.done():
                continue    # rejected while another slot was being fed
            if not group.order:
                continue    # parked while another slot was being fed
            # commit this step's KV append per live hypothesis, collect
            # its (device-masked) logits row in HYPOTHESIS order, then
            # run the shared selection rule once for the whole group
            rows_l = []
            for sid in group.order:
                bst = self._slots[sid]
                self.kv.note_append(bst.kv, bst.cursor)
                bst.cursor += 1
                row = np.asarray(fetched[sid, 0],
                                 dtype=np.float32).reshape(-1)
                if bst.grammar is not None and not m.logits_mask:
                    row = row + bst.grammar.mask()
                rows_l.append(row)
            stepped += len(rows_l)
            try:
                alive = self._commit_beam_selection(group, rows_l, now)
            except ArenaInvalidError as e:
                self._arena_lost(f"beam fork inject failure: {e}")
                return None
            if alive and group.request.expired(now):
                self._reject_beam_group(group, DeadlineExceededError(
                    "deadline expired mid-generation after "
                    f"{len(group.finished)} finished hypotheses"))
        return stepped

    def _sample_blocks(self, fetched, step, now):
        """`_sample` for a delivered BLOCK PASS: ``fetched`` is each
        slot's decided position in its block (-1: the pass was the block's
        commit) and then each slot's token. A commit opens the next block
        on the host's copy. A fill puts the token where it was decided and
        the answer then grows in POSITION order: a token decided ahead of
        an earlier position waits in ``pending`` (with the time this pass
        was delivered, which is the stamp it gets) until every position
        before it is decided, so a pass hands the stream 0 to
        ``block_len`` tokens. Returns ``(slot passes delivered, tokens
        decided)``."""
        m = self._model
        S, B = m.slots, m.block_len
        where, token = fetched[:S], fetched[S:]
        stepped = decided = 0
        for s, st, (p0, k, commit) in zip(step.active, step.states,
                                          step.blocks):
            if self._slots[s] is not st:
                continue    # retired or rejected since the launch
            stepped += 1
            st.ahead -= 1
            req = st.request
            self._metrics.count_block_pass("commit" if commit else "fill")
            if commit:
                st.block = [-1] * B
                self._metrics.incr("blocks_committed")
            else:
                at = int(where[s])
                st.block[at] = int(token[s])
                st.pending[p0 + at - len(req.prompt)] = (
                    int(token[s]), now, k)
                decided += 1
                while (len(st.generated) in st.pending
                       and not self._finished(st)):
                    tok, stamp, at_pass = st.pending.pop(len(st.generated))
                    st.generated.append(tok)
                    st.last_token = tok
                    st.decided_at.append(at_pass)
                    req.response.token_times.append(stamp)
                    self._metrics.tenant_incr("tokens", req.tenant)
            # finished wins over expired, as in `_sample`
            if self._finished(st):
                self._retire(s)
            elif req.expired(now):
                self._reject_in_flight(req, DeadlineExceededError(
                    "deadline expired mid-generation after "
                    f"{len(st.generated)} tokens"), slot=s)
        self._metrics.incr("block_tokens_decided", decided)
        return stepped, decided

    def _finished(self, st):
        m = self._model
        # the cursor as of the slot's last token on the host: a step
        # launched over it has moved the cursor once more
        return (len(st.generated) >= st.request.max_new
                or (m.eos_id is not None and st.last_token == m.eos_id)
                or st.cursor - st.ahead >= m.max_len)

    def _retire(self, slot):
        st = self._vacate(slot)
        outputs = {"tokens": np.asarray(st.generated, dtype="int64")}
        if st.decided_at is not None:
            outputs["decided_at"] = np.asarray(st.decided_at, dtype="int64")
        self._complete(st.request, outputs)

    def _complete(self, req, outputs):
        self._engine._tenant_unflight(req.tenant)
        req.response._complete(outputs=outputs)
        self._metrics.incr("completed")
        self._metrics.incr("retired")
        self._metrics.tenant_incr("completed", req.tenant)
        self._metrics.observe_request(req)
        self._metrics.observe_tokens(req)

    def _reject_in_flight(self, req, error, slot=None):
        if slot is not None:
            self._vacate(slot)
        self._engine._tenant_unflight(req.tenant)
        self._metrics.incr(
            "deadline_missed" if isinstance(error, DeadlineExceededError)
            else "failed")
        req.response._complete(error=error)
        self._metrics.observe_request(req)

    # -- reference path ----------------------------------------------------
    def offline_decode(self, prompt, max_new, sampling=None, grammar=None):
        """Offline whole-sequence reference: re-run the full causal
        prefill forward per generated token (no KV cache, no slots) with
        identical finish rules and the SAME committed selection policy
        (host-masked grammar + committed-stream sampling). The
        bit-exactness tests compare continuous output — in EVERY mode
        (paged decode, chunked prefill, speculative, sampled,
        constrained) — against THIS."""
        m = self._model
        if m.prefill_program is None:
            raise RuntimeError(
                f"model {m.label} has no stateless prefill program to "
                "re-run (per-slot recurrent state): its reference is a "
                "plain forward pass outside the engine")
        toks = list(prompt)
        out = []
        g = GrammarConstraint(grammar) if grammar is not None else None
        for _ in range(int(max_new)):
            row = self.prefill_logits(toks)[len(toks) - 1].astype(np.float32)
            if g is not None:
                row = row + g.mask()
            if sampling is not None and not sampling.greedy:
                nxt = sample_token(row, sampling, len(out))
            else:
                nxt = int(np.argmax(row))
            if g is not None:
                g.advance(nxt)
            out.append(nxt)
            toks.append(nxt)
            if m.eos_id is not None and nxt == m.eos_id:
                break
            if len(toks) >= m.max_len:
                break
        return out

    def offline_beam(self, prompt, max_new, params, grammar=None):
        """Offline beam reference: ``generate.offline_beam_decode`` with
        this entry's prefill forward as the whole-sequence logits
        oracle. The engine's slot-based incremental beam must give these
        hypotheses token for token
        (tests/test_generate.py::test_beam_matches_offline_reference_and_conserves_blocks)."""
        m = self._model

        def logits_fn(tokens):
            return self.prefill_logits(tokens)[len(tokens) - 1]

        g = GrammarConstraint(grammar) if grammar is not None else None
        return offline_beam_decode(logits_fn, prompt, int(max_new), params,
                                   m.eos_id, m.max_len, grammar=g)

    # -- observability ----------------------------------------------------
    def stats(self):
        m = self._model
        spec_t = self._metrics.count("spec_target_steps")
        spec_e = self._metrics.count("spec_emitted_tokens")
        spec_p = self._metrics.count("spec_proposed_tokens")
        return self._metrics.snapshot(extra={
            **self._metrics.queue_snapshot(self._queue),
            "model": m.name, "version": m.version,
            "slots": m.slots, "max_len": m.max_len,
            "block_size": m.block_size, "num_blocks": m.num_blocks,
            "active_slots": self._pool.active_count,
            "occupancy": self._metrics.occupancy(m.slots),
            "tokens_per_step": self._metrics.tokens_per_step(),
            "arena_mib": m.arena_bytes() / 2**20,
            "slotted_equivalent_mib":
                m.slotted_equivalent_bytes() / 2**20,
            **self.kv.stats(),
            "spec_steps_per_token": (spec_t / spec_e) if spec_e else None,
            "spec_acceptance_rate": (
                self._metrics.count("spec_accepted_tokens") / spec_p
                if spec_p else None),
            "spec_draft_kv_steps_per_token": (
                self._metrics.count("spec_draft_kv_steps") / spec_e
                if spec_e else None),
            "draft_pinned": self._draft_pinned,
            "compile_sources": dict(self.compile_sources),
            "breaker_state": (self._breaker.state if self._breaker
                              else None),
            "tenant_tokens": self._metrics.tenant_counts("tokens"),
            "tenant_completed": self._metrics.tenant_counts("completed"),
            "brownout_severity": self._brownout.level,
            "brownout": self._brownout.snapshot(),
            "parked_sessions": len(self._parked),
            "pending_admissions": len(self._pending),
        })

    @property
    def metrics(self):
        return self._metrics

    @property
    def model(self):
        return self._model

    @property
    def prefix_cache(self):
        return self.kv.prefix

    @property
    def block_pool(self):
        return self.kv.pool


class GenerationEngine:
    """Multi-tenant front door over N hosted decode models."""

    _SEQ = 0

    def __init__(self, place=None, queue_depth=256, breaker_threshold=3,
                 breaker_cooldown_s=1.0, prefix_cache_size=64,
                 hbm_budget_mb=None, host_tier_mb=64, label=None):
        import paddle_tpu as fluid

        if place is None:
            place = fluid.TPUPlace(0)
        self.place = place
        self.device = place.jax_device()
        GenerationEngine._SEQ += 1
        self.label = label or f"genengine-{GenerationEngine._SEQ}"
        self._queue_depth = int(queue_depth)
        self._breaker_threshold = breaker_threshold
        self._breaker_cooldown_s = breaker_cooldown_s
        self._prefix_cache_size = prefix_cache_size
        self._hbm_budget_mb = hbm_budget_mb
        # per-entry host-RAM KV tier budget (spill/write-back target)
        self._host_tier_bytes = int(host_tier_mb) << 20
        self._entries = {}        # (name, version) -> _ModelEntry
        self._latest = {}         # name -> version (last registered)
        self._reg_order = []      # keys in registration order (latest wins)
        self._tenants = {}        # tenant -> _TenantState
        self._tenant_lock = lockdep.named_lock("decode.tenant")
        self._vclock = 0.0        # engine-wide virtual time (last dispatch)
        self._started = False
        self._next_id = 0
        self._id_lock = lockdep.named_lock("decode.ids")
        # the device's lane: every entry's traced launches, in launch
        # order, to one ready watcher (its thread starts with the first)
        self.lane = DeviceLane(self.device)

    # -- model registry ---------------------------------------------------
    def register_model(self, model):
        """Host one (model, version). Sizes the paged arena against the
        HBM budget BEFORE any compile, then builds + warms the entry
        (from the compile cache when one is populated). Returns the
        entry."""
        if not isinstance(model, DecodeModel):
            model = model()        # zero-arg builder
        if model.key in self._entries:
            raise ValueError(f"model {model.label} already registered")
        KVStore.check_carries(model, self._host_tier_bytes,
                              self._prefix_cache_size)
        self._check_hbm(model)
        entry = _ModelEntry(
            self, model, self._queue_depth, self._breaker_threshold,
            self._breaker_cooldown_s, self._prefix_cache_size,
        ).build()
        self._entries[model.key] = entry
        self._latest[model.name] = model.version
        self._reg_order.append(model.key)
        if self._started:
            entry.start()
        return entry

    def unregister_model(self, name, version, timeout=60.0):
        """Retire one hosted (model, version): graceful DRAIN-BEFORE-
        RETIRE — admission to the entry closes, queued and in-flight
        generations finish, THEN the entry leaves the registry. The
        rolling-deploy path calls this for the old version once the new
        one serves; `latest` falls back to the newest still-hosted
        version of the name (registration order)."""
        key = (str(name), str(version))
        entry = self._entries.get(key)
        if entry is None:
            raise ValueError(
                f"no model {name}@{version} to unregister; hosted: "
                f"{['@'.join(k) for k in sorted(self._entries)]}")
        entry.shutdown(timeout)
        del self._entries[key]
        self._reg_order.remove(key)
        remaining = [v for n, v in self._reg_order if n == key[0]]
        if remaining:
            self._latest[key[0]] = remaining[-1]
        else:
            self._latest.pop(key[0], None)
        return entry

    def reroute_queued(self, name=None, version=None):
        """Pull every QUEUED (not yet prefilled) request off one entry's
        admission queue for re-dispatch elsewhere — the fleet router's
        drain accelerator: instead of waiting for a retiring/deploying
        replica to chew through its backlog, the backlog moves to
        healthy replicas with its original deadlines intact. In-flight
        slots are untouched (they finish here). Returns the removed
        GenerationRequests; their responses never complete — the caller
        owns re-dispatching them."""
        entry = self._resolve(name, version)
        with entry._cond:
            reqs = [r for r in entry._queue.iter_requests()]
            entry._queue.reroute(reqs)
        for r in reqs:
            self._tenant_unqueue(r.tenant)
        return reqs

    def _check_hbm(self, model):
        """Static pre-compile gate: decode-step peak HBM (the paged
        arena is persistable state, so it dominates) must fit the
        budget."""
        if not self._hbm_budget_mb:
            return
        from paddle_tpu.analysis.memory import (
            check_hbm_budget,
            estimate_peak_hbm,
        )
        from paddle_tpu.utils.enforce import EnforceError

        report = estimate_peak_hbm(
            model.decode_program,
            feed_shapes={n: s for n, s, _d in model.decode_feed_sig()},
            fetch_names=[model.logits_fetch],
        )
        diags = check_hbm_budget(
            report, self._hbm_budget_mb * 2**20, label=model.label)
        if diags:
            raise EnforceError(
                "KV arena does not fit the HBM budget:\n  "
                + "\n  ".join(d.message for d in diags))

    def models(self):
        return sorted(self._entries)

    def entry(self, name=None, version=None):
        return self._resolve(name, version)

    def _resolve(self, name, version):
        if name is None:
            if len(self._entries) != 1:
                raise RejectedError(
                    f"engine hosts {len(self._entries)} models; submit "
                    "must name one")
            return next(iter(self._entries.values()))
        name = str(name)
        if version is None:
            version = self._latest.get(name)
        entry = self._entries.get((name, str(version)))
        if entry is None:
            raise RejectedError(
                f"no model {name}@{version}; hosted: "
                f"{['@'.join(k) for k in sorted(self._entries)]}")
        return entry

    # -- tenancy ----------------------------------------------------------
    def set_tenant(self, tenant, weight=1.0, max_in_flight=None,
                   max_queued=None):
        """Configure one tenant: scheduling weight (stride share under
        contention) and admission quotas. Unknown tenants default to
        weight 1.0, no quotas."""
        if weight <= 0:
            raise ValueError(f"tenant weight must be > 0, got {weight}")
        with self._tenant_lock:
            st = self._tenants.get(str(tenant))
            if st is None:
                self._tenants[str(tenant)] = _TenantState(
                    weight, max_in_flight, max_queued)
            else:
                st.weight = float(weight)
                st.max_in_flight = max_in_flight
                st.max_queued = max_queued

    def _tenant(self, tenant):
        st = self._tenants.get(tenant)
        if st is None:
            st = _TenantState()
            self._tenants[tenant] = st
        return st

    def _tenant_unqueue(self, tenant):
        with self._tenant_lock:
            st = self._tenant(tenant)
            st.queued = max(st.queued - 1, 0)

    def _tenant_unflight(self, tenant):
        with self._tenant_lock:
            st = self._tenant(tenant)
            st.in_flight = max(st.in_flight - 1, 0)

    def _pick(self, queue, max_rows=None, lanes=None, fits=None):
        """Weighted-fair pick (caller holds queue.lock): first non-empty
        priority lane wins (strict priority), then the lane's queued
        tenant with the smallest virtual time, skipping tenants at their
        in-flight cap. The winner's FIRST queued request dispatches
        (per-tenant FIFO) and the tenant pays 1/weight virtual time.
        ``max_rows`` is the admission round's remaining slot budget: a
        tenant whose head request needs more rows (a beam) is skipped
        for the round — head-of-line within the tenant is deliberate,
        per-tenant FIFO is the ordering contract. ``lanes`` restricts the
        eligible priority lanes (brownout L3 zeroes the LOW-lane
        dispatch quota this way — queued LOW waits, it is not lost).
        ``fits(request)`` is the entry's own say on a tenant's head
        request (its block pool cannot cover it yet): skipped for the
        round like one that needs more rows."""
        with self._tenant_lock:
            for lane in (lanes if lanes is not None else Priority.LANES):
                requests = queue.lane(lane)
                if not requests:
                    continue
                best = None
                candidates = {}
                for r in requests:
                    if r.tenant in candidates:
                        continue
                    st = self._tenant(r.tenant)
                    if (st.max_in_flight is not None
                            and st.in_flight >= st.max_in_flight):
                        continue
                    if (max_rows is not None and r.rows > max_rows
                            or fits is not None and not fits(r)):
                        # not enough free slots (or blocks) THIS round for
                        # the tenant's head request; its turn comes back
                        candidates[r.tenant] = None
                        continue
                    candidates[r.tenant] = (st, r)
                candidates = {t: c for t, c in candidates.items()
                              if c is not None}
                if not candidates:
                    continue  # every queued tenant here is capped
                for tenant, (st, r) in candidates.items():
                    if best is None or st.vtime < best[0].vtime:
                        best = (st, r)
                st, req = best
                # catch-up: a long-idle tenant wins its first contested
                # pick (it IS behind) but then re-enters at the engine's
                # virtual clock instead of burning banked lag into a
                # starvation burst
                base = max(st.vtime, self._vclock)
                st.vtime = base + 1.0 / st.weight
                self._vclock = base
                # in-flight is RESERVED at pick time: a multi-slot
                # admission round calls _pick repeatedly before any
                # prefill runs, so charging later would let one round
                # blow through max_in_flight
                st.in_flight += 1
                queue.remove([req], batch=True)
                return req
        return None

    # -- lifecycle --------------------------------------------------------
    def start(self):
        if self._started:
            return self
        self._started = True
        for entry in self._entries.values():
            entry.start()
        return self

    def shutdown(self, timeout=60.0):
        """Graceful drain: stop admitting; queued + in-flight sequences
        finish generating before the loops exit."""
        for entry in self._entries.values():
            entry.shutdown(timeout)
        self.lane.close(timeout)
        self._started = False

    drain = shutdown

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.shutdown()
        return False

    # -- admission --------------------------------------------------------
    def submit(self, prompt_ids, model=None, version=None, tenant="default",
               priority=Priority.NORMAL, max_new_tokens=16,
               deadline_ms=None, deadline_at=None, draft_model=None,
               draft_version=None, spec_k=4, sampling=None,
               beam_width=None, grammar=None, draft_kv=True):
        """Admit one generation request; returns its Response future
        (``result()`` -> ``{"tokens": int64 array}``, and ``"decided_at"``
        for a model that fills blocks). Raises structured
        RejectedError on invalid prompts, over-quota tenants, or a full
        queue (with a measured retry-after). ``deadline_at`` is an
        ABSOLUTE ``time.perf_counter()`` deadline (it wins over
        ``deadline_ms``): a re-dispatched request carries its ORIGINAL
        deadline through the retry instead of being granted a fresh
        budget — the fleet router's at-most-once-visible failover
        depends on this. ``draft_model`` (+ optional ``draft_version``)
        opts into speculative decoding: the draft must be a hosted
        registry entry sharing the target's vocabulary; committed-
        coupling acceptance keeps the output bit-identical to
        non-speculative decode (greedy acceptance is its temperature-0
        case). ``draft_kv`` (default on) gives the proposals their own
        KV slot on the draft entry — O(1) draft work per token — when
        the draft entry can be PINNED (no primary traffic); otherwise
        the request silently uses replay proposals.

        Generation modes (r17): ``sampling`` — a SamplingParams (or
        kwargs dict) selecting temperature/top-k/top-p on the
        per-request committed threefry stream; ``beam_width`` — beam
        search over N slot-hypotheses (deterministic; exclusive with
        sampling and speculation); ``grammar`` — a CompiledGrammar
        whose per-step masks constrain output (requires a model built
        with ``logits_mask=True`` except on the speculative path, which
        masks host-side)."""
        entry = self._resolve(model, version)
        m = entry.model
        tenant = str(tenant)
        entry.metrics.incr("submitted")
        entry.metrics.tenant_incr("submitted", tenant)
        severity = entry._brownout.level
        if (severity >= 4 and priority != Priority.HIGH
                and entry._shed_confirmed()):
            # brownout L4: the ladder's last rung — shed non-HIGH at the
            # door with a measured retry-after instead of queueing work
            # the drain rate says will miss its deadline anyway
            entry.metrics.incr("rejected")
            entry.metrics.incr("brownout_shed")
            entry.metrics.tenant_incr("rejected", tenant)
            _instant("brownout::shed", level=severity, priority=priority,
                     tenant=tenant, why="l4_non_high")
            raise RejectedError(
                f"brownout {entry._brownout.name}: shedding non-HIGH "
                "traffic under overload",
                retry_after_s=entry._queue.retry_after_estimate(1))
        self._validate(m, prompt_ids, max_new_tokens, priority, entry)
        if isinstance(sampling, dict):
            sampling = SamplingParams(**sampling)
        if sampling is not None and not isinstance(sampling, SamplingParams):
            self._bad(entry, "sampling must be a SamplingParams or dict")
        if m.fills_blocks and (
                beam_width is not None or draft_model is not None
                or grammar is not None
                or (sampling is not None and not sampling.greedy)):
            self._bad(entry, f"model {m.label} fills its answer a block of "
                             f"{m.block_len} positions at a time, in the "
                             "order its own confidence gives, and a "
                             "block's K/V rows are not final until it is "
                             "committed: sampling, beam search, grammars "
                             "and speculative decoding are not served "
                             "for it")
        if not entry.kv.restores and (beam_width is not None
                                      or draft_model is not None):
            # a fork re-injects copied K/V rows and a verify re-derives
            # them in a one-shot prefill: the store can put none back
            self._bad(entry, f"model {m.label} keeps per-slot recurrent "
                             "state or has no prefill and inject programs: "
                             "beam search and speculative decoding "
                             "are not served for it")
        beam = None
        if beam_width is not None:
            beam = BeamParams(beam_width)
            if beam.width > m.slots:
                self._bad(entry,
                          f"beam width {beam.width} exceeds the entry's "
                          f"{m.slots} batch slots")
            if sampling is not None and not sampling.greedy:
                self._bad(entry, "beam search is deterministic; it does "
                                 "not compose with sampling")
            if draft_model is not None:
                self._bad(entry, "beam search does not compose with "
                                 "speculative decoding")
            if (severity >= 3 and beam.width > entry._brownout.beam_cap
                    and entry._shed_confirmed()):
                # brownout L3: wide beams multiply slot + block footprint;
                # cap NEW admissions (in-flight groups keep their width)
                entry.metrics.incr("rejected")
                entry.metrics.incr("brownout_shed")
                entry.metrics.tenant_incr("rejected", tenant)
                _instant("brownout::shed", level=severity,
                         priority=priority, tenant=tenant,
                         why="l3_beam_cap")
                raise RejectedError(
                    f"brownout {entry._brownout.name}: beam width capped "
                    f"at {entry._brownout.beam_cap} under pressure",
                    retry_after_s=entry._queue.retry_after_estimate(1))
        if grammar is not None:
            if not isinstance(grammar, CompiledGrammar):
                self._bad(entry, "grammar must be a CompiledGrammar")
            if m.eos_id is None:
                self._bad(entry, "grammar-constrained decode needs a "
                                 "model with an eos_id")
            if grammar.eos_id != m.eos_id:
                self._bad(entry,
                          f"grammar eos_id {grammar.eos_id} != model "
                          f"eos_id {m.eos_id}")
            if len(grammar.vocab) != m.vocab_size:
                self._bad(entry,
                          f"grammar vocab size {len(grammar.vocab)} != "
                          f"model vocab {m.vocab_size}")
            if draft_model is None and not m.logits_mask:
                self._bad(entry,
                          "grammar-constrained decode needs a model "
                          "built with logits_mask=True (the DEC_MASK "
                          "feed); only the speculative path masks "
                          "host-side")
        draft_key = None
        draft_kv = bool(draft_kv)
        if draft_model is not None:
            draft_entry = self._resolve(draft_model, draft_version)
            dm = draft_entry.model
            if dm.key == m.key:
                self._bad(entry, "draft model must differ from the target")
            if dm.vocab_size != m.vocab_size:
                self._bad(entry,
                          f"draft vocab {dm.vocab_size} != target vocab "
                          f"{m.vocab_size}")
            need = len(list(prompt_ids)) + int(max_new_tokens)
            if need > dm.max_len:
                self._bad(entry,
                          f"prompt + max_new_tokens ({need}) exceeds the "
                          f"draft model's max_len {dm.max_len}")
            if int(spec_k) < 1:
                self._bad(entry, f"spec_k must be >= 1, got {spec_k}")
            draft_key = dm.key
            if draft_kv:
                # pin the draft: draft-KV decode/inject calls DONATE the
                # draft arena, so the draft entry must carry no primary
                # traffic. Pinning is best-effort at admission (a request
                # picked but not yet slotted can slip the busy check);
                # production deployments dedicate the draft entry by
                # configuration, and the per-call _draft_lock serializes
                # every spec user either way.
                with draft_entry._cond:
                    busy = (not draft_entry._queue.empty()
                            or draft_entry._pool.active_count > 0)
                    if busy and not draft_entry._draft_pinned:
                        draft_kv = False    # replay fallback, this request
                    else:
                        draft_entry._draft_pinned = True
        else:
            draft_kv = False
        with self._tenant_lock:
            st = self._tenant(tenant)
            over_quota = (st.max_queued is not None
                          and st.queued >= st.max_queued)
            quota = (st.queued, st.max_queued)
            if not over_quota:
                st.queued += 1
        if over_quota:
            # the queue lock is taken OUTSIDE _tenant_lock here: the
            # scheduler thread acquires them in queue-then-tenant order
            # (_admit_free_slots -> _pick), so estimating retry-after
            # while still holding _tenant_lock would be an ABBA deadlock
            entry.metrics.incr("rejected")
            entry.metrics.incr("rejected_quota")
            entry.metrics.tenant_incr("rejected", tenant)
            raise RejectedError(
                f"tenant '{tenant}' is at its admission quota "
                f"({quota[0]}/{quota[1]} queued)",
                retry_after_s=entry._queue.retry_after_estimate(1),
            )
        if deadline_at is not None:
            deadline = float(deadline_at)
        else:
            deadline = (time.perf_counter() + deadline_ms / 1e3
                        if deadline_ms is not None else None)
        with self._id_lock:
            self._next_id += 1
            rid = self._next_id
        req = GenerationRequest(rid, prompt_ids, max_new_tokens, tenant,
                                priority, deadline, draft_key=draft_key,
                                spec_k=spec_k, sampling=sampling, beam=beam,
                                grammar=grammar, draft_kv=draft_kv)
        with entry._cond:
            pinned = entry._draft_pinned
        if pinned:
            # a pinned draft entry serves speculative proposals through
            # donated arena calls — concurrent primary traffic would
            # corrupt them. Reject before enqueue (best-effort, like the
            # pinning busy-check itself: dedicating the draft entry by
            # configuration is the production posture).
            self._tenant_unqueue(tenant)
            self._bad(entry, "entry is pinned as a draft-KV proposal "
                             "server; submit primary traffic elsewhere")
        try:
            with entry._cond:
                entry._queue.put(req)
                entry._cond.notify()
        except RejectedError:
            self._tenant_unqueue(tenant)
            entry.metrics.incr("rejected")
            entry.metrics.incr("rejected_shutdown" if entry._queue.closed()
                               else "rejected_queue_full")
            entry.metrics.tenant_incr("rejected", tenant)
            raise
        return req.response

    @staticmethod
    def _bad(entry, msg):
        entry.metrics.incr("rejected")
        entry.metrics.incr("rejected_invalid")
        raise RejectedError(msg)

    def _validate(self, m, prompt_ids, max_new, priority, entry):
        def bad(msg):
            self._bad(entry, msg)

        try:
            prompt = [int(t) for t in prompt_ids]
        except (TypeError, ValueError):
            bad("prompt_ids must be a sequence of token ids")
        if priority not in Priority.LANES:
            bad(f"unknown priority {priority!r}")
        if not prompt:
            bad("empty prompt")
        if any(t < 0 or t >= m.vocab_size for t in prompt):
            bad(f"prompt token out of range [0, {m.vocab_size})")
        if int(max_new) < 1:
            bad(f"max_new_tokens must be >= 1, got {max_new}")
        if len(prompt) + int(max_new) > m.max_len:
            bad(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new}) "
                f"exceeds the KV arena length {m.max_len}; shorten the "
                "request or host the model with a longer max_len")

    # -- observability ----------------------------------------------------
    def stats(self):
        per_model = {e.model.label: e.stats()
                     for e in self._entries.values()}
        with self._tenant_lock:
            tenants = {
                t: {"weight": st.weight, "in_flight": st.in_flight,
                    "queued": st.queued,
                    "max_in_flight": st.max_in_flight,
                    "max_queued": st.max_queued}
                for t, st in self._tenants.items()
            }
        return {
            "models": per_model,
            "tenants": tenants,
            "hosted": ["@".join(k) for k in sorted(self._entries)],
        }
