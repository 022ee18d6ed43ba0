"""GenerationEngine: continuous-batching decode over a paged KV arena.

The PR-2 ServingEngine batches whole requests into fixed buckets — a
finished sequence holds its rows until the whole bucket drains. This
engine schedules at ITERATION granularity (Orca, OSDI'22): a fixed batch
of S slots is stepped once per model iteration through ONE compiled
``[S, 1]`` decode executable; finished sequences retire between
iterations and admitted prompts prefill into free slots mid-flight, so
occupancy tracks offered load instead of the slowest batchmate.

Storage is a **block-granular paged arena** (vLLM's PagedAttention,
SOSP'23): KV rows live in fixed-size blocks handed out by
``pool.BlockPool``; the compiled programs see only flat row-index feeds
(the decode step: one packed integer array, below), so HBM scales with
USED tokens, prompts sharing a prefix share PHYSICAL
blocks through the radix index (copy-on-write at divergence), and the
arena is sized against ``analysis/memory.py``'s pre-compile HBM gate
instead of reserving a dense ``slots x max_len`` grid.

Scheduling modes, all bit-identical to the offline whole-sequence
reference for any admission order (tested, not asserted by construction
alone):

* **decode** — the ``[S, 1]`` hot path, as in PR 10. The step program
  chooses each slot's greedy token itself (``arg_max`` over its own
  float32 logits); a step whose slots are all greedy brings those S
  integers to the host, any other step (a sampled slot, a beam group, a
  grammar masked on the host, a hand-built model without
  ``token_fetch``) the whole ``[S, 1, V]`` logits. Decided per step
  from the slots' own policies; both give the same tokens. A step that
  needs its tokens alone (and steps no grammar, whose next mask follows
  from the token's value) is left on the device, and the next
  iteration launches its successor, fed those tokens as the device
  array they are, BEFORE it fetches them: depth one, decided per
  iteration from the scheduler's own state (``_iterate``), the same
  tokens in either order. A slot that was not in the step in flight
  (new: its first token came off its prompt's last chunk) joins the next
  one with its token from the host, beside the others' on the device:
  the step program chooses slot by slot. From the host a step takes ONE
  array, put once (``dec_step``, int32 ``[S, 4 + blocks per slot]``: each
  stepping slot's token or -1, cursor, attention length, write row and
  block table); the step program makes its positions, causal bias, row map
  and write rows of it on the device (``paged_step_feeds``). A put costs
  this host ~0.25 ms whatever its size (PERF.md §6, PR 39), so the
  count of puts, not their bytes, is what a step's launch pays.
* **one-shot prefill** — a prompt the chunk budget covers (every prompt
  of a model without a chunk program) runs the whole-prompt prefill
  program once, and its admission moves no bulk bytes across the host
  link: the program's K/V outputs are the inject program's feeds, the
  host takes the one logits row at the prompt's last position and, in
  one fetch, the rows such a prompt can fill (what the prefix cache and
  copy-on-write keep), and all of it is launched before the host waits.
* **chunked prefill** — a prompt longer than the chunk budget streams
  through the ``[1, C]`` chunk program ONE chunk per engine iteration,
  interleaved with decode steps, so a 32k-token admission never stalls
  in-flight generations for more than one chunk's compute. Chunks fully
  covered by radix-shared blocks are skipped (shared prefixes share
  prefill work AND storage). The whole of such an admission runs in the
  launch-ahead order: the arrival is admitted (blocks from the free
  list, a slot in mode ``"prefill"``) and every chunk launched, the
  last one too, with the decode step in flight untouched; the last
  chunk's one logits row is fetched behind the NEXT step's launch.
  What still DRAINS the step in flight first, and says why
  (``serving_decode_drains_total{why=}``): a one-shot prompt, a
  speculative or beam request, a parked or deferred session, blocks the
  free list cannot cover, a new slot that samples or masks a grammar, a
  brownout move, a stop, an open breaker, a step that nothing follows.
* **speculative** — a draft model (just another ``(model, version)``
  registry entry) greedily proposes k tokens; the target verifies all
  of them in ONE batch-prefill forward and emits the longest matching
  prefix plus its own correction token. Greedy acceptance makes the
  output BIT-IDENTICAL to target-only decode; the win is target
  steps-per-emitted-token < 1.

State of a second kind: a model may keep **per-slot recurrent state**
(``DecodeModel.slot_states``: a state-space layer's, beside the paged
rows). Its programs advance it in place for the tokens whose write row is
real and leave every other slot's bytes alone; the engine names the slot
to the chunk program, sends EVERY prompt of such a model through chunked
prefill from its first token (the chunk at position 0 starts the slot
from zero: ``decode::state_reset``), registers no block of it for
sharing, parks no session of it, and refuses what would need a snapshot
of a state: a prefix cache or a host tier at ``register_model``, beam
search and speculation at ``submit``. Launch-ahead stays safe: the one
step an ``eos_id`` wastes dirties only a state the next admission resets.
A step's integer counts (``counts_fetch``: how a step's tokens were
routed, how many passes of a looped stack they took) come back in the same
fetch as its tokens. A model without prefill and inject programs
(``DecodeModel.chunks_only``) need not be recurrent: one whose stack runs
several times a token keeps K/V rows per (pass, layer), ``state_names`` is
one pair per such state, and it is refused a host tier (nothing could put
its rows back), beams and speculation likewise.

**Admission by reservation.** An arena may be smaller than ``slots x
ceil(max_len / block_size)`` (a token's rows can cost too much to give
every slot its full length). With a host tier a session that finds the
pool empty mid-generation parks; WITHOUT one (``host_tier_mb=0``) it could
only fail, so such an entry (``_reserves``) admits a greedy or sampled
request against its WHOLE block chain, ``ceil((len(prompt) +
max_new_tokens) / block_size)``, both known at ``submit``: the pool
promises the chain (``BlockPool.reserve``; promised and unopened blocks
count against ``free_count``), the slot opens its blocks out of the
promise as its cursor moves and hands back the rest when it retires. A
tenant's head request whose chain the pool cannot cover stays in the
QUEUE (``_pick(fits=)``; ``admissions_deferred`` counts it once) and takes
no slot; one whose chain no pool could hold fails at its admission.
Nothing parks, nothing fails mid-generation, and an arrival whose chain is
covered joins the launch-ahead order without a drain, as before. Selected
by the pool's size and the tier's absence alone.

Correctness contract: (a) retired/foreign slots touch the arena only
through dropped or disjoint row scatters (exact no-ops), and (b) the
additive ``-1e9`` attention bias makes positions beyond a slot's cursor
contribute exactly 0.0 (the repo-wide padding contract); gather/scatter
relocate rows byte-for-byte, so the paged rebuild preserves PR 10's
bit-exactness property for every block size.

Multi-tenancy: one engine hosts N ``(model, version)`` entries, each with
its own slot batch, queue, and scheduler thread. Admission applies
per-tenant quotas (queued rows reject at the door; in-flight caps make
the picker skip, not reject) and WEIGHTED-FAIR selection layered over the
queue's strict priority lanes (stride scheduling).

Cold start: the executables per entry lower through ``core/lowering.py``
into the content-addressed compile cache. With a populated cache
directory, a fresh replica (or the circuit breaker's relaunched
replacement) restores them from the ``jax.export`` disk tier with ZERO
traces — subprocess-asserted in tests/test_decode.py. Before anything
compiles, the paged arena is sized against the peak-HBM budget via
``analysis/memory.py`` — an oversized block pool fails with sizing
advice, not an XLA OOM.

Measured from inside (all of it nothing while tracing is off): one
``decode::iterate`` span per scheduler iteration holds a span per phase —
``decode::admit`` > ``decode::prefill`` / ``prefill_fetch`` / ``inject``,
``decode::chunk`` / ``chunk_fetch``, ``decode::feeds`` / ``step`` /
``step_fetch`` / ``sample`` — each carrying its request's id where it has
one; a launch span also says how many host arrays it put (``puts``: one
a decode step), their ``bytes``, how long the host spent inside
``jax.device_put`` and inside the executable's call, ``decode::step``
whether it was launched ahead of the previous step's fetch (``ahead``),
``decode::chunk`` whether a step was in flight (``ahead``) and whether it
is its prompt's last (``last``), that chunk's ``decode::chunk_fetch``
whether a launch was made over it first (``deferred``), and a
``decode::step_fetch`` made with nothing launched over it, why
(``drain``). Always on: a time stamp per token on the ``Response``, taken
when the host has the token, the bytes that cross the device boundary,
the steps that fetched the whole logits, the steps and the chunks
launched ahead and the drains by reason (``DecodeMetrics``).
"""

import threading
import time

import numpy as np

from paddle_tpu import profiler
from paddle_tpu.observability import lockdep
from paddle_tpu.observability.tracer import instant as _instant
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
from paddle_tpu.serving.decode.metrics import DecodeMetrics
from paddle_tpu.serving.decode.model import NEG_INF, DecodeModel
from paddle_tpu.serving.decode.pool import (
    BlockPool,
    PrefixCache,
    SlotPool,
    block_hashes,
    prompt_key,
)
from paddle_tpu.serving.decode.tier import HostKVTier
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


class _ArenaInvalidError(RuntimeError):
    """A DONATED arena update (inject/chunk) failed mid-execution: the
    old buffers were consumed and the new ones never materialized, so the
    whole KV pool — not just the admitting request — is undefined."""


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
    ``beam``). ``blocks`` is the slot's block table; ``row_map[p]`` the
    physical arena row of position ``p`` (what the chunk and inject
    programs are fed) and ``table`` the blocks' ids (the slot's row of
    the decode step's one feed, which makes the row map of it on the
    device). ``d_*`` is the draft-KV footprint of a speculative slot:
    its slot/blocks/row-map/table ON THE DRAFT ENTRY plus ``d_cursor``, the
    next draft arena position without a committed KV row. ``ahead``
    counts the slot's tokens that a launched decode step has produced on
    the device and the host has not read yet: ``cursor`` already counts
    their rows, ``generated`` and ``last_token`` do not hold them.
    ``reserve`` is what is left of the slot's reservation (admission by
    reservation): the blocks of its whole chain that it has not opened."""

    __slots__ = ("request", "mode", "cursor", "last_token", "generated",
                 "blocks", "row_map", "table", "plen", "done", "shared_len",
                 "toks", "sampling", "grammar", "beam", "score", "seq",
                 "ahead", "reserve", "d_entry", "d_slot", "d_blocks",
                 "d_row_map", "d_table", "d_cursor")

    def __init__(self, request, mode="decode"):
        self.request = request
        self.mode = mode
        self.cursor = 0
        self.last_token = None
        self.generated = []
        self.blocks = []
        self.row_map = None
        self.table = None
        self.seq = 0            # admission order (default victim policy)
        self.ahead = 0          # tokens launched, not yet on the host
        self.reserve = 0        # blocks promised by the pool, not yet opened
        self.plen = len(request.prompt)
        self.done = 0           # chunked prefill: prompt positions landed
        self.shared_len = 0     # positions served by radix-shared blocks
        self.toks = None        # spec mode: prompt + emitted so far
        self.sampling = None    # SamplingParams (committed-stream sampling)
        self.grammar = None     # per-hypothesis GrammarConstraint
        self.beam = None        # _BeamGroup this slot belongs to
        self.score = 0.0        # beam: cumulative float64 log-prob
        self.d_entry = None     # draft-KV: the draft _ModelEntry
        self.d_slot = None
        self.d_blocks = None
        self.d_row_map = None
        self.d_table = None
        self.d_cursor = 0


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
    when the launch was not traced)."""

    __slots__ = ("fetches", "active", "states", "groups", "tokens_only",
                 "host_s", "launch")

    def __init__(self, fetches, active, states, groups, tokens_only,
                 launch=None):
        self.fetches = fetches
        self.active = active
        self.states = states
        self.groups = groups
        self.tokens_only = tokens_only
        self.host_s = 0.0
        self.launch = launch


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


def _map_blocks(m, blocks, row_map):
    """``(row_map, table)`` of a slot of model ``m`` that holds ``blocks``:
    the ``[max_len]`` int64 arena row of every position the blocks cover,
    written into ``row_map`` (made when None; what lies past the blocks
    is left as it was, and is never read), and the blocks' ids
    (`DecodeModel.block_table`)."""
    bs = m.block_size
    if row_map is None:
        row_map = np.zeros(m.max_len, dtype="int64")
    for i, b in enumerate(blocks):
        lo = i * bs
        hi = min(lo + bs, m.max_len)
        row_map[lo:hi] = b.row0 + np.arange(hi - lo)
    return row_map, m.block_table(blocks)


def _by_layer(live):
    """The ``(k, v)`` rows of each layer in a ``[2 * layers, P, H]`` host
    copy of a one-shot prefill (``_ModelEntry._prefill_to_host``)."""
    return [(live[i], live[i + 1]) for i in range(0, len(live), 2)]


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
        self._blocks = BlockPool(model.num_blocks, model.block_size,
                                 count=self._metrics.incr)
        # blocks the paged-attention kernel copies as one unit at this
        # geometry (0: no kernel serves it), to count a step's units
        from paddle_tpu.kernels.attention import _paged_group
        self._copy_unit = _paged_group(
            model.block_size, model.blocks_per_slot, model.kv_width,
            model.kv_dtype)
        self._prefix = PrefixCache(prefix_cache_size)
        # graceful degradation (r18): host-RAM KV tier, parked sessions,
        # deferred admissions, and the brownout severity ladder. The
        # pool writes registered blocks back to the tier at LRU eviction
        # (decode.blocks -> decode.tier); reads go through the engine so
        # the device rows come off the live arena.
        self._tier = HostKVTier(capacity_bytes=engine._host_tier_bytes)
        if engine._host_tier_bytes:
            self._blocks.attach_tier(self._tier,
                                     read_rows=self._read_block_rows)
        # admission by reservation: an arena that cannot give every slot
        # its full length, with no tier to park a session on, admits a
        # request against its WHOLE block chain, so that no admitted
        # request can find the pool empty mid-generation
        self._reserves = (not engine._host_tier_bytes
                          and model.num_blocks
                          < model.slots * model.blocks_per_slot)
        self._parked = []       # [_ParkedSession] FIFO
        self._pending = []      # [GenerationRequest] deferred admissions
        self._brownout = BrownoutController()
        self._bt_seen = 0       # brownout transitions already counted
        self._iteration = 0     # decode::iterate spans opened (traced only)
        self._launched = None   # the _LaunchedStep in flight, if any
        self._chunk_rows = []   # [_LaunchedChunk] last chunks not landed
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
        self._draft_lock = lockdep.named_lock("decode.draft")
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
            np.zeros((m.slots, 1), jax.dtypes.canonicalize_dtype(np.int64)),
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
        self._stack_live = jax.jit(
            lambda *kv: jax.numpy.stack([a[0, :rows] for a in kv])
        ).lower(*[sds(1, L, m.hidden)] * (2 * len(m.prefill_kv_fetches))
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
        and the prefill program's constant causal bias."""
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
        states = [(n, (m.rows, m.kv_width), m.kv_dtype)
                  for kv in m.state_names for n in kv] + m.slot_states
        for n, shape, dtype in states:
            self._scope.set(n, jax.device_put(
                jnp.zeros(shape, dtype), self._engine.device))
        self._pool.reset()
        self._blocks.reset()
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
          for what has to see the device or changes who steps
          (`_drain_reason`; for a picked request, `_admission_waits`):
          a one-shot prompt, whose ``decode::prefill_fetch`` waits — a
          produced token never waits behind an admission's prefill —, a
          speculative or beam request, a parked or deferred session, a
          block acquisition the free list cannot cover, a brownout move,
          a stop, an open breaker, and a step that nothing follows.

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
        an ``eos_id`` is not known yet and costs one wasted row."""
        return (len(st.generated) + st.ahead < st.request.max_new
                and st.cursor < self._model.max_len)

    def _drain_reason(self):
        """Why the step in flight has to be fetched and delivered before
        this iteration goes on, or None when the iteration may run under
        it and launch the next step ahead of its fetch. From state that
        is there: a phase of this iteration has to see the device or
        changes who steps beyond one slot (a parked or deferred session,
        a speculative slot's verify, the last chunk of a BEAM request's
        prompt, whose first selection forks slots and copies arena rows),
        the engine is stopping or its breaker is not closed, or no slot
        of the step in flight steps again. An arrival and the last chunk
        of any other prompt are no reason: `_admission_waits` decides for
        the requests picked, and a last chunk is a launch whose row
        lands behind the next step's (`_advance_prefills`, `_step`)."""
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
            room = self._blocks.free_count

            def fits(req):
                # admission by reservation: a tenant's head request whose
                # chain the pool cannot cover now waits in the queue (its
                # turn comes back; FIFO within the tenant), and no slot is
                # spent on it. One that can NEVER fit goes on, to fail
                # loudly at its admission
                need = self._chain(req)
                if need <= room - blocks or need > self._model.num_blocks:
                    return True
                self._hold_back(req)
                return False

            while self._pool.free_count - rows > 0:
                # budget in ROWS, not requests: a beam admission claims
                # width slots (seed + first-selection forks) before the
                # next pick runs
                req = self._engine._pick(
                    self._queue, max_rows=self._pool.free_count - rows,
                    lanes=lanes, fits=fits if self._reserves else None)
                if req is None:
                    break
                picked.append(req)
                rows += req.rows
                need = self._chain(req)
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
        arenas, an exhausted pool parks a victim). Under admission by
        reservation the blocks are a request's whole chain, and the pool's
        count leaves out what it has promised already."""
        bs = self._model.block_size
        blocks = 0
        for req in picked:
            if (req.draft_key is not None or req.beam is not None
                    or not self._takes_chunks(req)):
                return True
            blocks += self._chain(req) or (len(req.prompt) + bs - 1) // bs
        return blocks > self._blocks.free_count

    def _chain(self, req):
        """The blocks a request's whole sequence takes, prompt and answer
        (both known at ``submit``), where its admission reserves them: 0
        for an engine that does not reserve, and for a beam or speculative
        request (a fork copies and shares blocks, a verify holds none:
        neither's footprint is a sum known here; they are served from what
        is promised to nobody)."""
        if (not self._reserves or req.beam is not None
                or req.draft_key is not None):
            return 0
        m = self._model
        return -(-min(len(req.prompt) + req.max_new, m.max_len)
                 // m.block_size)

    def _hold_back(self, req):
        """The pool cannot cover ``req``'s chain yet: counted once a
        request, however many rounds it waits."""
        if not req.held_back:
            req.held_back = True
            self._metrics.incr("admissions_deferred")

    def _takes_chunks(self, req):
        """Whether a prompt streams through the chunk program: one the
        chunk budget does not cover, and every prompt, from its first
        token, of a model that has no one-shot prefill (a recurrent
        model's chunks build the slot's state as they go, and no block of
        it was ever registered, so nothing is shared)."""
        m = self._model
        return bool(m.chunk_tokens and "chunk" in self._entries
                    and (len(req.prompt) > m.chunk_tokens or m.chunks_only))

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
                       reserved=self._chain(req) if outcome == "admitted"
                       else 0, free=self._blocks.free_count)
            return outcome

    def _admit_into_slot(self, req):
        if req.expired():
            # picked but dead: release the pick-time in-flight
            # reservation; no slot to free
            self._engine._tenant_unflight(req.tenant)
            self._metrics.incr("deadline_missed")
            req.response._complete(error=DeadlineExceededError(
                "deadline expired before prefill"))
            self._metrics.observe_request(req)
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
        except _ArenaInvalidError as e:
            # donated inject failed: like a step failure, every
            # in-flight sequence is lost (failed loudly), the
            # outcome drives the breaker, and the arena resets
            self._slots[slot] = None
            self._engine._tenant_unflight(req.tenant)
            self._metrics.incr("failed")
            req.response._complete(error=RequestError(
                f"request {req.id} failed in inject: {e}"))
            self._metrics.observe_request(req)
            self._metrics.incr("step_failures")
            self._probe_relaunched = False
            if self._breaker is not None:
                self._breaker_event(self._breaker.record_failure())
            self._reject_all_slots(lambda r: ReplicaLostError(
                f"request {r.id} lost to arena "
                f"failure during admission: {e}"))
            self._reset_arenas()
            # the reset arena is valid (zeroed): the REMAINING picked
            # requests still admit — dropping them would abandon
            # their futures and leak their tenants' queued counters
            return "done"
        except Exception as e:  # request-attributed, not replica health
            self._pool.release(slot)
            self._slots[slot] = None
            self._engine._tenant_unflight(req.tenant)
            self._metrics.incr("failed")
            req.response._complete(error=RequestError(
                f"request {req.id} failed in prefill: {e}"))
            self._metrics.observe_request(req)
            return "done"
        return "admitted"

    def _row_of(self, st, p):
        b = st.blocks[p // self._model.block_size]
        return b.row0 + p % self._model.block_size

    def _rebuild_row_map(self, st):
        st.row_map, st.table = _map_blocks(self._model, st.blocks,
                                           st.row_map)

    def _acquire_blocks(self, req):
        """Acquire the prompt's block chain, parking victims instead of
        hard-failing under exhaustion. Loud failure is reserved for the
        one unfixable case — the prompt alone can never fit the pool.
        Otherwise victims are preempted (spilled to the host tier, to
        resume byte-identically) until the prompt fits; if that is not
        possible right now, ``_DeferAdmission`` sends the request to
        ``_pending`` with its tenant reservation intact.

        Under admission by reservation (`_chain`) nobody is parked: the
        request's whole chain is promised by the pool or the request
        waits, and what comes back third is the part of the chain not
        opened yet (``_Slot.reserve``)."""
        m = self._model
        chain = self._chain(req)
        if chain > m.num_blocks:
            self._metrics.incr("blocks_exhausted")
            self._metrics.incr("blocks_failed_total")
            raise RuntimeError(
                f"the request's chain of {chain} blocks (prompt and answer)"
                f" can never fit a pool of {m.num_blocks}; shorten it or "
                "host the model with more blocks")
        if chain:
            if not self._blocks.reserve(chain):
                self._hold_back(req)
                raise _DeferAdmission()
            held = self._blocks.reserved
            blocks, shared_len = self._blocks.acquire_for_prompt(
                req.prompt, promised=chain)
            self._metrics.incr("reserved_admissions")
            self._metrics.incr("blocks_reserved", chain)
            # what the prompt's blocks used up of the promise (reserved
            # moves on this thread alone)
            return (blocks, shared_len,
                    chain - (held - self._blocks.reserved))
        blocks, shared_len = self._blocks.acquire_for_prompt(req.prompt)
        if blocks is not None:
            return blocks, shared_len, 0
        self._metrics.incr("blocks_exhausted")
        if (len(req.prompt) + m.block_size - 1) // m.block_size \
                > m.num_blocks:
            self._metrics.incr("blocks_failed_total")
            raise RuntimeError(
                f"block pool exhausted ({self._blocks.stats()['blocks_free']}"
                f" free of {m.num_blocks}) and the prompt alone can never "
                "fit; shorten the prompt or host the model with more blocks")
        # don't preempt on behalf of NEW work while earlier preempted
        # sessions are still waiting — they have first claim on capacity
        while blocks is None and not self._parked:
            if not self._park_victim(req):
                break
            blocks, shared_len = self._blocks.acquire_for_prompt(req.prompt)
        self._metrics.incr("blocks_parked_total")
        if blocks is None:
            self._metrics.incr("admissions_deferred")
            raise _DeferAdmission()
        return blocks, shared_len, 0

    # -- preemption / host-tier spill / resume ----------------------------
    def _read_arenas(self, pick):
        """``pick(arena)`` of every K and V arena, per state pair (a
        layer's, or a (pass, layer)'s), and the bytes brought to the host
        for it: each arena WHOLE, whatever is picked. They are fetches
        (``serving_fetched_bytes_total``) and are counted in
        ``serving_arena_read_bytes_total`` besides."""
        out, nbytes = [], 0
        for kn, vn in self._model.state_names:
            k = self._fetch(self._scope.find_var(kn))
            v = self._fetch(self._scope.find_var(vn))
            nbytes += k.nbytes + v.nbytes
            out.append((np.array(pick(k)), np.array(pick(v))))
        self._metrics.incr("arena_read_bytes", nbytes)
        return out, nbytes

    def _read_block_rows(self, b):
        """Tier write-back reader: one registered block's live arena rows
        (called by the pool inside ``decode.blocks`` at LRU eviction —
        before the evictee's rows can be overwritten by its successor).
        A ``decode::writeback`` span, with the bytes it brought over."""
        with _span("decode::writeback") as sp:
            rows, nbytes = self._read_arenas(
                lambda a: a[b.row0:b.row0 + b.size_used])
            if sp is not None:
                sp.set(block=b.id, rows=b.size_used, bytes=nbytes)
        return rows

    def _read_rows(self, row_map, n):
        """One slot's KV rows ``[0:n)`` off the live arena, per layer,
        and the bytes of arena brought to the host for them."""
        idx = np.asarray(row_map[:n], dtype=np.int64)
        return self._read_arenas(lambda a: a[idx])

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
                or self._model.chunks_only):
            # a session whose last token is still on the device cannot
            # be spilled: its rows are known, its tokens are not. Nor can
            # one of a model that nothing re-injects into: the tier keeps
            # K/V rows, and a recurrent state is no function of them
            return False
        req = st.request
        m = self._model
        if st.mode == "spec":
            # no target arena rows: the park is pure host state. The
            # draft-KV footprint (if any) is released; resume falls back
            # to replay proposals — same committed tokens either way.
            with profiler.RecordEvent("decode::spill"):
                faults.fire("decode.spill")
                self._release_draft_locked(st)
            self._slots[s] = None
            self._pool.release(s)
            # lockdep: ok(single writer: the scheduler thread; submit-side readers only probe emptiness (GIL-atomic) and tolerate staleness)
            self._parked.append(_ParkedSession(req, "spec", [st], []))
            self._metrics.incr("sessions_parked")
            return True
        need = (st.plen + req.max_new + m.block_size - 1) // m.block_size
        if need > m.num_blocks:
            return False
        key = f"park:{req.id}:0"
        with profiler.RecordEvent("decode::spill") as ev:
            faults.fire("decode.spill")
            rows, nbytes = self._read_rows(st.row_map, st.cursor)
            if ev.span is not None:
                ev.span.set(bytes=nbytes)
            toks = (list(req.prompt) + list(st.generated))[:st.cursor]
            if not self._tier.put(key, rows, st.cursor, tokens=toks):
                return False
        self._slots[s] = None
        self._pool.release(s)
        self._blocks.release(st.blocks)
        st.blocks = []
        self._release_draft_locked(st)
        # lockdep: ok(single writer: the scheduler thread; submit-side readers only probe emptiness (GIL-atomic) and tolerate staleness)
        self._parked.append(_ParkedSession(req, "decode", [st], [key]))
        self._metrics.incr("sessions_parked")
        return True

    def _park_group(self, group):
        """Preempt a whole beam group: every live hypothesis spills its
        rows (rank-keyed), the group releases ALL its slots (spares
        included), and resume rebuilds ``order`` in the same rank order —
        selection tie-breaking stays bit-identical."""
        req = group.request
        m = self._model
        live = [(sid, self._slots[sid]) for sid in group.order]
        need = sum((st.cursor + m.block_size - 1) // m.block_size
                   for _, st in live)
        if need > m.num_blocks:
            return False
        keys = []
        spilled = 0
        with profiler.RecordEvent("decode::spill") as ev:
            faults.fire("decode.spill")
            for rank, (sid, st) in enumerate(live):
                key = f"park:{req.id}:{rank}"
                rows, nbytes = self._read_rows(st.row_map, st.cursor)
                spilled += nbytes
                if ev.span is not None:
                    ev.span.set(bytes=spilled)
                toks = (list(req.prompt) + list(st.generated))[:st.cursor]
                if not self._tier.put(key, rows, st.cursor, tokens=toks):
                    for k in keys:
                        # lockdep: ok(HostKVTier is internally locked — decode.tier, a leaf under decode.blocks)
                        self._tier.discard(k)
                    return False
                keys.append(key)
        states = []
        for sid, st in live:
            self._slots[sid] = None
            self._pool.release(sid)
            self._blocks.release(st.blocks)
            st.blocks = []
            states.append(st)
        for sid in group.spare:
            self._pool.release(sid)
        group.spare = []
        group.order = []
        # lockdep: ok(single writer: the scheduler thread; submit-side readers only probe emptiness (GIL-atomic) and tolerate staleness)
        self._parked.append(
            _ParkedSession(req, "beam", states, keys, group=group))
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
        for key in ps.keys:
            # lockdep: ok(HostKVTier is internally locked — decode.tier, a leaf under decode.blocks)
            self._tier.discard(key)
        self._engine._tenant_unflight(ps.request.tenant)
        self._metrics.incr("deadline_missed"
                           if isinstance(error, DeadlineExceededError)
                           else "failed")
        ps.request.response._complete(error=error)
        self._metrics.observe_request(ps.request)

    def _resume_session(self, ps):
        """Re-admit one parked session. Returns False when capacity is
        still insufficient (caller retries next iteration); True when the
        session left the parked list — resumed, or terminally failed via
        an arena loss during re-injection."""
        m = self._model
        if ps.mode == "spec":
            s = self._pool.acquire()
            if s is None:
                return False
            with profiler.RecordEvent("decode::resume"):
                faults.fire("decode.resume")
                self._slots[s] = ps.states[0]
            self._metrics.incr("sessions_resumed")
            return True
        if ps.mode == "decode":
            st = ps.states[0]
            s = self._pool.acquire()
            if s is None:
                return False
            blocks = self._blocks.acquire_rows(st.cursor)
            if blocks is None:
                self._pool.release(s)
                return False
            st.blocks = blocks
            st.shared_len = 0
            self._rebuild_row_map(st)
            self._slots[s] = st
            with profiler.RecordEvent("decode::resume"):
                faults.fire("decode.resume")
                ok = self._inject_rows(st, ps.keys[0])
            if not ok:
                return True     # arena lost; session rejected with the rest
            self._metrics.incr("sessions_resumed")
            return True
        # beam: all live hypotheses come back together, in rank order
        group = ps.group
        got = []
        ok = True
        for st in ps.states:
            s = self._pool.acquire()
            blocks = (self._blocks.acquire_rows(st.cursor)
                      if s is not None else None)
            if s is None or blocks is None:
                if s is not None:
                    self._pool.release(s)
                ok = False
                break
            got.append((s, st, blocks))
        if not ok:
            for s, st, blocks in got:
                self._pool.release(s)
                self._blocks.release(blocks)
            return False
        group.order = []
        for s, st, blocks in got:
            st.blocks = blocks
            st.shared_len = 0
            self._rebuild_row_map(st)
            self._slots[s] = st
            group.order.append(s)
        # re-establish the group's width reservation, best-effort: forks
        # need spares, and admission must not steal them back first
        while len(group.order) + len(group.spare) < group.width:
            sid = self._pool.acquire()
            if sid is None:
                break
            group.spare.append(sid)
        with profiler.RecordEvent("decode::resume"):
            faults.fire("decode.resume")
            for rank, (s, st, blocks) in enumerate(got):
                if not self._inject_rows(st, ps.keys[rank]):
                    for key in ps.keys:
                        # lockdep: ok(HostKVTier is internally locked — decode.tier, a leaf under decode.blocks)
                        self._tier.discard(key)
                    return True     # arena lost; group rejected with the rest
        self._metrics.incr("sessions_resumed")
        return True

    def _inject_feeds(self, inj_rows, pieces):
        """The inject program's feeds from HOST rows: ``pieces`` lists
        ``(position, kv)``, ``kv[i]`` the ``(k, v)`` rows of layer ``i``
        that belong at that position onward; each layer's rows are padded
        to the program's ``[1, L, H]`` (``inj_rows`` says which of them
        land anywhere)."""
        m = self._model
        inj = {DecodeModel.INJ_ROWS: inj_rows}
        for i, names in enumerate(m.inject_kv_feeds):
            for j, name in enumerate(names):
                arr = np.zeros((1, m.max_len, m.hidden), "float32")
                for p, kv in pieces:
                    rows = kv[i][j]
                    arr[0, p:p + len(rows)] = rows
                inj[name] = arr
        return inj

    def _inject_rows(self, st, key):
        """Re-inject a resumed session's KV rows ``[0:cursor)``. The tier
        entry is consumed if present and CRC-clean; otherwise (evicted or
        quarantined) the rows are RECOMPUTED from the committed tokens —
        byte-identical, because a causal KV row is a pure function of its
        token prefix. Returns False on arena loss (the donated inject
        failed; ``_arena_lost`` already rejected every slot, this session
        included)."""
        m = self._model
        n = st.cursor
        # lockdep: ok(HostKVTier is internally locked — decode.tier, a leaf under decode.blocks)
        ent = self._tier.pop(key)
        if ent is not None and ent.size_used == n:
            kv = ent.kv_rows
        else:
            toks = (list(st.request.prompt) + list(st.generated))[:n]
            fetches = self._run("prefill", self._prefill_feeds(toks))
            kvr = [self._fetch(f) for f in fetches[1:]]
            kv = [(kvr[2 * i][0, :n], kvr[2 * i + 1][0, :n])
                  for i in range(len(m.state_names))]
            self._metrics.incr("resume_replays")
        inj_rows = np.full((m.max_len,), m.rows, dtype="int64")
        inj_rows[:n] = st.row_map[:n]
        try:
            self._run("inject", self._inject_feeds(inj_rows, [(0, kv)]))
        except Exception as e:
            self._arena_lost(f"resume inject failure: {e}")
            return False
        return True

    def _restore_from_tier(self, st):
        """Chunked admission's host-tier fast path: contiguous full
        prompt blocks just past the radix-shared prefix whose rows were
        written back at eviction re-INJECT instead of re-running chunk
        prefill — prefix-cache reach is bounded by host RAM, not HBM.
        Returns the prompt position covered through (0 = no extension);
        only applies from a block boundary, since a shared partial tail
        already occupies the next block index."""
        m = self._model
        bs = m.block_size
        if st.shared_len % bs != 0:
            return 0
        prompt = st.request.prompt
        hashes = block_hashes(prompt, bs)
        start = st.shared_len // bs
        ents = []
        idx = start
        while idx < len(hashes) and (idx + 1) * bs <= st.plen:
            ent = self._tier.get("blk:" + hashes[idx])
            if ent is None or ent.size_used != bs:
                break
            ents.append(ent)
            idx += 1
        if not ents:
            return 0
        lo, hi = start * bs, idx * bs
        inj_rows = np.full((m.max_len,), m.rows, dtype="int64")
        inj_rows[lo:hi] = st.row_map[lo:hi]
        inj = self._inject_feeds(inj_rows, [
            (lo + j * bs, ent.kv_rows) for j, ent in enumerate(ents)])
        try:
            with profiler.RecordEvent("decode::inject") as ev:
                if ev.span is not None:
                    ev.span.set(request=st.request.id)
                self._run("inject", inj, ev.span)
        except Exception as e:
            raise _ArenaInvalidError(str(e)) from e
        self._metrics.incr("tier_hits", len(ents))
        return hi

    # -- brownout ----------------------------------------------------------
    def _brownout_tick(self):
        """One severity evaluation per scheduler iteration. Occupancy
        saturates while anything is parked or deferred — the arena is
        over-subscribed even if the instantaneous row count dipped.
        Returns whether the ladder moved."""
        occ = self._blocks.stats()["occupancy"]
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
            occ = self._blocks.stats()["occupancy"]
        except Exception:
            occ = 0.0
        qp = self._queue.pressure()
        live = max(occ, qp["queue_seconds"], qp["deadline"])
        return live >= self._brownout.exit[self._brownout.level - 1]

    def _prefill_into(self, req, slot):
        m = self._model
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
            st = _Slot(req, mode="spec")
            st.seq = self._admit_seq
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
            blocks, shared_len, reserve = self._acquire_blocks(req)
            st = _Slot(req, mode="prefill")
            st.seq = self._admit_seq
            st.blocks = blocks
            st.reserve = reserve
            st.shared_len = shared_len
            # the FINAL chunk always runs (it produces the last-position
            # logits), even when the radix served every block
            st.done = min(shared_len, plen - 1)
            self._rebuild_row_map(st)
            restored = self._restore_from_tier(st)
            if restored > st.done:
                st.done = min(restored, plen - 1)
            self._slots[slot] = st
            self._metrics.incr("admitted")
            self._metrics.tenant_incr("admitted", req.tenant)
            return
        key = prompt_key(prompt)
        cached = self._prefix.get(key)
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
                fetches = self._run("prefill", self._prefill_feeds(prompt),
                                    ev.span)
        try:
            blocks, shared_len, reserve = self._acquire_blocks(req)
        except _DeferAdmission:
            if fetches is not None:
                # the retry finds the prompt in the prefix cache
                self._prefill_to_host(req, key, fetches)
            raise
        st = _Slot(req, mode="decode")
        st.seq = self._admit_seq
        st.blocks = blocks
        st.reserve = reserve
        st.shared_len = shared_len
        self._rebuild_row_map(st)
        if shared_len < plen:
            # inject ONLY the non-shared suffix: shared blocks already
            # hold byte-identical rows (same tokens -> same prefix ->
            # same KV bytes)
            inj_rows = np.full((m.max_len,), m.rows, dtype="int64")
            inj_rows[shared_len:plen] = st.row_map[shared_len:plen]
            if fetches is not None:
                inj = {DecodeModel.INJ_ROWS: inj_rows}
                for i, (kn, vn) in enumerate(m.inject_kv_feeds):
                    inj[kn] = fetches[1 + 2 * i]
                    inj[vn] = fetches[2 + 2 * i]
            else:
                inj = self._inject_feeds(inj_rows,
                                         [(0, _by_layer(cached[0]))])
            try:
                with profiler.RecordEvent("decode::inject") as ev:
                    faults.fire("decode.inject")
                    if ev.span is not None:
                        ev.span.set(request=req.id)
                    self._run("inject", inj, ev.span)
            except Exception as e:
                raise _ArenaInvalidError(str(e)) from e
            if fetches is not None:
                self._metrics.incr("prefill_device_injects")
        if fetches is not None:
            cached = self._prefill_to_host(req, key, fetches)
            self._metrics.observe_prefill(time.perf_counter() - t0)
        live, logits_row = cached

        def host_rows(start, stop):
            return [(np.array(k[start:stop]), np.array(v[start:stop]))
                    for k, v in _by_layer(live)]

        self._blocks.register_prompt_blocks(blocks, prompt,
                                            host_rows=host_rows)
        st.cursor = plen
        self._slots[slot] = st
        self._metrics.incr("admitted")
        self._metrics.tenant_incr("admitted", req.tenant)
        if req.beam is not None:
            st.mode = "beam"
            self._begin_beam(slot, logits_row)
            return
        st.sampling = req.sampling
        if req.grammar is not None:
            st.grammar = GrammarConstraint(req.grammar)
        first = self._choose_token(st, logits_row, device_masked=False)
        st.last_token = first
        st.generated = [first]
        req.response.token_times.append(time.perf_counter())
        # the prefill's first token: counted apart from generated_tokens
        # so tokens_per_step stays a decode-step quantity (<= S)
        self._metrics.incr("prefill_tokens")
        self._metrics.tenant_incr("tokens", req.tenant)
        if self._finished(st):
            self._retire(slot)

    def _prefill_feeds(self, prompt):
        m = self._model
        toks = np.zeros((1, m.max_len), "int64")
        toks[0, :len(prompt)] = prompt
        pos = np.arange(m.max_len, dtype="int64")[None]
        return {DecodeModel.PRE_TOKENS: toks,
                DecodeModel.PRE_POSITIONS: pos,
                DecodeModel.PRE_BIAS: self._causal_bias}

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
            logits_row = self._fetch(row)
            live = self._fetch(live)
            self._prefix.put(key, live, logits_row)
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
        is a launch AHEAD (``ahead=`` on ``decode::chunk``,
        ``serving_chunk_launches_ahead_total``). Where no step is in
        flight and no slot steps there is nothing to launch over it, and
        the row is fetched at once; so is a beam request's, whose first
        selection forks slots and copies arena rows (`_drain_reason`
        drained for it)."""
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
        C, L, R = m.chunk_tokens, m.max_len, m.rows
        start = st.done
        stop = min(start + C, st.plen)
        real = stop - start
        last = stop == st.plen
        ahead = self._launched is not None
        toks = np.zeros((1, C), "int64")
        toks[0, :real] = req.prompt[start:stop]
        pos = np.zeros((1, C), "int64")
        pos[0, :real] = np.arange(start, stop)
        bias = np.full((1, C, L), NEG_INF, "float32")
        bias[0, :real] = np.where(
            np.arange(L)[None, :] <= (start + np.arange(real))[:, None],
            np.float32(0.0), np.float32(NEG_INF))
        wrows = np.full((C,), R, dtype="int64")
        for c in range(real):
            p = start + c
            if p >= st.shared_len:   # never rewrite radix-shared rows
                wrows[c] = st.row_map[p]
        t0 = time.perf_counter()
        try:
            with profiler.RecordEvent("decode::chunk") as ev:
                faults.fire("decode.chunk")
                if ev.span is not None:
                    ev.span.set(request=req.id, tokens=real, ahead=ahead,
                                last=last, passes=m.passes)
                feeds = {
                    DecodeModel.CHU_TOKENS: toks,
                    DecodeModel.CHU_POSITIONS: pos,
                    DecodeModel.CHU_BIAS: bias,
                    DecodeModel.CHU_ROWS: st.row_map,
                    DecodeModel.CHU_WRITE_ROWS: wrows,
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
        self._metrics.observe_chunk(real, time.perf_counter() - t0, ahead)
        st.done = stop
        if not last:
            return 1
        # the one row a prompt's last chunk is run for, [V] of the
        # [1, C, V] that stay on the device
        self._chunk_rows.append(_LaunchedChunk(s, st, self._pickers["chunk"](
            fetches[0], np.int32(real - 1))))
        if req.beam is not None or not (ahead or self._any_stepping()):
            self._land_chunks(deferred=False)
        return 1

    def _land_chunks(self, deferred):
        """Fetch every last chunk's logits row that is still on the
        device and start its slot: the prompt's blocks registered, the
        cursor at the prompt's end, the first token chosen and stamped
        (a beam request's first selection), the slot in mode
        ``"decode"`` with its token on the HOST, or retired where that
        token ends the request, or rejected where its deadline ran out
        meanwhile (finished wins over expired, as in `_sample`).
        ``deferred`` says whether a launch was made over the row since
        its chunk's (``decode::chunk_fetch`` carries it): the fetch is
        then of a buffer that is ready, or nearly, since the chunk ran
        BEFORE that launch."""
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
            if not self._model.recurrent:
                self._blocks.register_prompt_blocks(st.blocks, req.prompt)
            st.cursor = st.plen
            if req.beam is not None:
                st.mode = "beam"
                try:
                    self._begin_beam(s, logits_row)
                except _ArenaInvalidError as e:
                    self._arena_lost(f"beam fork inject failure: {e}")
                continue
            st.mode = "decode"
            st.sampling = req.sampling
            if req.grammar is not None:
                st.grammar = GrammarConstraint(req.grammar)
            first = self._choose_token(st, logits_row, device_masked=False)
            st.last_token = first
            st.generated = [first]
            now = time.perf_counter()
            req.response.token_times.append(now)
            self._metrics.incr("prefill_tokens")
            self._metrics.tenant_incr("tokens", req.tenant)
            if self._finished(st):
                self._retire(s)
            elif req.expired(now):
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
            # cross-entry read: draft._run from this thread may race a
            # draft-side breaker relaunch, whose builder contract makes
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
                        with profiler.RecordEvent("decode::spec_draft"):
                            fetches = draft._run(
                                "prefill", draft._prefill_feeds(dtoks))
                        nxt = int(np.argmax(
                            draft._fetch(fetches[0])[0, len(dtoks) - 1]))
                        props.append(nxt)
                        dtoks.append(nxt)
                    self._metrics.incr("spec_draft_steps", k)
                else:
                    dtoks = list(st.toks) + props
                self._metrics.incr("spec_proposed_tokens", k)
                t0 = time.perf_counter()
                with profiler.RecordEvent("decode::spec_verify"):
                    faults.fire("decode.verify")
                    fetches = self._run("prefill",
                                        self._prefill_feeds(dtoks))
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
        whole-prompt replay. Draft blocks are deliberately never
        radix-registered: the draft arena shares no partial tails, so
        the proposal hot path can never trigger a COW there. Any
        failure falls back to replay proposals (counted), never fails
        the request."""
        if not draft._draft_ok or not draft._draft_pinned:
            return
        prompt = st.request.prompt
        d_slot = None
        blocks = None
        try:
            with draft._draft_lock:
                d_slot = draft._pool.acquire()
                if d_slot is None:
                    self._metrics.incr("spec_draft_kv_fallbacks")
                    return
                blocks, _shared = draft._blocks.acquire_for_prompt(prompt)
                if blocks is None:
                    draft._pool.release(d_slot)
                    self._metrics.incr("spec_draft_kv_fallbacks")
                    return
                with profiler.RecordEvent("decode::spec_draft_prefill"):
                    fetches = draft._run("prefill",
                                         draft._prefill_feeds(prompt))
                kv_rows = [draft._fetch(f) for f in fetches[1:]]
                st.d_entry = draft
                st.d_slot = d_slot
                st.d_blocks = blocks
                st.d_row_map = None
                self._rebuild_draft_row_map(draft, st)
                dm = draft.model
                plen = len(prompt)
                inj_rows = np.full((dm.max_len,), dm.rows, dtype="int64")
                inj_rows[:plen] = st.d_row_map[:plen]
                inj = {DecodeModel.INJ_ROWS: inj_rows}
                for i, (kn, vn) in enumerate(dm.inject_kv_feeds):
                    inj[kn] = kv_rows[2 * i]
                    inj[vn] = kv_rows[2 * i + 1]
                with profiler.RecordEvent("decode::spec_draft_inject"):
                    draft._run("inject", inj)
                st.d_cursor = plen
                self._metrics.incr("spec_draft_kv_prefills")
        except Exception:
            # the inject is DONATED on the draft arena: poison the entry
            # (all draft-KV users revert to replay) rather than trusting
            # an undefined arena
            draft._draft_ok = False
            if st.d_entry is draft:
                st.d_entry = None
                st.d_slot = None
                st.d_blocks = None
                st.d_row_map = None
                st.d_cursor = 0
            if blocks is not None:
                draft._blocks.release(blocks)
            if d_slot is not None:
                draft._pool.release(d_slot)
            self._metrics.incr("spec_draft_kv_fallbacks")

    def _rebuild_draft_row_map(self, draft, st):
        st.d_row_map, st.d_table = _map_blocks(draft.model, st.d_blocks,
                                               st.d_row_map)

    def _release_draft(self, st):
        """Return a spec slot's draft-side footprint (caller holds the
        draft lock, or knows no other thread can touch this state)."""
        draft = st.d_entry
        if draft is None:
            return
        if st.d_blocks:
            draft._blocks.release(st.d_blocks)
        if st.d_slot is not None:
            draft._pool.release(st.d_slot)
        st.d_entry = None
        st.d_slot = None
        st.d_blocks = None
        st.d_row_map = None
        st.d_cursor = 0

    def _release_draft_locked(self, st):
        draft = st.d_entry
        if draft is None:
            return
        with draft._draft_lock:
            self._release_draft(st)

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
            self._release_draft_locked(st)
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
        dm = draft.model
        if write:
            blocks, _nb, cow = draft._blocks.ensure_appendable(
                st.d_blocks, p)
            if blocks is None:
                self._release_draft(st)
                self._metrics.incr("spec_draft_kv_fallbacks")
                return None
            assert cow is None, "draft blocks are never radix-shared"
            st.d_blocks = blocks
            if _nb is not None:
                self._rebuild_draft_row_map(draft, st)
        step = dm.step_feed()
        row = dm.rows       # write=False: the row is right already
        if write:
            b = st.d_blocks[p // dm.block_size]
            row = b.row0 + p % dm.block_size
        dm.fill_step(step, st.d_slot, p, st.d_table, row, int(token))
        feeds = {DecodeModel.DEC_STEP: step,
                 DecodeModel.DEC_TOKEN: draft._no_tokens}
        if dm.logits_mask:
            feeds[DecodeModel.DEC_MASK] = np.zeros(
                (dm.slots, 1, dm.vocab_size), "float32")
        try:
            with profiler.RecordEvent("decode::spec_draft_kv"):
                fetches = draft._run("step", feeds)
        except Exception:
            # donated call on the DRAFT arena failed: poison the draft
            # for every user; this request reverts to replay proposals
            draft._draft_ok = False
            self._release_draft(st)
            self._metrics.incr("spec_draft_kv_fallbacks")
            return None
        if write:
            draft._blocks.note_append(st.d_blocks[p // dm.block_size])
        self._metrics.incr("spec_draft_kv_steps")
        return draft._fetch(fetches[0])[st.d_slot, 0]

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

    def _apply_cow(self, st, cow):
        """Copy-on-write landed a fresh block: re-inject the shared
        partial's retained host rows into it, then remap the slot."""
        m = self._model
        u = cow.size_used
        inj_rows = np.full((m.max_len,), m.rows, dtype="int64")
        inj_rows[:u] = cow.block.row0 + np.arange(u)
        with profiler.RecordEvent("decode::cow_inject"):
            self._run("inject",
                      self._inject_feeds(inj_rows, [(0, cow.host_rows)]))
        self._rebuild_row_map(st)

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
        # claim the rest of the group's row reservation up front (the
        # admission round budgeted width rows for this pick)
        for _ in range(group.width - 1):
            sid = self._pool.acquire()
            if sid is None:
                break
            group.spare.append(sid)
        self._metrics.incr("beam_requests")
        try:
            row = np.asarray(logits_row, dtype=np.float32).reshape(-1)
            if st.grammar is not None:
                row = row + st.grammar.mask()
            self._commit_beam_selection(group, [row], time.perf_counter())
        except _ArenaInvalidError:
            raise               # admission's arena handler owns cleanup
        except Exception as e:
            self._reject_beam_group(group, RequestError(
                f"request {req.id} failed in first beam selection: {e}"))

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
                except _ArenaInvalidError:
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
        self._blocks.check_conservation()
        if len(group.finished) >= group.width or not new_order:
            self._retire_beam(group)
            return False
        return True

    def _fork_beam(self, group, parent, token, score):
        """COW-fork one live hypothesis: second owner on the parent's
        full blocks, a private tail block filled by a device row copy
        (arena scope read -> inject), and a fresh slot carrying the
        forked host state."""
        m = self._model
        child_blocks, nb, src = self._blocks.fork_blocks(
            parent.blocks, parent.cursor)
        if child_blocks is None:
            raise RuntimeError("block pool exhausted forking a beam")
        slot = group.spare.pop() if group.spare else self._pool.acquire()
        if slot is None:
            self._blocks.release(child_blocks)
            raise RuntimeError("slot pool exhausted forking a beam")
        if nb is not None:
            u = nb.size_used
            inj_rows = np.full((m.max_len,), m.rows, dtype="int64")
            inj_rows[:u] = nb.row0 + np.arange(u)
            inj = {DecodeModel.INJ_ROWS: inj_rows}
            for i, (kn_s, vn_s) in enumerate(m.state_names):
                kn, vn = m.inject_kv_feeds[i]
                karr = np.zeros((1, m.max_len, m.hidden), "float32")
                varr = np.zeros((1, m.max_len, m.hidden), "float32")
                karr[0, :u] = np.asarray(
                    self._scope.find_var(kn_s))[src.row0:src.row0 + u]
                varr[0, :u] = np.asarray(
                    self._scope.find_var(vn_s))[src.row0:src.row0 + u]
                inj[kn] = karr
                inj[vn] = varr
            try:
                with profiler.RecordEvent("decode::beam_fork_inject"):
                    self._run("inject", inj)
            except Exception as e:
                raise _ArenaInvalidError(str(e)) from e
        st = _Slot(group.request, mode="beam")
        st.beam = group
        st.blocks = child_blocks
        st.plen = parent.plen
        st.shared_len = parent.shared_len
        st.cursor = parent.cursor
        st.last_token = int(token)
        st.generated = parent.generated + [int(token)]
        st.score = score
        if parent.grammar is not None:
            st.grammar = parent.grammar.fork().advance(token)
        self._rebuild_row_map(st)
        self._slots[slot] = st
        return slot

    def _release_beam_slot(self, sid, to_spare=False):
        st = self._slots[sid]
        self._slots[sid] = None
        if to_spare and st is not None and st.beam is not None:
            st.beam.spare.append(sid)   # keep the group's reservation
        else:
            self._pool.release(sid)
        if st.blocks:
            self._blocks.release(st.blocks)

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
        self._engine._tenant_unflight(req.tenant)
        ranked = beam_finished_ranking(group.finished)
        if not ranked:
            req.response._complete(error=RequestError(
                f"request {req.id}: beam search finished no hypothesis"))
            self._metrics.incr("failed")
            self._metrics.observe_request(req)
            return
        # the best hypothesis may have finished selections ago: its
        # tokens' stamps are the first len(tokens) selections'
        del req.response.token_times[len(ranked[0][0]):]
        req.response._complete(outputs={
            "tokens": np.asarray(ranked[0][0], dtype="int64"),
            "beams": [{"tokens": np.asarray(t, dtype="int64"),
                       "score": float(sc)} for t, sc in ranked],
        })
        self._metrics.incr("completed")
        self._metrics.incr("retired")
        self._metrics.incr("beam_finished", len(ranked))
        self._metrics.tenant_incr("completed", req.tenant)
        self._metrics.observe_request(req)
        self._metrics.observe_tokens(req)

    def _reject_beam_group(self, group, error):
        """Fail one beam request as a UNIT: release every slot the group
        still holds, then complete its single response once. (The
        arena-failure path may already have completed it through the
        admitting request's handler — the done() guard keeps the
        write-once future honest.)"""
        self._release_group_slots(group)
        req = group.request
        if req.response.done():
            return
        self._engine._tenant_unflight(req.tenant)
        self._metrics.incr(
            "deadline_missed" if isinstance(error, DeadlineExceededError)
            else "failed")
        req.response._complete(error=error)
        self._metrics.observe_request(req)

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
        a step and its host half, in one of two orders, then the logits
        row of a prompt's last chunk that was launched before it.

        The cursor half of the host's work (`_advance_cursors`) runs as
        soon as the launch has returned. A step that `_tokens_suffice`
        and that steps no grammar is then left IN FLIGHT. If one was in
        flight already, this is the launch AHEAD of its fetch: the feeds
        were built from cursors that count it, its ``[S, 1]`` tokens are
        this step's token feed without leaving the device (a slot that
        was not in it, new since, is fed its own from the host), and it
        is fetched and delivered (`_land`) only now, under the step just
        launched. Any other step (a sampled slot, a beam group, a
        grammar, a model without ``token_fetch``) lands in this same
        body, as every step once did. A step in flight that this one
        cannot follow (`_step_feeds` names why), or that nothing
        follows, is drained first.

        Last, what lies behind the launch: every last chunk's row
        (`_land_chunks`). The device ran that chunk BEFORE the step just
        launched, so the fetch finds it ready or nearly, and the slot it
        starts steps with the NEXT launch, its token from the host.

        The fetch (``decode::step_fetch``, where the host waits for the
        device unless the step has finished under its successor) brings
        the ``[S, 1]`` tokens, or the ``[S, 1, V]`` float32 logits,
        counted in ``serving_decode_logits_fetch_steps_total``; the span
        says which (``rows="tokens"|"logits"``). Nothing is sliced out
        of the device array: that would dispatch, and could compile,
        inside a serving window."""
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
        t0 = time.perf_counter()
        launch = None
        try:
            with profiler.RecordEvent("decode::step") as ev:
                faults.fire("decode.step")
                fetches = self._run("step", feeds, ev.span)
                if ev.span is not None:
                    launch = self._metrics.count("step_launches")
                    ev.span.set(ahead=prev is not None, launch=launch,
                                passes=self._model.passes)
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
        step = _LaunchedStep(fetches, active,
                             [self._slots[s] for s in active], groups,
                             self._tokens_suffice(active, groups), launch)
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
        group's cursors move with its selection, in `_sample`."""
        bs = self._model.block_size
        for st in states:
            self._blocks.note_append(st.blocks[st.cursor // bs])
            st.cursor += 1
            st.ahead += 1

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
                # the S tokens and the step's counts in ONE vector
                both = self._fetch(step.fetches[2])
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
            stepped = self._sample(fetched, active, step.groups, now,
                                   step.tokens_only)
            if sp is not None:
                sp.set(tokens=stepped)
        if stepped is not None:
            self._metrics.observe_step(
                stepped, stepped, time.perf_counter() - t0 + step.host_s)

    def _step_feeds(self):
        """The decode step's feeds from the live slots: ``(feeds, active
        slot ids, beam groups with a live slot)``, or None when there is
        nothing to step (or the arena was lost making a cursor
        writable). ONE host array, ``dec_step`` (`DecodeModel.step_feed`
        / `fill_step`): a stepping slot's token, cursor, length, write
        row and block table, ~70 integers; the step program makes the
        bias and the row map of them on the device, and no ``[S, L]``
        array is built here. ``dec_token`` is a device array either
        way: zeros that nobody reads, or with a step in flight (the
        cursors count it already, a slot it finishes is left out) that
        step's ``[S, 1]`` output itself, every stepping slot's token -1:
        rows of slots that do not step are ignored as ever (their write
        row is the sentinel, their length 0, so their bias all
        ``NEG_INF``). A slot that was NOT in that step (new since: its
        first token came off a prompt's last chunk, ``ahead`` 0) is fed
        its token from the host in the same array, beside the others'
        -1. That holds only if such a slot needs nothing but its token
        (`_rides_ahead`) and no slot has to be parked; else nothing is
        built and the reason to drain comes back as a string: ``"slots"``
        for a new slot that samples, masks a grammar or is a beam
        hypothesis (its step brings the logits over and lands in the body
        that launched it), ``"park"``. What the loop has done before is
        done again unchanged after the drain: a block opened stays
        opened."""
        m = self._model
        S = m.slots
        step = m.step_feed()
        dmask = (np.zeros((S, 1, m.vocab_size), "float32")
                 if m.logits_mask else None)
        active = []
        groups = []     # beam groups with a live slot this step
        live_blocks = copy_units = 0
        launched = self._launched
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
            # make the cursor position writable: allocate a fresh block
            # when it opens a new chunk, COW when it lands in a SHARED
            # partial tail (divergence), unregister an exclusively-owned
            # partial before mutating it
            try:
                blocks, _nb, cow = self._blocks.ensure_appendable(
                    st.blocks, st.cursor, promised=st.reserve > 0)
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
            if blocks is None:
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
            st.blocks = blocks
            if _nb is not None and st.reserve:
                st.reserve -= 1
            if cow is not None:
                try:
                    self._apply_cow(st, cow)
                except Exception as e:
                    # the COW re-inject is a DONATED call: its failure
                    # invalidates the whole arena, not one request
                    self._arena_lost(f"copy-on-write inject failure: {e}")
                    return None
            elif _nb is not None:
                self._rebuild_row_map(st)
            if st.mode == "beam":
                if st.beam not in groups:
                    groups.append(st.beam)
            else:
                active.append(s)
            m.fill_step(step, s, st.cursor, st.table,
                        self._row_of(st, st.cursor),
                        -1 if st.ahead else st.last_token)
            reads = st.cursor // m.block_size + 1
            live_blocks += reads
            if self._copy_unit:
                copy_units += -(-reads // self._copy_unit)
            if dmask is not None and st.grammar is not None:
                # the grammar's next-token constraint rides in as DATA —
                # same compiled program for every request, zero retraces
                dmask[s, 0] = st.grammar.mask()
        if not active and not groups:
            return None
        self._metrics.observe_blocks(live_blocks, S * m.blocks_per_slot,
                                     copy_units)
        feeds = {DecodeModel.DEC_STEP: step,
                 DecodeModel.DEC_TOKEN: (self._no_tokens if launched is None
                                         else launched.fetches[1])}
        if dmask is not None:
            feeds[DecodeModel.DEC_MASK] = dmask
        return feeds, active, groups

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
                self._blocks.note_append(
                    bst.blocks[bst.cursor // m.block_size])
                bst.cursor += 1
                row = np.asarray(fetched[sid, 0],
                                 dtype=np.float32).reshape(-1)
                if bst.grammar is not None and not m.logits_mask:
                    row = row + bst.grammar.mask()
                rows_l.append(row)
            stepped += len(rows_l)
            try:
                alive = self._commit_beam_selection(group, rows_l, now)
            except _ArenaInvalidError as e:
                self._arena_lost(f"beam fork inject failure: {e}")
                return None
            if alive and group.request.expired(now):
                self._reject_beam_group(group, DeadlineExceededError(
                    "deadline expired mid-generation after "
                    f"{len(group.finished)} finished hypotheses"))
        return stepped

    def _finished(self, st):
        m = self._model
        # the cursor as of the slot's last token on the host: a step
        # launched over it has moved the cursor once more
        return (len(st.generated) >= st.request.max_new
                or (m.eos_id is not None and st.last_token == m.eos_id)
                or st.cursor - st.ahead >= m.max_len)

    def _retire(self, slot):
        st = self._slots[slot]
        self._slots[slot] = None
        self._pool.release(slot)
        if st.blocks:
            self._blocks.release(st.blocks, st.reserve)
        self._release_draft_locked(st)
        req = st.request
        self._engine._tenant_unflight(req.tenant)
        req.response._complete(outputs={
            "tokens": np.asarray(st.generated, dtype="int64"),
        })
        self._metrics.incr("completed")
        self._metrics.incr("retired")
        self._metrics.tenant_incr("completed", req.tenant)
        self._metrics.observe_request(req)
        self._metrics.observe_tokens(req)

    def _reject_in_flight(self, req, error, slot=None):
        if slot is not None:
            st = self._slots[slot]
            self._slots[slot] = None
            self._pool.release(slot)
            if st is not None and st.blocks:
                self._blocks.release(st.blocks, st.reserve)
            if st is not None:
                self._release_draft_locked(st)
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
            t = len(toks) - 1
            fetches = self._run("prefill", self._prefill_feeds(toks))
            row = self._fetch(fetches[0])[0, t].astype(np.float32)
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
            fetches = self._run("prefill", self._prefill_feeds(tokens))
            return self._fetch(fetches[0])[0, len(tokens) - 1]

        g = GrammarConstraint(grammar) if grammar is not None else None
        return offline_beam_decode(logits_fn, prompt, int(max_new), params,
                                   m.eos_id, m.max_len, grammar=g)

    # -- observability ----------------------------------------------------
    def stats(self):
        m = self._model
        pool = self._blocks.stats()
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
            "block_pool": pool,
            "block_dedup_ratio": pool["dedup_ratio"],
            "spec_steps_per_token": (spec_t / spec_e) if spec_e else None,
            "spec_acceptance_rate": (
                self._metrics.count("spec_accepted_tokens") / spec_p
                if spec_p else None),
            "spec_draft_kv_steps_per_token": (
                self._metrics.count("spec_draft_kv_steps") / spec_e
                if spec_e else None),
            "draft_pinned": self._draft_pinned,
            "prefix_cache_entries": len(self._prefix),
            "prefix_hits": self._prefix.hits,
            "prefix_misses": self._prefix.misses,
            "compile_sources": dict(self.compile_sources),
            "breaker_state": (self._breaker.state if self._breaker
                              else None),
            "tenant_tokens": self._metrics.tenant_counts("tokens"),
            "tenant_completed": self._metrics.tenant_counts("completed"),
            "host_tier": self._tier.stats(),
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
        return self._prefix

    @property
    def block_pool(self):
        return self._blocks


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
        if model.recurrent and (self._prefix_cache_size
                                or self._host_tier_bytes):
            from paddle_tpu.utils.enforce import EnforceError

            raise EnforceError(
                f"model {model.label} keeps per-slot recurrent state, "
                "which the prefix cache and the host KV tier cannot carry: "
                "both key on K/V rows, a function of the token prefix "
                "alone, and hold no snapshot of a state. Host it on an "
                "engine with prefix_cache_size=0 and host_tier_mb=0 (got "
                f"prefix_cache_size={self._prefix_cache_size}, "
                f"host_tier_mb={self._host_tier_bytes >> 20})")
        if model.chunks_only and self._host_tier_bytes:
            from paddle_tpu.utils.enforce import EnforceError

            raise EnforceError(
                f"model {model.label} has no inject program: what the host "
                "KV tier keeps (an evicted block's rows, a parked "
                "session's) could never be put back. Host it on an engine "
                f"with host_tier_mb=0 (got {self._host_tier_bytes >> 20})")
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
        (``result()`` -> ``{"tokens": int64 array}``). Raises structured
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
        if m.chunks_only and (beam_width is not None
                              or draft_model is not None):
            # a fork re-injects copied K/V rows and a verify re-derives
            # them in a one-shot prefill; a model served by chunks alone
            # has neither program (and a fork carries no recurrent state)
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
