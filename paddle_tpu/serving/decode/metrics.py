"""Decode-engine metrics: the serving counter set + iteration-level series.

Extends ServingMetrics (same engine-label discipline, same registry /
profiler mirroring, same per-tenant counters) with the quantities that
only exist under iteration-level scheduling: decode steps, active
slot-steps (the occupancy numerator), generated tokens, prefill runs vs
prefix-cache hits, retirements, and step/prefill latency histograms.
``occupancy()`` is the headline number: mean fraction of the S-slot batch
doing real work per iteration — what continuous batching buys over
request-at-a-time bucketing.

What a caller waits for, from the per-token time stamps on ``Response``:
``serving_decode_first_token_seconds`` (submit to first token) and
``serving_decode_inter_token_seconds`` (the mean gap between a request's
tokens), observed at retirement. What crosses the device boundary:
``serving_fed_bytes_total`` / ``serving_fetched_bytes_total``, with
``serving_step_launches_total`` to put them per decode step. A greedy
decode step fetches its ``[S, 1]`` tokens, chosen by the step program;
``serving_decode_logits_fetch_steps_total`` counts the steps that brought
the whole ``[S, 1, V]`` float32 logits to the host instead, because a
slot sampled, searched beams or masked a grammar on the host. What a
step's attention has to read: ``serving_decode_live_blocks_total`` over
``serving_decode_block_slots_total``; and in how many copy units the
kernel brings it in, ``serving_paged_copy_units_total``, of which
``serving_paged_copy_units_ahead_total`` are in flight before their reduce
is due; the blocks it copies, ``serving_paged_copy_blocks_total`` (the
live blocks where a kernel serves the geometry), and those of them that lie
in a run of the slot's block table and go in ONE descriptor,
``serving_paged_run_blocks_total``.

The order of a decode step's phases. A step is feeds, launch
(``decode::step``), ONE fetch (``decode::step_fetch``) and the host half
that needs the fetched values (``decode::sample``). A step that needs
only its tokens stays on the device after its launch, and the scheduler
launches the next one, fed those tokens as a device array, AHEAD of the
fetch: ``serving_decode_steps_ahead_total`` counts such launches,
beside ``serving_step_launches_total`` that counts all, and the
``decode::step`` span says ``ahead=True|False``. A chunked admission rides
in the same order: an arrival whose admission is a block acquisition and
a slot in mode ``"prefill"`` is admitted under the step in flight, and
every chunk of its prompt, the last one too, is a launch and no fetch
(``serving_chunk_launches_ahead_total`` counts the chunk launches made with
a step in flight, beside ``serving_chunk_runs_total`` that counts all;
``decode::chunk`` says ``ahead=`` and ``last=``). The last chunk's one
logits row is fetched behind the NEXT step's launch (``decode::chunk_fetch``
says ``deferred=True``), and the slot it starts joins the step after with
its token from the host. A DRAIN is the other order: the step in flight
is fetched and delivered with nothing launched over it, because the
iteration has to see the device or changes who steps. Its
``decode::step_fetch`` span says why (``drain=``), and
``serving_decode_drains_total{why=}`` counts it, tracing on or off
(``DRAIN_REASONS``): ``admission`` (a picked request takes the one-shot
prefill, speculates, searches beams, or needs blocks the free list lacks),
``prefill`` (a beam request's last chunk), ``slots`` (a new slot samples
or masks a grammar: its step lands in its own body), ``park``, ``parked``
(a parked or deferred session), ``spec``, ``idle`` (nothing follows the
step), ``brownout``, ``breaker``, ``shutdown``. Every step is observed
once in ``serving_decode_step_seconds``, when it is delivered: the wall
time of the ``_step`` body that delivered it (with a step in flight that
is the launch of step N+1 and the fetch and host half of step N), or of
the drain, plus the time of the body that launched it if that body
delivered nothing. The sum over a window is the host time spent on
decode steps; an iteration's other phases are not in it.

Where the loop's time goes when it is not in a phase, and where a
launch's goes. ``serving_decode_wait_seconds`` observes every sleep of the
scheduler on its condition (the 20 ms idle poll, the breaker's wait; a
``decode::wait`` span each): its sum over a window is the share of it the
loop was asleep. ``serving_decode_step_put_seconds`` and
``serving_decode_step_call_seconds`` observe the two halves of every
launch of the step program, from its start to the end of the feeds'
``jax.device_put``s and from there to the executable's return: the
``put_ns`` / ``call_ns`` of the ``decode::step`` span, which carries
``launch=<n>`` (the value of ``serving_step_launches_total``), as does the
``decode::step_fetch`` that lands that step.

What the DEVICE did with each launch, only while the tracer's lanes are on
(``tracing(path, lanes=True)``; the engine's device lane, ``lane.py``: a
ready watcher stamps when the device had finished each launch, and the
account parts the wall time between two stamps). Float seconds, each
``serving_<name>_total``: a launch handed over while the launch before was
still running gives the time between the two stamps to
``device_queued_seconds`` (the device was busy all of it); any other launch
parts its interval into ``device_idle_empty_seconds`` (nothing dispatched,
the loop asleep in `_wait`), ``device_idle_host_seconds`` (nothing
dispatched, the loop at work) and ``device_unqueued_seconds`` (dispatch
latency and device time together, which the host cannot part). The four add
up to the wall time the account covers.

A model that fills its answer a block at a time:
``serving_block_passes_total{kind=fill|commit}`` counts the slots'
delivered passes (their sum is what ``serving_active_slot_steps_total``
moves by), ``serving_block_tokens_decided_total`` the tokens they decided
and ``serving_blocks_committed_total`` the blocks committed;
``decode::step`` and ``decode::step_fetch`` carry ``block_len=``,
``commit=`` (slots whose pass is a commit) and ``decided=``,
``decode::chunk`` says ``block_mask=``.

The KV block pool and the host tier, counted where it happens
(``pool.py``): ``serving_pool_block_allocs_total`` blocks handed out,
``serving_pool_evictions_total`` of them recycled a cached block,
``serving_tier_writebacks_total`` evicted blocks the tier took;
``serving_arena_read_bytes_total`` bytes of K/V arena brought to the host
to spill rows (an eviction's write-back, a ``decode::writeback`` span; a
parked session's ``decode::spill``), which are fetches like any other and
so are in ``serving_fetched_bytes_total`` too.
"""

from paddle_tpu.serving.metrics import ServingMetrics

__all__ = ["DecodeMetrics", "TOKEN_BUCKETS", "WAIT_BUCKETS",
           "LAUNCH_BUCKETS", "DRAIN_REASONS", "BLOCK_PASS_KINDS"]

# what a delivered pass of a block-filling model was to a slot: it decided
# one of the block's positions, or it found the block whole and left the
# K/V rows that stay (engine.py, "Answers filled a block at a time")
BLOCK_PASS_KINDS = ("fill", "commit")

# why a step in flight was fetched with nothing launched over it: every
# reason `engine.py _drain_reason`, `_iterate_phases` and `_step_feeds`
# name, each a series of serving_decode_drains_total from the start (a
# reader finds the family in a window without a drain)
DRAIN_REASONS = ("admission", "prefill", "slots", "park", "parked", "spec",
                 "idle", "brownout", "breaker", "shutdown")

# 10 ms wide from 50 ms to 500 ms, where a token's wait falls on the chip
# (a decode step is ~110 ms, a first token a few of them), so a quantile
# read off the buckets is good to 10 ms there; the usual ladder outside
TOKEN_BUCKETS = (
    (0.001, 0.0025, 0.005, 0.01, 0.025)
    + tuple(round(0.05 + 0.01 * i, 2) for i in range(46))
    + (0.6, 0.75, 1.0, 1.5, 2.5, 5.0, 10.0, 25.0, 50.0)
)

# the scheduler's sleeps, 0.1 ms to 0.1 s: a poll that runs out takes just
# over its 20 ms and has a bucket of its own, one cut short by a submit
# falls anywhere under it
WAIT_BUCKETS = (1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3, 5e-3, 1e-2, 1.5e-2, 2e-2,
                2.5e-2, 5e-2, 0.1)

# each half of a step's launch, 50 us to 50 ms, 0.1 ms wide from 0.5 to
# 1.5 ms where both fall on the chip (0.9-1.2 ms)
LAUNCH_BUCKETS = (
    (5e-5, 1e-4, 2.5e-4)
    + tuple(round(5e-4 + 1e-4 * i, 4) for i in range(11))
    + (2e-3, 2.5e-3, 5e-3, 1e-2, 2.5e-2, 5e-2)
)


#: real positions of a chunk launch: powers of two up to any chunk size
CHUNK_TOKEN_BUCKETS = tuple(float(1 << i) for i in range(1, 14))


def _capped_pairs(start, stop, cap):
    """The sum over ``p`` in ``[start, stop)`` of ``min(cap, p + 1)``: the
    (query, row) pairs a chunk's queries see where each sees at most
    ``cap`` rows (a window, an indexer's top-k)."""
    ramp = min(max(cap - 1, start), stop)
    return (ramp - start) * (start + ramp + 1) // 2 + (stop - ramp) * cap


class DecodeMetrics(ServingMetrics):
    COUNTERS = ServingMetrics.COUNTERS + (
        # iteration-level scheduler ("generated_tokens" counts tokens a
        # decode STEP produced; each admission's prefill-derived first
        # token is "prefill_tokens" — delivered total is their sum)
        "decode_steps", "active_slot_steps", "generated_tokens",
        "prefill_tokens", "retired", "step_failures",
        # admission / KV pool (prefix hit/miss totals live on
        # PrefixCache itself — stats() reports them from that one
        # source; only the per-tenant prefix_hits series is a counter)
        "prefills", "rejected_quota", "blocks_exhausted",
        # one-shot admissions whose K/V rows went from the prefill
        # program's outputs into the arena without visiting the host
        "prefill_device_injects",
        # chunked prefill (one budgeted chunk per engine iteration)
        "chunk_runs", "chunk_tokens",
        # rows behind the chunks (their first positions, summed) and the
        # (query, row) pairs their masks opened: what the chunks' attention
        # is required to do follows the prompts so far
        "chunk_context_rows", "chunk_attended_rows",
        # chunk launches, last or not, made with a decode step in flight
        "chunk_launches_ahead",
        # speculative decoding: target verify forwards vs emitted tokens
        # is the headline ratio; accepted/proposed is the acceptance rate
        "spec_target_steps", "spec_draft_steps", "spec_proposed_tokens",
        "spec_accepted_tokens", "spec_emitted_tokens",
        # draft-KV speculative slots (r17): O(1)-per-token proposals from
        # the draft entry's own paged arena; fallbacks count reversion to
        # whole-prompt replay proposals (resource exhaustion / poisoning)
        "spec_draft_kv_steps", "spec_draft_kv_prefills",
        "spec_draft_kv_fallbacks",
        # generation modes (r17): committed-stream sampling, grammar
        # mask steps, and beam lifecycle events
        "sampled_tokens", "grammar_steps", "beam_requests", "beam_forks",
        "beam_prunes", "beam_finished",
        # circuit breaker relaunch (AOT-warmed replacement replicas)
        "relaunches",
        # graceful degradation (r18): arena exhaustion now splits into
        # park-with-retry (session spilled to the host tier, resumed
        # byte-identically later) vs loud failure (host tier exhausted
        # or the request can never fit); "blocks_exhausted" stays the
        # umbrella total of both outcomes
        "blocks_parked_total", "blocks_failed_total",
        "sessions_parked", "sessions_resumed", "resume_replays",
        "tier_hits", "admissions_deferred",
        # brownout ladder (serving/brownout.py): witnessed transitions
        # and L4 sheds
        "brownout_transitions", "brownout_shed",
        # the device boundary: bytes of the feeds every launch puts,
        # bytes of the fetches brought back to the host, and the launches
        # of the decode-step program (the denominator for "per step");
        # and of the engine's own decode steps, those whose fetch was
        # the whole logits and not the device-chosen tokens
        "fed_bytes", "fetched_bytes", "step_launches",
        "decode_logits_fetch_steps",
        # step launches made before the previous step was fetched
        "decode_steps_ahead",
        # what the paged-attention kernel has to read: blocks that hold
        # a stepping slot's positions up to its cursor, over every block
        # of every slot (what the whole-arena gather read)
        "decode_live_blocks", "decode_block_slots",
        # how the kernel brings those blocks in: copy units (what it
        # starts and waits for together: kernels/attention.py
        # _paged_group) over the stepping slots, and those of them whose
        # copy is started before their reduce is due (all but a step's
        # first: a slot's first unit rides under the slot before it)
        "paged_copy_units", "paged_copy_units_ahead",
        # the blocks those units hold (the live blocks, where a kernel
        # serves the geometry), and those of them in an aligned group of
        # kernels/attention.py paged_run_blocks table entries that lie
        # side by side in the arena and are all live: the kernel copies
        # such a group in one descriptor an arena, by the same rule
        "paged_copy_blocks", "paged_run_blocks",
        # a model with routed experts of which this chip holds a share,
        # per decode step as the device ran it (wasted slots included):
        # tokens x k over the expert layers, those that landed on a held
        # expert, held experts with at least one token (of held experts x
        # expert layers a step: a constant of the model, like the
        # state-space layers a stepping slot updates, so neither is
        # counted here), and the busiest held expert's tokens summed over
        # the expert layers (the straggler a grouped product waits for)
        "moe_assignments", "moe_held_assignments", "moe_touched_experts",
        "moe_peak_expert_tokens",
        # the prompt CHUNKS' routed layers (summed on the device by the
        # chunk program, handed over by the next step: hybrid.py
        # GROUPED_COUNTS): the (token, held expert) pairs their routing
        # made, and the rows multiplied for them (each held expert's pairs
        # in whole row tiles under the grouped product, every token for
        # every held expert under the dense one) and the held experts with
        # a pair (whose matrices a layer of a launch has to read), summed
        # over the layers
        "moe_grouped_pairs", "moe_grouped_rows", "moe_grouped_experts",
        # a model whose stack runs several times a token, per decode step
        # as the device ran it: stepping tokens x the passes they took,
        # and the sum over them of the pass at which the exit gate expects
        # to leave, in thousandths
        "loop_pass_tokens", "loop_exit_pass_milli",
        # admission by reservation (an arena smaller than slots x length,
        # no tier): requests admitted against their whole block chain, and
        # the blocks promised to them; "admissions_deferred" counts, once
        # a request, those the pool and not the slots made wait
        "reserved_admissions", "blocks_reserved",
        # the KV block pool and the host tier (counted by pool.py through
        # the sink the engine hands it): blocks handed out, those of them
        # that recycled a cached block, evicted blocks the tier took; and
        # the bytes of arena brought to the host to spill rows
        "pool_block_allocs", "pool_evictions", "tier_writebacks",
        "arena_read_bytes",
        # a model that fills its answer a block at a time, per delivered
        # pass: tokens decided (a fill pass decides one a slot) and blocks
        # whose commit pass was delivered; the slots' passes by kind are
        # serving_block_passes_total{kind=fill|commit}
        "block_tokens_decided", "blocks_committed",
        # a model with window groups (model.py KVGroup; none of these moves
        # for any other): blocks its sequences gave back from behind their
        # windows, blocks promised to admissions there; per decode step and
        # per chunk the rows the window layers' attention reads (from the
        # first live block to the last position, a slot and group) beside
        # the rows their context holds; and, summed over the decode steps,
        # the blocks live and promised in the first (full) group's pool and
        # in the window groups' beside those pools' sizes
        "kv_window_blocks_released", "kv_blocks_reserved_window",
        "attention_rows_read_step", "attention_rows_in_context_step",
        "attention_rows_read_chunk", "attention_rows_in_context_chunk",
        # what a chunk's window layers REQUIRE, a group: the rows from its
        # first query's lower edge to its last position, and the (query,
        # row) pairs the window's mask opens
        "attention_window_rows_chunk", "attention_window_pairs_chunk",
        # a model whose layers keep an indexer's arena (model.py, "An
        # indexer's arena"; none of these moves for any other), summed over
        # its layers: per decode step the rows a stepping slot's query is
        # let attend to, min(index_topk, rows in its context), beside the
        # index keys it scores (all of them; the rows in context are
        # "attention_rows_in_context_step", a slot and layer here); per
        # chunk the (query, row) pairs the selection opens and the pairs
        # it scores
        "sparse_rows_selected_step", "index_rows_scanned_step",
        "sparse_rows_selected_chunk", "index_rows_scanned_chunk",
        "kv_blocks_live_full", "kv_blocks_live_window",
        "kv_blocks_promised_full", "kv_blocks_promised_window",
        "kv_pool_blocks_full", "kv_pool_blocks_window",
        # the device lane (lane.py), float seconds, moved only while the
        # tracer's lanes are on: the four parts of the wall time between the
        # watcher's stamps
        "device_queued_seconds", "device_unqueued_seconds",
        "device_idle_empty_seconds", "device_idle_host_seconds",
    )

    def __init__(self, engine_label=None, registry=None):
        super().__init__(engine_label=engine_label, registry=registry)
        labels = {"engine": self.engine_label}
        self._step = self._registry.histogram(
            "serving_decode_step_seconds",
            "one decode iteration (all slots)", labels=labels,
        )
        self._prefill = self._registry.histogram(
            "serving_prefill_seconds",
            "prompt prefill forward latency", labels=labels,
        )
        self._chunk = self._registry.histogram(
            "serving_chunk_prefill_seconds",
            "one budgeted chunk-prefill forward", labels=labels,
        )
        # its sum over a window is the prompt tokens prefilled there
        self._chunk_size = self._registry.histogram(
            "serving_chunk_prefill_tokens",
            "real prompt positions of one chunk launch", labels=labels,
            buckets=CHUNK_TOKEN_BUCKETS,
        )
        self._released = None       # `_observe_release` makes it
        self._first_token = self._registry.histogram(
            "serving_decode_first_token_seconds",
            "submit to first token", labels=labels, buckets=TOKEN_BUCKETS,
        )
        self._inter_token = self._registry.histogram(
            "serving_decode_inter_token_seconds",
            "mean gap between one request's tokens", labels=labels,
            buckets=TOKEN_BUCKETS,
        )
        self._wait = self._registry.histogram(
            "serving_decode_wait_seconds",
            "one sleep of the scheduler on its condition", labels=labels,
            buckets=WAIT_BUCKETS,
        )
        self._step_put = self._registry.histogram(
            "serving_decode_step_put_seconds",
            "a step launch up to the end of its feeds' device_puts",
            labels=labels, buckets=LAUNCH_BUCKETS,
        )
        self._step_call = self._registry.histogram(
            "serving_decode_step_call_seconds",
            "a step launch inside the executable's call", labels=labels,
            buckets=LAUNCH_BUCKETS,
        )
        self._drains = {
            why: self._registry.counter(
                "serving_decode_drains_total",
                "steps in flight fetched with nothing launched over them",
                labels={**labels, "why": why},
            )
            for why in DRAIN_REASONS
        }
        self._block_passes = {
            kind: self._registry.counter(
                "serving_block_passes_total",
                "a slot's delivered passes over its block, by kind",
                labels={**labels, "kind": kind},
            )
            for kind in BLOCK_PASS_KINDS
        }
        for h in (self._step, self._prefill, self._chunk,
                  self._first_token, self._inter_token, self._wait,
                  self._step_put, self._step_call, *self._drains.values(),
                  *self._block_passes.values()):
            h.reset()

    def observe_step(self, active_slots, new_tokens, seconds):
        self.incr("decode_steps")
        self.incr("active_slot_steps", active_slots)
        self.incr("generated_tokens", new_tokens)
        self._step.observe(seconds)

    def observe_prefill(self, seconds):
        self.incr("prefills")
        self._prefill.observe(seconds)

    def observe_chunk(self, tokens, seconds, ahead=False, context=0):
        """One launch of the chunk program over ``tokens`` real prompt
        positions with ``context`` rows behind them (a causal mask: query
        c sees ``context + c + 1`` rows)."""
        self.incr("chunk_runs")
        self.incr("chunk_tokens", tokens)
        self.incr("chunk_context_rows", context)
        self.incr("chunk_attended_rows",
                  tokens * context + tokens * (tokens + 1) // 2)
        self._chunk_size.observe(tokens)
        if ahead:
            self.incr("chunk_launches_ahead")
        self._chunk.observe(seconds)

    def count_drain(self, why):
        """One step in flight drained, for the reason ``why``."""
        self._drains[why].inc()

    def count_block_pass(self, kind):
        """One slot's delivered pass over its block: a ``fill`` (it
        decided a position) or the block's ``commit``."""
        self._block_passes[kind].inc()

    def block_passes(self):
        """{kind: count} of the slots' delivered block passes."""
        return {kind: int(c.value)
                for kind, c in self._block_passes.items()}

    def drains(self):
        """{why: count} of the drains so far."""
        return {why: int(c.value) for why, c in self._drains.items()}

    def observe_wait(self, seconds):
        self._wait.observe(seconds)

    def observe_step_launch(self, put_seconds, call_seconds):
        self._step_put.observe(put_seconds)
        self._step_call.observe(call_seconds)

    def count_launch(self, kind, fed_bytes):
        """One launch of the ``kind`` program that put ``fed_bytes`` of
        feeds on the device."""
        self.incr("fed_bytes", fed_bytes)
        if kind == "step":
            self.incr("step_launches")

    def launches(self, kind):
        """The number of the ``kind`` program's launch that has just
        returned, by the counters that are always on:
        ``serving_step_launches_total`` (what ``decode::step`` carries as
        ``launch=``), else one more than ``serving_chunk_runs_total`` or
        ``serving_prefills_total``, which count a launch once the engine
        has seen it succeed."""
        if kind == "step":
            return int(self.count("step_launches"))
        return int(self.count("chunk_runs" if kind == "chunk"
                              else "prefills")) + 1

    def slept_seconds(self):
        """Seconds the loop has slept in `_wait` so far."""
        return self._wait.sum

    def observe_device(self, parts):
        """One stamped launch as the device lane accounts it
        (``lane.Parts``)."""
        if parts.queued:
            self.incr("device_queued_seconds", parts.device_ns * 1e-9)
            return
        if parts.idle_empty_ns:
            self.incr("device_idle_empty_seconds", parts.idle_empty_ns * 1e-9)
        if parts.idle_host_ns:
            self.incr("device_idle_host_seconds", parts.idle_host_ns * 1e-9)
        self.incr("device_unqueued_seconds", parts.unqueued_ns * 1e-9)

    def observe_blocks(self, live, slots, copy_units, copy_blocks,
                       run_blocks):
        """One decode step's feeds: ``live`` blocks hold its stepping
        slots' positions up to their cursors, of ``slots`` block slots
        (S x blocks per slot) in the step's row map; the kernel brings
        them in as ``copy_units`` units, all but the first in flight
        before their reduce is due: ``copy_blocks`` blocks, ``run_blocks``
        of them in runs of one descriptor."""
        self.incr("decode_live_blocks", live)
        self.incr("paged_copy_blocks", copy_blocks)
        self.incr("paged_run_blocks", run_blocks)
        self.incr("decode_block_slots", slots)
        self.incr("paged_copy_units", copy_units)
        self.incr("paged_copy_units_ahead", max(copy_units - 1, 0))

    def observe_window_rows(self, length, w):
        """One stepping slot's footing ``w`` in a windowed group: the
        group's layers read the rows from its first live block to the
        cursor, of the ``length`` their context holds."""
        self.incr("attention_rows_read_step", length - w.base)
        self.incr("attention_rows_in_context_step", length)

    def observe_sparse_step(self, length, topk, layers):
        """One stepping slot of a model with an indexer, ``length`` rows in
        its context: each of the ``layers`` scores every row's index key
        and lets the query attend to ``min(topk, length)`` of them."""
        self.incr("sparse_rows_selected_step", min(topk, length) * layers)
        self.incr("index_rows_scanned_step", length * layers)
        self.incr("attention_rows_in_context_step", length * layers)

    def observe_sparse_chunk(self, start, stop, topk, layers):
        """One chunk ``[start, stop)`` of such a model: the query at ``p``
        scores ``p + 1`` index keys and keeps ``min(topk, p + 1)`` rows, a
        layer."""
        scanned = layers * _capped_pairs(start, stop, stop)
        self.incr("sparse_rows_selected_chunk",
                  layers * _capped_pairs(start, stop, topk))
        self.incr("index_rows_scanned_chunk", scanned)
        self.incr("attention_rows_in_context_chunk", scanned)

    def observe_window_chunk(self, start, stop, w, given):
        """One chunk ``[start, stop)`` over footing ``w`` in a windowed
        group, which gave ``given`` blocks back before it: the group's
        layers read the rows from its first live block to ``stop`` of the
        ``stop`` their context holds, and are REQUIRED to read those from
        the first query's lower edge on, over the pairs its window opens."""
        size = w.window
        self.incr("attention_rows_read_chunk", stop - w.base)
        self.incr("attention_rows_in_context_chunk", stop)
        self.incr("attention_window_rows_chunk",
                  stop - max(start - size + 1, 0))
        self.incr("attention_window_pairs_chunk",
                  _capped_pairs(start, stop, size))
        self._observe_release(given)

    def _observe_release(self, blocks):
        """Blocks one release gave back (its sum over a window is the
        blocks released there); made when a model first releases."""
        if self._released is None:
            self._released = self._registry.histogram(
                "serving_window_release_blocks",
                "blocks one release behind the windows gave back",
                labels={"engine": self.engine_label},
                buckets=CHUNK_TOKEN_BUCKETS)
        self._released.observe(blocks)

    def observe_pools(self, pools, group, given):
        """One decode step's release in windowed group ``group`` of a
        store's ``pools``, which gave ``given`` blocks back: the blocks
        live and promised in that group's pool, beside those in the first
        group's, and the pools' sizes."""
        self._observe_release(given)
        for name, pool in (("full", pools[0]), ("window", pools[group])):
            self.incr("kv_blocks_live_" + name, pool.live_count)
            self.incr("kv_blocks_promised_" + name, pool.reserved)
            self.incr("kv_pool_blocks_" + name, pool.num_blocks)

    def observe_tokens(self, request):
        """At retirement: the request's time to first token and the mean
        gap between its tokens, from ``Response.token_times``."""
        resp = request.response
        times = resp.token_times
        if not times:
            return
        self._first_token.observe(times[0] - request.submit_time)
        if len(times) > 1:
            self._inter_token.observe(
                (resp.finish_time - times[0]) / (len(times) - 1))

    def occupancy(self, slots):
        steps = self.count("decode_steps")
        if steps <= 0:
            return 0.0
        return self.count("active_slot_steps") / float(steps * slots)

    def tokens_per_step(self):
        steps = self.count("decode_steps")
        if steps <= 0:
            return 0.0
        return self.count("generated_tokens") / float(steps)

    def snapshot(self, extra=None):
        out = super().snapshot(extra=None)
        out.update(self._step.snapshot("decode_step"))
        out.update(self._prefill.snapshot("prefill"))
        out.update(self._chunk.snapshot("chunk_prefill"))
        out.update(self._first_token.snapshot("first_token"))
        out.update(self._inter_token.snapshot("inter_token"))
        out.update(self._wait.snapshot("decode_wait"))
        out.update(self._step_put.snapshot("step_put"))
        out.update(self._step_call.snapshot("step_call"))
        out["decode_drains"] = self.drains()
        out["block_passes"] = self.block_passes()
        if extra:
            out.update(extra)
        return out
