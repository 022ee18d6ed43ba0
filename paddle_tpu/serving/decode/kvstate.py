"""The K/V state of a sequence: how its rows are kept, moved and given back.

The scheduler (engine.py) decides WHO runs, parks, forks and retires; this
module owns what that means for storage, for one hosted model: the block
pool and its radix index (pool.py), the host tier (tier.py), the
whole-prompt prefix cache, the inject program's feeds and every read of an
arena. `SeqKV` is one sequence's footing on one entry; `KVStore` is the
entry's store, and each of its operations is written once.

**The arena.** K/V rows live in fixed-size blocks of a flat ``[R, H]`` row
arena per state pair (a layer's, or a (pass, layer)'s); the programs see
row indices only, so HBM follows the tokens in use and prompts that share a
prefix share physical blocks (copy-on-write where they part). A row for
position ``p`` is a pure function of ``tokens[:p + 1]``, which is why a
spilled, evicted or corrupt row may always be recomputed byte for byte.
The correctness contract: (a) retired and foreign slots touch the arena
only through dropped or disjoint row scatters (exact no-ops), (b) the
additive ``-1e9`` attention bias makes positions beyond a slot's cursor
contribute exactly 0.0, and gather/scatter relocate rows byte for byte, so
every block size gives the tokens of the whole-sequence reference.

**Rows reach the arena one way** (`KVStore.write_rows`, the inject program)
and leave it one way (`read_rows` / `read_block`, which bring every arena
WHOLE to the host and count it: ROADMAP 3.16). A failed donated launch
leaves the arena undefined: the store raises `ArenaInvalidError` and rejects
nobody; what that means is each caller's.

**Admission by reservation.** An arena may be smaller than ``slots x
ceil(max_len / block_size)``. With a host tier a sequence that finds the
pool empty parks; without one it could only fail, so such a store
(``reserves``) promises a greedy or sampled request its WHOLE chain,
``ceil((len(prompt) + max_new) / block_size)``, at `acquire`, or gives None
and the request waits in the queue (counted once). The sequence opens its
blocks out of the promise (`open_block`) and `release` hands back the rest.
Chosen by the pool's size and the tier's absence alone.

**Layer groups.** A model may keep some attention layers' rows in groups of
their own (model.py ``KVGroup``): layers that see the last ``window``
positions and nothing older. Each such group has a `BlockPool` of its own
(``KVStore.window_pools``) and a sequence a footing in it (`WindowKV`) that
holds only the blocks with a position inside the window of the sequence's
NEXT query: `KVStore.release_behind` gives the others back. When: the
scheduler calls it for position ``p`` right before it builds the feeds of the
launch whose first query stands at ``p`` (the step of the token at ``p``,
the chunk that starts at ``p``). Every launch that read the blocks given
back was made before that, and whoever is handed them next writes them in a
launch made after it; the device runs launches in the order they were made
(a step launched ahead included: PR 42's launch-ahead keeps ONE order of
launches, it only fetches later), so a row is overwritten only after its
last reader ran. Nothing approximates: a row inside a query's window is in
a live block, a row outside it is masked (the oldest live block's) or
absent. Admission promises a request, a group, what it can ever hold there
at once, and a block given back renews the promise for the blocks still to
be opened. Such a store always reserves, shares no block and carries
neither a prefix cache nor a tier: a block that was given back can be
neither shared nor restored.

**What a store refuses to carry.** The tier and the prefix cache key on K/V
rows; a per-slot recurrent state is no function of them, and a model
without an inject program (``chunks_only``) could never take rows back. So
`GenerationEngine.register_model` refuses such a model a prefix cache or a
tier, its blocks are never registered for sharing and its sessions never
spill. A model that fills its answer a block at a time is refused both as
well: a block's rows are rewritten by every pass until it is committed.
"""

import numpy as np

from paddle_tpu import profiler
from paddle_tpu.observability.tracer import span as _span
from paddle_tpu.resilience import faults
from paddle_tpu.serving.decode.model import DecodeModel
from paddle_tpu.serving.decode.pool import (
    BlockPool,
    PrefixCache,
    SlotPool,
    block_hashes,
    prompt_key,
)
from paddle_tpu.serving.decode.tier import HostKVTier

# SlotPool is the scheduler's (which batch slot is free); it is handed on
# so that the scheduler imports this module alone
__all__ = ["ArenaInvalidError", "KVStore", "SeqKV", "SlotPool", "WindowKV"]


class ArenaInvalidError(RuntimeError):
    """A DONATED arena update (inject) failed mid-execution: the old
    buffers were consumed and the new ones never materialized, so the
    whole KV pool — not just the sequence written — is undefined."""


def _block_rows(blocks, block_size):
    """The arena rows of ``blocks``' positions, in the chain's order."""
    row0 = np.fromiter((b.row0 for b in blocks), "int64", len(blocks))
    return (row0[:, None] + np.arange(block_size)).reshape(-1)


def _chunk_rows(row_map, base, lo, start, stop, width, nowhere):
    """A chunk program's ``[width]`` write rows for positions
    ``[start:stop)`` out of a ``row_map`` that starts at position ``base``:
    the positions under ``lo`` and the padding write ``nowhere``."""
    rows = np.full((width,), nowhere, dtype="int64")
    if lo < stop:
        rows[lo - start:stop - start] = row_map[lo - base:stop - base]
    return rows


class SeqKV:
    """The storage state of ONE sequence on ONE entry. ``blocks`` is its
    block chain; ``row_map[p]`` the arena row of position ``p`` (what the
    chunk and inject programs are fed) and ``table`` the blocks' ids (its
    row of the decode step's one feed); ``shared_len`` the positions that
    radix-shared blocks already hold, never to be rewritten; ``reserve``
    what is left of its reservation: blocks promised and not yet opened."""

    __slots__ = ("blocks", "row_map", "table", "reserve", "shared_len",
                 "windows", "_m")

    def __init__(self, model, blocks, shared_len=0, reserve=0, windows=()):
        self._m = model
        self.blocks = blocks
        self.shared_len = shared_len
        self.reserve = reserve
        # its footing in each of the model's window groups (`WindowKV`)
        self.windows = windows
        self.row_map = np.zeros(model.max_len, dtype="int64")
        self.remap()

    def remap(self):
        """``row_map`` and ``table`` after ``blocks`` changed. What lies
        past the blocks is left as it was, and is never read."""
        m = self._m
        rows = _block_rows(self.blocks, m.block_size)[:m.max_len]
        self.row_map[:len(rows)] = rows
        self.table = m.block_table(self.blocks)

    def row_of(self, p):
        bs = self._m.block_size
        return self.blocks[p // bs].row0 + p % bs

    def chunk_write_rows(self, start, stop, width):
        """The chunk program's ``[width]`` write rows for positions
        ``[start:stop)``: a shared position and the padding write nowhere."""
        return _chunk_rows(self.row_map, 0, max(start, self.shared_len),
                           start, stop, width, self._m.rows)


class WindowKV:
    """One sequence's footing in ONE window group (model.py ``KVGroup``).
    ``blocks`` is the chain's LIVE part and ``first`` the index, in the
    whole chain, of ``blocks[0]``; ``row_map`` and ``table`` (what the
    chunk program and the decode step are fed) start at that block, so
    position ``p`` lies at ``row_map[p - first * block_size]``. ``reserve``
    is what the pool has promised the sequence and it has not opened,
    ``left`` the blocks it has yet to open over its life, ``limit`` the most
    it holds at once: ``reserve == min(limit - len(blocks), left)``."""

    __slots__ = ("blocks", "first", "reserve", "left", "limit", "row_map",
                 "table", "_m", "_rows")

    def __init__(self, model, group, life_blocks):
        self._m = model
        self._rows = group.num_blocks * model.block_size
        self.blocks, self.first = [], 0
        self.left = life_blocks
        most = model.window_chunk_blocks(group)
        self.limit = self.reserve = min(life_blocks, most)
        self.row_map = np.zeros(most * model.block_size, "int64")
        self.table = np.zeros(model.window_table_blocks(group), "int32")

    def remap(self):
        """``row_map`` and ``table`` after ``blocks`` or ``first`` changed;
        what lies past the blocks is never read."""
        bs = self._m.block_size
        rows = _block_rows(self.blocks, bs)
        self.row_map[:len(rows)] = rows
        n = min(len(self.blocks), len(self.table))
        self.table[:n] = rows[:n * bs:bs] // bs

    def row_of(self, p):
        return self.row_map[p - self.first * self._m.block_size]

    def chunk_write_rows(self, start, stop, width):
        """The chunk program's ``[width]`` write rows in this group for
        positions ``[start:stop)``; the padding writes nowhere."""
        return _chunk_rows(self.row_map, self.first * self._m.block_size,
                           start, start, stop, width, self._rows)


class KVStore:
    """One entry's K/V storage. ``run(kind, feeds, span)`` launches one of
    the entry's programs, ``fetch(value)`` brings a device value to the
    host and counts it, ``scope()`` is where the arenas live: launch and
    scope stay the entry's. All of it runs on the entry's scheduler thread
    (a draft entry's: under its ``decode.draft`` lock); `stats` may be read
    from any."""

    def __init__(self, model, tier_bytes, prefix_cache_size, metrics, run,
                 fetch, scope, device):
        from paddle_tpu.kernels.attention import paged_copy_unit

        self._model = model
        self._metrics = metrics
        self._run = run
        self._fetch = fetch
        self._scope = scope
        self._device = device
        self.pool = BlockPool(model.num_blocks, model.block_size,
                              count=metrics.incr)
        self.prefix = PrefixCache(prefix_cache_size)
        # the pool writes registered blocks back to the tier at LRU
        # eviction (decode.blocks -> decode.tier), through `_writeback`
        self.tier = HostKVTier(capacity_bytes=tier_bytes)
        if tier_bytes:
            self.pool.attach_tier(self.tier, read_rows=self._writeback)
        # a window group's pool, by the group's place in the model's list;
        # what happens in them is counted here and not by the pools
        self.window_pools = [BlockPool(g.num_blocks, model.block_size)
                             for g in model.window_groups]
        self.reserves = (not tier_bytes and (
            model.num_blocks < model.slots * model.blocks_per_slot
            or bool(self.window_pools)))
        # blocks the paged-attention kernel copies as one unit at this
        # geometry (0: no kernel serves it), to count a step's units
        self.copy_unit = paged_copy_unit(
            model.block_size, model.blocks_per_slot, model.kv_width,
            model.kv_dtype, model.arenas // len(model.state_names))

    @staticmethod
    def check_carries(model, tier_bytes, prefix_cache_size):
        """Refuse, before anything is built, a model whose state a store
        of these sizes could not carry."""
        from paddle_tpu.serving.request import ServingError
        from paddle_tpu.utils.enforce import EnforceError

        if model.fills_blocks and (prefix_cache_size or tier_bytes):
            raise ServingError(
                f"model {model.label} fills its answer a block of "
                f"{model.block_len} positions at a time: a block's K/V "
                "rows are rewritten by every pass and final only once it "
                "is committed, so neither the prefix cache nor the host KV "
                "tier may hold them. Host it on an engine with "
                "prefix_cache_size=0 and host_tier_mb=0 (got "
                f"prefix_cache_size={prefix_cache_size}, "
                f"host_tier_mb={tier_bytes >> 20})")
        if model.recurrent and (prefix_cache_size or tier_bytes):
            raise EnforceError(
                f"model {model.label} keeps per-slot recurrent state, "
                "which the prefix cache and the host KV tier cannot carry: "
                "both key on K/V rows, a function of the token prefix "
                "alone, and hold no snapshot of a state. Host it on an "
                "engine with prefix_cache_size=0 and host_tier_mb=0 (got "
                f"prefix_cache_size={prefix_cache_size}, "
                f"host_tier_mb={tier_bytes >> 20})")
        if model.window_groups and (prefix_cache_size or tier_bytes):
            raise EnforceError(
                f"model {model.label} keeps attention layers in window "
                "groups, which give back the blocks behind a sequence's "
                "window: a block that was given back can be neither shared "
                "by a later prompt nor restored from the host KV tier, and "
                "the prefix cache and the tier hold whole prefixes. Host "
                "it on an engine with prefix_cache_size=0 and "
                f"host_tier_mb=0 (got prefix_cache_size={prefix_cache_size}"
                f", host_tier_mb={tier_bytes >> 20})")
        if model.chunks_only and tier_bytes:
            raise EnforceError(
                f"model {model.label} has no inject program: what the host "
                "KV tier keeps (an evicted block's rows, a parked "
                "session's) could never be put back. Host it on an engine "
                f"with host_tier_mb=0 (got {tier_bytes >> 20})")

    # -- blocks: a chain taken, grown, forked and given back ---------------
    @property
    def free_blocks(self):
        return self.pool.free_count

    def occupancy(self):
        return self.pool.stats()["occupancy"]

    def chain(self, req):
        """The blocks a request's whole sequence takes, prompt and answer
        (both known at ``submit``), where its admission reserves them: 0
        for a store that does not reserve, and for a beam or speculative
        request (a fork copies and shares blocks, a verify holds none:
        neither's footprint is a sum known here; they are served from what
        is promised to nobody)."""
        if (not self.reserves or req.beam is not None
                or req.draft_key is not None):
            return 0
        m = self._model
        return -(-min(len(req.prompt) + req.max_new, m.max_len)
                 // m.block_size)

    def admission_blocks(self, req):
        """What `acquire` would take from the free list for ``req``."""
        bs = self._model.block_size
        return self.chain(req) or (len(req.prompt) + bs - 1) // bs

    def covers(self, req, taken):
        """Whether ``req``'s chain can be promised beside ``taken`` blocks
        that this round's picks will take; if not it is held back. A chain
        that can NEVER fit goes on, to fail loudly at its admission."""
        need = self.chain(req)
        if ((need <= self.free_blocks - taken
             or need > self._model.num_blocks)
                and all(n <= pool.free_count or n > pool.num_blocks
                        for n, pool in zip(self._window_needs(need),
                                           self.window_pools))):
            return True
        self.hold_back(req)
        return False

    def _window_needs(self, chain):
        """What a request whose whole sequence takes ``chain`` blocks is
        promised in each window group: what it can hold there at once."""
        m = self._model
        return [min(chain, m.window_chunk_blocks(g))
                for g in m.window_groups]

    def hold_back(self, req):
        """The pool cannot cover ``req``'s chain yet: counted once a
        request, however many rounds it waits."""
        if not req.held_back:
            req.held_back = True
            self._metrics.incr("admissions_deferred")

    def acquire(self, req):
        """The prompt's block chain as a `SeqKV`, or None when the pool
        cannot give it now: under reservation the request is held back
        (its whole chain is promised or nothing is), else the pool is
        exhausted and the scheduler decides whom to park before it asks
        again. Raises only for what no pool of this size could hold."""
        m = self._model
        chain = self.chain(req)
        if chain > m.num_blocks:
            self._never_fits(
                f"the request's chain of {chain} blocks (prompt and answer)"
                f" can never fit a pool of {m.num_blocks}; shorten it or "
                "host the model with more blocks")
        for need, pool in zip(self._window_needs(chain), self.window_pools):
            if need > pool.num_blocks:
                self._never_fits(
                    f"the {need} blocks the request can hold at once in a "
                    f"window group can never fit its pool of "
                    f"{pool.num_blocks}; host the model with more blocks")
        if chain:
            if not self.pool.reserve(chain):
                self.hold_back(req)
                return None
            windows = self._promise_windows(chain)
            if windows is None:
                self.pool.release((), chain)
                self.hold_back(req)
                return None
            held = self.pool.reserved
            blocks, shared_len = self.pool.acquire_for_prompt(
                req.prompt, promised=chain)
            self._metrics.incr("reserved_admissions")
            self._metrics.incr("blocks_reserved", chain)
            # less what the prompt's blocks used up of the promise
            # (reserved moves on this thread alone)
            return SeqKV(m, blocks, shared_len,
                         chain - (held - self.pool.reserved), windows)
        blocks, shared_len = self.pool.acquire_for_prompt(req.prompt)
        if blocks is not None:
            return SeqKV(m, blocks, shared_len)
        if -(-len(req.prompt) // m.block_size) > m.num_blocks:
            self._never_fits(
                f"block pool exhausted ({self.pool.stats()['blocks_free']}"
                f" free of {m.num_blocks}) and the prompt alone can never "
                "fit; shorten the prompt or host the model with more blocks")
        return None

    def _promise_windows(self, chain):
        """A `WindowKV` a window group for a sequence of ``chain`` blocks,
        each promised what it can hold at once, or None, and nothing
        promised, where a group's pool cannot cover that now."""
        m = self._model
        windows = tuple(WindowKV(m, g, chain) for g in m.window_groups)
        for i, (w, pool) in enumerate(zip(windows, self.window_pools)):
            if not pool.reserve(w.reserve):
                for v, given in zip(windows[:i], self.window_pools):
                    given.release((), v.reserve)
                return None
            self._metrics.incr("kv_blocks_reserved_window", w.reserve)
        return windows

    def release_behind(self, kv, position):
        """Give back, in every window group, the blocks of ``kv`` that lie
        wholly behind the window of a query at ``position`` (the next one
        the sequence launches: the module docstring says why that is
        safe), and renew the promise for as many of them as the sequence
        has yet to open. Returns the blocks given back."""
        m = self._model
        bs, given = m.block_size, 0
        for g, w, pool in zip(m.window_groups, kv.windows,
                              self.window_pools):
            first = max(position - g.window + 1, 0) // bs
            n = min(first - w.first, len(w.blocks))
            if n <= 0:
                continue
            dead, w.blocks = w.blocks[:n], w.blocks[n:]
            w.first += n
            keep = min(w.limit - len(w.blocks), w.left) - w.reserve
            pool.recycle(dead, keep)
            w.reserve += keep
            w.remap()
            given += n
        if given:
            self._metrics.incr("kv_window_blocks_released", given)
        return given

    def open_windows(self, kv, stop):
        """Make every position below ``stop`` writable in every window
        group, out of the sequence's promise there (which `acquire` sized
        so that it cannot run out once `release_behind` has run for the
        launch's first position)."""
        bs = self._model.block_size
        for w, pool in zip(kv.windows, self.window_pools):
            need = -(-stop // bs) - w.first - len(w.blocks)
            if need <= 0:
                continue
            if need > w.reserve:
                raise RuntimeError(
                    f"a window group's promise of {w.limit} blocks does "
                    f"not cover {need} more beside {len(w.blocks)} live")
            w.blocks += pool.open_promised(need)
            w.reserve -= need
            w.left -= need
            w.remap()

    def _never_fits(self, why):
        self._metrics.incr("blocks_exhausted")
        self._metrics.incr("blocks_failed_total")
        raise RuntimeError(why)

    def acquire_rows(self, n):
        """Fresh private blocks for rows ``[0:n)`` that the caller puts
        back (`restore`), or None when the pool cannot cover them."""
        blocks = self.pool.acquire_rows(n)
        return None if blocks is None else SeqKV(self._model, blocks)

    def open_block(self, kv, cursor):
        """Make position ``cursor`` writable: a fresh block where it opens
        one (out of the sequence's reservation, if it holds one), a
        copy-on-write where it lands in a SHARED partial tail (the shared
        rows re-injected into the private copy), an unregistration where
        the sequence owns a registered partial alone. False where the pool
        is empty (nothing changed; the scheduler parks, drains or
        rejects). RuntimeError on a pool invariant violation,
        `ArenaInvalidError` where the copy-on-write's inject failed."""
        if kv.windows:
            self.open_windows(kv, cursor + 1)
        blocks, nb, cow = self.pool.ensure_appendable(
            kv.blocks, cursor, promised=kv.reserve > 0)
        if blocks is None:
            return False
        kv.blocks = blocks
        if nb is None:
            return True
        if kv.reserve:
            kv.reserve -= 1
        if cow is not None:
            self.write_rows(cow.block, 0, cow.size_used, cow.host_rows,
                            "decode::cow_inject")
        kv.remap()
        return True

    def note_append(self, kv, cursor):
        """One row landed at position ``cursor`` (host bookkeeping)."""
        self.pool.note_append(kv.blocks[cursor // self._model.block_size])

    def fork(self, parent, cursor):
        """A second owner for ``parent``'s first ``cursor`` positions (a
        beam fork): its full blocks shared, its partial tail copied into a
        private block, arena to arena through the host."""
        blocks, nb, src = self.pool.fork_blocks(parent.blocks, cursor)
        if blocks is None:
            raise RuntimeError("block pool exhausted forking a beam")
        if nb is not None:
            rows, _ = self.read_block(src, nb.size_used)
            self.write_rows(nb, 0, nb.size_used, rows,
                            "decode::beam_fork_inject")
        return SeqKV(self._model, blocks, parent.shared_len)

    def release(self, kv):
        """Give back a sequence's blocks and the unopened part of its
        reservation. Registered blocks stay cached for the next prompt."""
        if kv is None:
            return
        if kv.blocks:
            self.pool.release(kv.blocks, kv.reserve)
        for w, pool in zip(kv.windows, self.window_pools):
            pool.release(w.blocks, w.reserve)
            w.blocks, w.reserve = [], 0

    # -- rows: the one write and the one read -------------------------------
    def write_rows(self, target, lo, hi, source, span, fault=None, **attrs):
        """THE inject: put ``source`` at the arena rows of ``target``'s
        positions ``[lo:hi)`` (a `SeqKV`; or a `Block`, whose offsets they
        are). ``source`` is host rows for exactly those positions, one
        ``(k, v)`` per state pair, or a one-shot prefill's host copy (ONE
        ``[2 * pairs, P, H]`` array from position 0), each padded to the
        program's ``[1, L, H]``; or the prefill program's K/V outputs
        themselves, which stay on the device. Every other row of the feed
        names the sentinel and lands nowhere. Launched under a span named
        ``span`` (None: the caller's own) that carries ``attrs``;
        ``fault`` names a fault site fired inside it."""
        m = self._model
        inj_rows = np.full((m.max_len,), m.rows, dtype="int64")
        inj_rows[lo:hi] = (target.row_map[lo:hi] if isinstance(target, SeqKV)
                           else target.row0 + np.arange(lo, hi))
        names = [n for pair in m.inject_kv_feeds for n in pair]
        if isinstance(source, np.ndarray):
            source = [(source[i, lo:hi], source[i + 1, lo:hi])
                      for i in range(0, len(source), 2)]
        if isinstance(source[0], tuple):
            padded = []
            for rows in (r for pair in source for r in pair):
                arr = np.zeros((1, m.max_len, m.hidden), "float32")
                arr[0, lo:hi] = rows
                padded.append(arr)
            source = padded
        feeds = dict(zip(names, source))
        feeds[DecodeModel.INJ_ROWS] = inj_rows
        try:
            if span is None:
                self._run("inject", feeds, None)
                return
            with profiler.RecordEvent(span) as ev:
                if fault is not None:
                    faults.fire(fault)
                if ev.span is not None and attrs:
                    ev.span.set(**attrs)
                self._run("inject", feeds, ev.span)
        except Exception as e:
            raise ArenaInvalidError(str(e)) from e

    def _read(self, pick):
        """``pick(arena)`` of every arena, per entry of ``state_names`` (a
        K and V pair, or a latent cache's one), and the
        bytes brought to the host for it: each arena WHOLE, whatever is
        picked. They are fetches (``serving_fetched_bytes_total``) and are
        counted in ``serving_arena_read_bytes_total`` besides."""
        out, nbytes = [], 0
        scope = self._scope()
        for names in self._model.state_names:
            arenas = [self._fetch(scope.find_var(n)) for n in names]
            nbytes += sum(a.nbytes for a in arenas)
            out.append(tuple(np.array(pick(a)) for a in arenas))
        self._metrics.incr("arena_read_bytes", nbytes)
        return out, nbytes

    def read_rows(self, kv, n):
        """A sequence's rows ``[0:n)`` off the live arena, one ``(k, v)``
        per state pair, and the bytes of arena brought over for them."""
        idx = np.asarray(kv.row_map[:n], dtype=np.int64)
        return self._read(lambda a: a[idx])

    def read_block(self, block, n=None):
        """The first ``n`` rows of one block (all it uses), likewise."""
        n = block.size_used if n is None else n
        return self._read(lambda a: a[block.row0:block.row0 + n])

    def _writeback(self, block):
        """The pool's reader at LRU eviction (inside ``decode.blocks``,
        before the evictee's rows can be overwritten by its successor): a
        ``decode::writeback`` span, with the bytes it brought over."""
        with _span("decode::writeback") as sp:
            rows, nbytes = self.read_block(block)
            if sp is not None:
                sp.set(block=block.id, rows=block.size_used, bytes=nbytes)
        return rows

    def host_rows(self, outs, n):
        """Rows ``[0:n)`` per state pair of the prefill program's K/V
        outputs ``outs`` (device arrays), each fetched whole."""
        kv = [self._fetch(o) for o in outs]
        return [(kv[i][0, :n], kv[i + 1][0, :n])
                for i in range(0, len(kv), 2)]

    # -- the host tier: spill, restore, evicted prefixes ---------------------
    def spill(self, kv, n, owner, rank, tokens):
        """Rows ``[0:n)`` of a parked sequence to the host tier. Returns
        ``(key, bytes read)``; the key is None where the tier cannot take
        them. The blocks stay the caller's to `release`."""
        key = f"park:{owner}:{rank}"
        rows, nbytes = self.read_rows(kv, n)
        if not self.tier.put(key, rows, n, tokens=tokens):
            key = None
        return key, nbytes

    def drop_spilled(self, keys):
        """Spilled rows nobody will ask back (a parked session dropped)."""
        for key in keys:
            self.tier.discard(key)

    def restore(self, kv, key, n, recompute):
        """Put a resumed sequence's rows ``[0:n)`` back. The tier entry is
        consumed if present and CRC-clean; otherwise (evicted or
        quarantined) the rows come from ``recompute()``, the prefill
        program's K/V outputs over the committed tokens: byte-identical."""
        ent = self.tier.pop(key)
        if ent is not None and ent.size_used == n:
            rows = ent.kv_rows
        else:
            rows = self.host_rows(recompute(), n)
            self._metrics.incr("resume_replays")
        self.write_rows(kv, 0, n, rows, None)

    def restore_prefix(self, kv, prompt, request):
        """Chunked admission's host-tier fast path: contiguous full prompt
        blocks just past the radix-shared prefix whose rows were written
        back at eviction are re-injected instead of prefilled again.
        Returns the prompt position covered through (0: none). Only from a
        block boundary: a shared partial tail occupies the next block."""
        bs = self._model.block_size
        if kv.shared_len % bs != 0:
            return 0
        hashes = block_hashes(prompt, bs)
        start = idx = kv.shared_len // bs
        ents = []
        while idx < len(hashes) and (idx + 1) * bs <= len(prompt):
            ent = self.tier.get("blk:" + hashes[idx])
            if ent is None or ent.size_used != bs:
                break
            ents.append(ent)
            idx += 1
        if not ents:
            return 0
        rows = [tuple(np.concatenate([e.kv_rows[i][j] for e in ents])
                      for j in (0, 1)) for i in range(len(ents[0].kv_rows))]
        self.write_rows(kv, start * bs, idx * bs, rows, "decode::inject",
                        request=request)
        self._metrics.incr("tier_hits", len(ents))
        return idx * bs

    # -- sharing: the radix index and the whole-prompt cache ----------------
    def register(self, kv, prompt, live=None):
        """Index a prompt's freshly written blocks so later prompts share
        them; its partial tail too where ``live`` (the one-shot prefill's
        host copy) can back a copy-on-write. A recurrent model's blocks
        are never shared: its state is no function of them. Nor are those
        of a model with window groups: the rows a later prompt would need
        there may have been given back."""
        if self._model.recurrent or self._model.window_groups:
            return
        host_rows = None
        if live is not None:
            def host_rows(start, stop):
                return [(np.array(live[i, start:stop]),
                         np.array(live[i + 1, start:stop]))
                        for i in range(0, len(live), 2)]
        self.pool.register_prompt_blocks(kv.blocks, prompt,
                                         host_rows=host_rows)

    def prefix_get(self, prompt):
        """``(key, entry)`` of the whole-prompt cache: the entry is a
        one-shot prefill's host copy ``(live rows, logits row)`` or None."""
        key = prompt_key(prompt)
        return key, self.prefix.get(key)

    def prefix_put(self, key, live, logits_row):
        self.prefix.put(key, live, logits_row)

    # -- relaunch, observability --------------------------------------------
    def reset(self):
        """Zero every arena and empty the pool (a failed donated call left
        the old buffers invalid). The tier and the prefix cache hold host
        rows, which stay true."""
        import jax
        import jax.numpy as jnp

        m = self._model
        scope = self._scope()
        sized = [(m.state_names, m.rows)] + [
            (g.state_names, g.num_blocks * m.block_size)
            for g in m.window_groups]
        for names, rows in sized:
            for n in (n for pair in names for n in pair):
                scope.set(n, jax.device_put(
                    jnp.zeros((rows, m.kv_width), m.kv_dtype), self._device))
        self.pool.reset()
        for pool in self.window_pools:
            pool.reset()

    def stats(self):
        pool = self.pool.stats()
        return {
            **({"window_pools": [p.stats() for p in self.window_pools]}
               if self.window_pools else {}),
            "block_pool": pool,
            "block_dedup_ratio": pool["dedup_ratio"],
            "prefix_cache_entries": len(self.prefix),
            "prefix_hits": self.prefix.hits,
            "prefix_misses": self.prefix.misses,
            "host_tier": self.tier.stats(),
        }
