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

**Layer groups.** A model's attention layers fall into groups that keep
different rows (model.py ``KVGroup``), each with arenas and a `BlockPool` of
its own (``KVStore.pools``, in the model's order), and a sequence has a
footing in each (``SeqKV.groups``). A group whose queries see the whole
context takes a prompt's blocks at `acquire`, may share them and holds them
to the end. A group with a ``window`` opens its blocks as the launches reach
them and holds only those with a position inside the window of the
sequence's NEXT query: `KVStore.release_behind` gives the others back. When:
the scheduler calls it for position ``p`` right before it builds the feeds
of the launch whose first query stands at ``p`` (the step of the token at
``p``, the chunk that starts at ``p``). Every launch that read the blocks
given back was made before that, and whoever is handed them next writes them
in a launch made after it; the device runs launches in the order they were
made (a step launched ahead included: PR 42's launch-ahead keeps ONE order
of launches, it only fetches later), so a row is overwritten only after its
last reader ran. Nothing approximates: a row inside a query's window is in
a live block, a row outside it is masked (the oldest live block's) or
absent. Admission promises a request, a group, what it can ever hold there
at once, and a block given back renews the promise for the blocks still to
be opened.

**What a store refuses to carry** is said once, by `KVStore.check_carries`,
from four facts the model states of its state and one its groups do. Rows
that are rewritten until a block is committed (``fills_blocks``) are no
function of the token prefix yet; a per-slot ``recurrent`` state is no
function of rows at all; rows that a group with a window gave back are
gone; an indexer's keys (``index_names``) lie in an arena that neither the
cache nor the tier reads: none of these may sit in the prefix cache or the
host tier, which key on K/V rows and hold whole prefixes, and the latter
three are never registered for sharing (``shares``). A model without an
inject program (``chunks_only``) could never take rows back: it gets no
tier, its sessions never spill, and nothing forks or re-derives its rows
(``restores``). A store with a windowed group always reserves.
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
__all__ = ["ArenaInvalidError", "KVStore", "SeqKV", "SlotPool"]


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
    """The storage state of ONE sequence on ONE entry: its footing in the
    model's first layer group, and through ``groups`` in every group (this
    one first). ``blocks`` is the LIVE part of the footing's block chain
    and ``first`` the index, in the whole chain, of ``blocks[0]`` (0 unless
    the group has a ``window``); ``row_map`` and ``table`` (what the chunk
    and inject programs and the decode step are fed) start at that block,
    so position ``p`` lies at ``row_map[p - base]``.
    ``shared_len`` is the positions that radix-shared blocks already hold,
    never to be rewritten; ``reserve`` what is left of the footing's
    reservation, blocks promised and not yet opened; ``life`` the blocks of
    the whole sequence where they were promised and ``limit`` the most of
    them the footing holds at once. With ``run`` (the blocks a paged kernel
    copies in ONE descriptor where the table names them side by side:
    ``kernels.attention.paged_run_blocks``) ``runs[g]`` is how many blocks
    of the table's first ``g`` aligned groups of ``run`` entries lie in such
    runs, by the kernel's own rule."""

    __slots__ = ("blocks", "first", "row_map", "table", "reserve", "life",
                 "limit", "shared_len", "window", "runs", "_more", "_bs",
                 "_rows", "_run", "_runs_first")

    def __init__(self, model, group, life=0, blocks=None, shared_len=0,
                 run=0):
        self._bs = model.block_size
        self._rows = group.num_blocks * model.block_size
        self._run = run
        self.runs, self._runs_first = [0], 0
        self.window = group.window
        self.blocks, self.first = blocks or [], 0
        self.shared_len = shared_len
        self.life = life
        self.limit = self.reserve = min(life, model.window_chunk_blocks(group))
        self.row_map = np.zeros(model.chunk_rows(group), "int64")
        self.table = np.zeros(model.window_table_blocks(group), "int32")
        self._more = ()
        self.remap()

    @property
    def groups(self):
        """The sequence's footing in every layer group, this one first."""
        return (self, *self._more)

    @property
    def base(self):
        """The position of ``row_map[0]``: the first live block's first."""
        return self.first * self._bs

    @property
    def left(self):
        """Blocks of its ``life`` the footing has yet to open."""
        return self.life - self.first - len(self.blocks)

    def remap(self):
        """``row_map`` and ``table`` after ``blocks`` or ``first`` changed.
        What lies past the blocks is left as it was, and is never read."""
        rows = _block_rows(self.blocks, self._bs)[:len(self.row_map)]
        self.row_map[:len(rows)] = rows
        n = min(len(self.blocks), len(self.table))
        self.table[:n] = rows[:n * self._bs:self._bs] // self._bs
        if self._run:
            self._count_runs(n // self._run)

    def _count_runs(self, whole):
        """``runs`` for the table's first ``whole`` groups. A chain changes
        at its tail alone (a block opened, the tail copied on write) unless
        its first live block moved, so the groups counted before the last
        one stand: a step that opens a block pays for one group, not for
        the chain."""
        run, runs, blocks = self._run, self.runs, self.blocks
        keep = (max(min(len(runs) - 2, whole), 0)
                if self._runs_first == self.first else 0)
        self._runs_first = self.first
        del runs[keep + 1:]
        for g in range(keep, whole):
            ids = [b.id for b in blocks[g * run:(g + 1) * run]]
            runs.append(runs[-1] + run * (
                ids == list(range(ids[0], ids[0] + run))))

    def run_blocks(self, live):
        """Of the footing's first ``live`` blocks, those a paged kernel
        copies as part of a run: the blocks of the aligned groups that are
        wholly among them and flagged (`remap`)."""
        runs = self.runs
        g = live // self._run if self._run else 0
        return runs[g] if g < len(runs) else runs[-1]

    def row_of(self, p):
        bs = self._bs
        return self.blocks[p // bs - self.first].row0 + p % bs

    def chunk_write_rows(self, start, stop, width):
        """The chunk program's ``[width]`` write rows in this group for
        positions ``[start:stop)``: a shared position and the padding write
        nowhere."""
        return _chunk_rows(self.row_map, self.base,
                           max(start, self.shared_len), start, stop, width,
                           self._rows)


class KVStore:
    """One entry's K/V storage. ``run(kind, feeds, span)`` launches one of
    the entry's programs, ``fetch(value)`` brings a device value to the
    host and counts it, ``scope()`` is where the arenas live: launch and
    scope stay the entry's. All of it runs on the entry's scheduler thread
    (a draft entry's: under its ``decode.draft`` lock); `stats` may be read
    from any."""

    def __init__(self, model, tier_bytes, prefix_cache_size, metrics, run,
                 fetch, scope, device):
        from paddle_tpu.kernels.attention import (
            paged_copy_unit, paged_run_blocks,
        )

        self._model = model
        self._metrics = metrics
        self._run = run
        self._fetch = fetch
        self._scope = scope
        self._device = device
        # a pool a layer group, in the model's order; what happens in a
        # windowed group's is counted here and not by the pool
        self.pools = [
            BlockPool(g.num_blocks, model.block_size,
                      count=metrics.incr if g.window is None else None)
            for g in model.groups]
        # the groups that give back what lies behind a window, by index
        self.windowed = [i for i, g in enumerate(model.groups)
                         if g.window is not None]
        self.prefix = PrefixCache(prefix_cache_size)
        # the pool writes registered blocks back to the tier at LRU
        # eviction (decode.blocks -> decode.tier), through `_writeback`
        self.tier = HostKVTier(capacity_bytes=tier_bytes)
        if tier_bytes:
            self.pool.attach_tier(self.tier, read_rows=self._writeback)
        self.shares, self.restores = self.check_carries(
            model, tier_bytes, prefix_cache_size)
        self.reserves = (not tier_bytes and (
            model.num_blocks < model.slots * model.blocks_per_slot
            or bool(self.windowed)))
        # blocks the paged-attention kernel copies as one unit at this
        # geometry (0: no kernel serves it), to count a step's units
        self.copy_unit = paged_copy_unit(
            model.block_size, model.blocks_per_slot, model.kv_width,
            model.kv_dtype, model.arenas // len(model.state_names))
        # blocks of a copy unit that go in ONE descriptor where a slot's
        # table names them side by side (0: every block alone), to count
        # a step's run blocks
        self.run_blocks = paged_run_blocks(self.copy_unit, model.num_blocks)

    @property
    def pool(self):
        """The first group's pool: the one that shares and spills."""
        return self.pools[0]

    @property
    def window_pools(self):
        """The pools of the groups after the first."""
        return self.pools[1:]

    @staticmethod
    def check_carries(model, tier_bytes=0, prefix_cache_size=0):
        """What the model's state lets a store do, as ``(shares,
        restores)``: whether a prompt's blocks may be registered for later
        prompts to share, and whether rows can be put back (a session
        spills, a beam forks, a verify re-derives them). Raises, before
        anything is built, where a store of these sizes would have to
        carry what such a state cannot give it."""
        from paddle_tpu.serving.request import ServingError
        from paddle_tpu.utils.enforce import EnforceError

        sizes = (f"prefix_cache_size={prefix_cache_size}, "
                 f"host_tier_mb={tier_bytes >> 20})")
        both = ("Host it on an engine with prefix_cache_size=0 and "
                "host_tier_mb=0 (got " + sizes)
        if model.fills_blocks and (prefix_cache_size or tier_bytes):
            raise ServingError(
                f"model {model.label} fills its answer a block of "
                f"{model.block_len} positions at a time: a block's K/V "
                "rows are rewritten by every pass and final only once it "
                "is committed, so neither the prefix cache nor the host KV "
                "tier may hold them. " + both)
        if model.recurrent and (prefix_cache_size or tier_bytes):
            raise EnforceError(
                f"model {model.label} keeps per-slot recurrent state, "
                "which the prefix cache and the host KV tier cannot carry: "
                "both key on K/V rows, a function of the token prefix "
                "alone, and hold no snapshot of a state. " + both)
        if model.window_groups and (prefix_cache_size or tier_bytes):
            raise EnforceError(
                f"model {model.label} keeps attention layers in window "
                "groups, which give back the blocks behind a sequence's "
                "window: a block that was given back can be neither shared "
                "by a later prompt nor restored from the host KV tier, and "
                "the prefix cache and the tier hold whole prefixes. " + both)
        if model.index_names and (prefix_cache_size or tier_bytes):
            raise EnforceError(
                f"model {model.label} keeps an indexer's keys in an arena "
                "of their own beside K and V: the prefix cache and the host "
                "KV tier hold K and V rows alone, so a prefix taken from "
                "either would come back without the keys its rows are "
                "chosen by. " + both)
        if model.chunks_only and tier_bytes:
            raise EnforceError(
                f"model {model.label} has no inject program: what the host "
                "KV tier keeps (an evicted block's rows, a parked "
                "session's) could never be put back. Host it on an engine "
                f"with host_tier_mb=0 (got {tier_bytes >> 20})")
        return (not (model.recurrent or model.window_groups
                     or model.index_names),
                not model.chunks_only)

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
        """Whether ``req``'s chain can be promised, in every group, beside
        ``taken`` blocks of whole chains that this round's picks will take
        (of a group that holds whole chains); if not it is held back. A
        chain that can NEVER fit goes on, to fail loudly at its
        admission."""
        if all(n <= pool.free_count - (taken if g.window is None else 0)
               or n > pool.num_blocks
               for g, n, pool in zip(self._model.groups,
                                     self._needs(self.chain(req)),
                                     self.pools)):
            return True
        self.hold_back(req)
        return False

    def _needs(self, chain):
        """What a request whose whole sequence takes ``chain`` blocks is
        promised in each group: what it can hold there at once."""
        m = self._model
        return [min(chain, m.window_chunk_blocks(g)) for g in m.groups]

    def hold_back(self, req):
        """The pool cannot cover ``req``'s chain yet: counted once a
        request, however many rounds it waits."""
        if not req.held_back:
            req.held_back = True
            self._metrics.incr("admissions_deferred")

    def acquire(self, req):
        """The prompt's footing in every group as a `SeqKV`, or None when
        a pool cannot give it now: under reservation the request is held
        back (its whole chain is promised in every group or nothing is),
        else the pool is exhausted and the scheduler decides whom to park
        before it asks again. Raises only for what no pool of this size
        could hold."""
        m = self._model
        chain = self.chain(req)
        needs = self._needs(chain)
        for g, need, pool in zip(m.groups, needs, self.pools):
            if need > pool.num_blocks:
                self._never_fits(
                    f"the {need} blocks the request (prompt and answer) "
                    f"holds at once in layer group {g.name!r} can never "
                    f"fit its pool of {pool.num_blocks}; shorten it or "
                    "host the model with more blocks")
        if chain and not self._promise(needs):
            self.hold_back(req)
            return None
        held = self.pool.reserved
        blocks, shared_len = self.pool.acquire_for_prompt(
            req.prompt, promised=chain)
        if blocks is None:
            if -(-len(req.prompt) // m.block_size) > m.num_blocks:
                self._never_fits(
                    "block pool exhausted "
                    f"({self.pool.stats()['blocks_free']} free of "
                    f"{m.num_blocks}) and the prompt alone can never fit; "
                    "shorten the prompt or host the model with more blocks")
            return None
        kv = self._seq(blocks, shared_len, chain)
        # less what the prompt's blocks used up of the promise (reserved
        # moves on this thread alone)
        kv.reserve -= held - self.pool.reserved
        return kv

    def _promise(self, needs):
        """Promise a sequence ``needs`` blocks, a group; False, and nothing
        promised, where a group's pool cannot cover that now."""
        for i, (need, pool) in enumerate(zip(needs, self.pools)):
            if not pool.reserve(need):
                for given, back in zip(self.pools[:i], needs):
                    given.release((), back)
                return False
        self._metrics.incr("reserved_admissions")
        self._metrics.incr("blocks_reserved", needs[0])
        for i in self.windowed:
            self._metrics.incr("kv_blocks_reserved_window", needs[i])
        return True

    def _seq(self, blocks, shared_len=0, chain=0):
        """A sequence's footing in every group, ``blocks`` its chain in the
        first, each promised what `_needs` says of a ``chain``."""
        m = self._model
        kv = SeqKV(m, m.groups[0], chain, blocks, shared_len,
                   self.run_blocks)
        kv._more = tuple(SeqKV(m, g, chain) for g in m.groups[1:])
        return kv

    def release_behind(self, kv, position, group):
        """Give back, in windowed group ``group`` (an index of
        ``windowed``), the blocks of ``kv`` that lie wholly behind the
        window of a query at ``position`` (the next one the sequence
        launches: the module docstring says why that is safe), and renew
        the promise for as many of them as the sequence has yet to open.
        Returns the blocks given back."""
        w = kv.groups[group]
        first = max(position - w.window + 1, 0) // self._model.block_size
        n = min(first - w.first, len(w.blocks))
        if n <= 0:
            return 0
        dead, w.blocks = w.blocks[:n], w.blocks[n:]
        w.first += n
        keep = min(w.limit - len(w.blocks), w.left) - w.reserve
        self.pools[group].recycle(dead, keep)
        w.reserve += keep
        w.remap()
        self._metrics.incr("kv_window_blocks_released", n)
        return n

    def open_windows(self, kv, stop):
        """Make every position below ``stop`` writable in every windowed
        group, out of the sequence's promise there (which `acquire` sized
        so that it cannot run out once `release_behind` has run for the
        launch's first position)."""
        bs = self._model.block_size
        for i in self.windowed:
            w = kv.groups[i]
            need = -(-stop // bs) - w.first - len(w.blocks)
            if need <= 0:
                continue
            if need > w.reserve:
                raise RuntimeError(
                    f"a window group's promise of {w.limit} blocks does "
                    f"not cover {need} more beside {len(w.blocks)} live")
            w.blocks += self.pools[i].open_promised(need)
            w.reserve -= need
            w.remap()

    def _never_fits(self, why):
        self._metrics.incr("blocks_exhausted")
        self._metrics.incr("blocks_failed_total")
        raise RuntimeError(why)

    def acquire_rows(self, n):
        """Fresh private blocks for rows ``[0:n)`` that the caller puts
        back (`restore`), or None when the pool cannot cover them."""
        blocks = self.pool.acquire_rows(n)
        return None if blocks is None else self._seq(blocks)

    def open_block(self, kv, cursor):
        """Make position ``cursor`` writable: a fresh block where it opens
        one (out of the sequence's reservation, if it holds one), a
        copy-on-write where it lands in a SHARED partial tail (the shared
        rows re-injected into the private copy), an unregistration where
        the sequence owns a registered partial alone. False where the pool
        is empty (nothing changed; the scheduler parks, drains or
        rejects). RuntimeError on a pool invariant violation,
        `ArenaInvalidError` where the copy-on-write's inject failed."""
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
        return self._seq(blocks, parent.shared_len)

    def release(self, kv):
        """Give back a sequence's blocks and the unopened part of its
        reservation. Registered blocks stay cached for the next prompt."""
        if kv is None:
            return
        for w, pool in zip(kv.groups, self.pools):
            pool.release(w.blocks, w.reserve)
            if w.window is not None:
                # private blocks, freed at once: given back but once
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
        host copy) can back a copy-on-write. Not where the state lets
        nothing be shared (`check_carries`)."""
        if not self.shares:
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
        for g, pool in zip(m.groups, self.pools):
            for n in (n for pair in g.state_names for n in pair):
                scope.set(n, jax.device_put(
                    jnp.zeros((pool.rows, m.kv_width), m.kv_dtype),
                    self._device))
            pool.reset()
        for n in m.index_names:
            scope.set(n, jax.device_put(
                jnp.zeros((self.pool.rows, m.index_width), m.kv_dtype),
                self._device))

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
