"""DecodeModel: the fixed-shape program contract of the paged decode engine.

A generation model is served through THREE (optionally FOUR) fixed-shape
programs that share one scope (weights by name) and one **paged KV
arena**: per layer, one flat persistable ``[R, H]`` row matrix per K and
per V, where ``R = num_blocks * block_size``. Block tables live on the
host (serving/decode/pool.py); programs see only **row-index feeds** (the
decode step: a block table it expands itself), so
memory scales with *used* tokens while every compiled shape stays
static:

* **decode step** — the per-iteration hot path. ONE static shape and,
  from the host, ONE small feed: ``dec_step``, int32
  ``[S, 4 + ceil(L / block_size)]`` — a slot's token (-1: the one in
  ``dec_token``), its position, its attention length (position + 1; 0 for
  a slot that does not step), its write row (``R`` = "write nowhere",
  dropped — retired slots are bit-invisible, admitted slots join
  mid-flight, and the compiled executable never sees the batch change),
  then its block table (block ids, 0 past the last block).
  ``dec_token`` ``[S, 1]`` is the tokens of the slots that carry -1: the
  step before's own output, handed over on the device. The program's
  first op (``paged_step_feeds``) makes of the two what the layers read:
  token and position ``[S, 1]``, the attention bias ``[S, 1, L]`` (0.0
  below a slot's length, ``-1e9`` from it on), the gather row map
  ``[S * L]`` (position ``p`` of slot ``s`` reads arena row
  ``rows[s * L + p]`` = ``table[s, p // bs] * bs + p % bs``) and the
  scatter rows ``[S]``. Arenas are DONATED through core/lowering.py: the
  scatter is an in-place device update.
  Two outputs: the float32 logits ``[S, 1, V]`` and, chosen on the
  device from those same logits, ``next_token [S, 1]`` (their argmax
  over the vocabulary). The engine brings the tokens alone to the host
  on a step whose slots are all greedy, the logits on every other.
* **prefill** — whole-prompt forward at ``[1, L]`` with a causal
  additive bias (a constant the engine puts on the device once), giving
  per-layer K/V rows ``[1, L, H]`` and logits ``[1, L, V]``. Stateless
  (donation off), and its outputs STAY on the device: an admission
  feeds the K/V outputs to the inject program as they are and brings to
  the host the one ``[V]`` logits row at the prompt's last position and
  the rows a one-shot prompt can fill, ``[2 * layers, P, H]`` in one
  fetch (P = ``chunk_tokens`` where a chunk program takes every longer
  prompt, else ``L``). That host copy is what the prefill cache keeps
  and what backs the copy-on-write bytes of shared partial blocks.
* **inject** — scatters up to ``L`` prefill K/V rows into arbitrary
  arena rows by a row map ``[L]`` (rows >= ``R`` dropped); its K/V feeds
  are the prefill program's outputs on a prefill-cache miss, host rows
  padded to ``[1, L, H]`` on a hit, a resume or a copy-on-write.
  Shared-prefix admissions inject ONLY their non-shared suffix — shared
  blocks already hold byte-identical rows.
* **chunk prefill** (built when ``chunk_tokens`` is set) — ``[1, C]``
  prompt chunk against the paged arena: scatters the chunk's own K/V
  rows, gathers the full ``[L]`` context view back, and attends under a
  bias that opens exactly the causal prefix, made on the device from the
  chunk's two integers (``chu_span``: its first position and its count of
  real positions; ``chunk_mask_bias``). Long prompts
  stream through it one budgeted chunk per engine iteration instead of
  stalling the decode batch.

All shapes are static, so a warmed engine holds exactly three (four
with chunking) executables and can never retrace. Every parameter,
feed, and arena var name is derived from the ``(name, version)`` prefix
— content-identical rebuilds (circuit-breaker relaunch, a cold replica)
re-derive identical programs and hit the compile cache instead of
recompiling.

Exactness: gather/scatter move rows byte-for-byte and the additive
``-1e9`` bias zeroes masked positions exactly, so paged decode is
bit-identical to the dense slotted design for any block size — the
degenerate geometry ``block_size=max_len, num_blocks=slots`` IS the
PR 10 slotted arena.
"""

import numpy as np

__all__ = ["DecodeModel", "KVGroup", "build_decoder_model"]

# additive-mask value: exp(-1e9) underflows to exactly 0.0 (the repo-wide
# padding contract), so masked cache positions are bit-invisible
NEG_INF = -1e9


def window_table_blocks(window, block_size, blocks_per_slot):
    """Blocks a slot can hold live in a window group when it STEPS at
    ``p``: from the block of ``p - window + 1`` to the block of ``p``."""
    return min(blocks_per_slot, (window + block_size - 2) // block_size + 1)


def window_chunk_blocks(window, chunk_tokens, block_size, blocks_per_slot):
    """Blocks a slot can hold live in a window group while a CHUNK runs:
    from the block of ``start - window + 1`` to the block of the chunk's
    last position."""
    return min(blocks_per_slot,
               (window + chunk_tokens + block_size - 3) // block_size + 1)


class KVGroup:
    """One group of a model's attention layers: its arenas (``state_names``,
    as ``DecodeModel.state_names``: ``[num_blocks * block_size, kv_width]``
    each) and the ``num_blocks`` of its block pool. ``window`` is None where
    the group's queries see the whole context, else the positions they see
    (their own among them): a sequence then holds in the group only the
    blocks with a position inside the window of its next query, and the
    programs read them through a table and a row map that start at the
    sequence's first LIVE block (kvstate.py ``SeqKV``;
    ``DecodeModel.fill_step``, ``chunk_feeds``)."""

    __slots__ = ("name", "state_names", "num_blocks", "window")

    def __init__(self, name, state_names, num_blocks, window):
        self.name = str(name)
        self.state_names = [tuple(names) for names in state_names]
        self.num_blocks = int(num_blocks)
        self.window = None if window is None else int(window)
        if self.num_blocks < 1 or (window is not None and self.window < 1):
            raise ValueError(f"group {name}: window {window} and num_blocks "
                             f"{num_blocks} have to be positive")


class DecodeModel:
    """The paged programs + their naming contract and geometry.

    ``state_names`` lists per-layer ``(k_arena, v_arena)`` var names
    (each ``[R, H]``), or a 1-tuple ``(arena,)`` where a layer's cache is
    ONE arena (a latent cache: a token's row is its key and, in its first
    lanes, its value); ``prefill_kv_fetches`` the matching per-layer
    ``(k_rows, v_rows)`` fetch names of the prefill program. ``builder``
    (optional) is a zero-arg callable that re-creates a content-identical
    DecodeModel — the circuit breaker's relaunch path uses it to rebuild
    a replica that warms entirely from the compile cache.

    A model may keep state of a second kind (``slot_states``: ``(name,
    shape, dtype)`` of arrays whose first axis is the SLOT, not the arena
    row): the recurrent state of a state-space layer, advanced in place by
    the chunk and step programs and reset by a prompt's first chunk. Such
    a model has no stateless prefill and no inject program (both None):
    every prompt streams through the chunk program, which is also fed the
    slot's index (``CHU_SLOT``), and K/V rows alone can neither be shared
    by prefix nor parked (``GenerationEngine.register_model`` refuses a
    prefix cache or a host tier for it). ``kv_width`` / ``kv_dtype`` are
    the arenas' row width and dtype where they are not ``hidden`` /
    float32 (grouped-query attention in bfloat16). ``counts_fetch`` names
    an int32 vector, the step's ``[S]`` tokens then one integer per
    ``count_names``: what a greedy step hands the host in its ONE fetch,
    the integers added to the counters of those names. ``state_names``
    is one pair per state the attention layers keep, a layer's or, where a
    stack runs ``passes`` times a token with rows of its own each time, a
    (pass, layer)'s; ``passes`` is what the launch spans say of it.

    The decode step fetches ``[logits_fetch, token_fetch]``:
    ``logits_fetch`` names the float32 ``[S, 1, V]`` logits (mask added
    when ``logits_mask``), ``token_fetch`` the ``[S, 1]`` integers that
    the program's own ``arg_max`` takes from them: the first index of
    each row's maximum, what ``np.argmax`` gives the host over the same
    row. A hand-built model without such a var leaves it None, and the
    engine then fetches the logits on every step.

    The decode step's feeds: ``DEC_STEP`` (`step_feed`, `fill_step`,
    `block_table`: the one array the host builds and puts a step),
    ``DEC_TOKEN`` (``[S, 1]``, a device array: the step before's tokens
    where ``DEC_STEP`` says -1) and, with ``logits_mask``, ``DEC_MASK``.
    The program's ``paged_step_feeds`` op turns the first two into the
    tokens, positions, bias, row map and write rows its layers read.

    **A model that generates by filling blocks** (``block_len`` B > 1,
    ``mask_token``): positions come in blocks of B; a position sees every
    earlier block and the WHOLE of its own, prompt and answer alike
    (`chunk_bias` states the rule). The decode program is then a BLOCK PASS: it runs the B
    positions of each stepping slot's current block (a position not yet
    decided holds ``mask_token``), rewrites their K/V rows, and decides ONE
    more position a slot, the one its own confidence ranks first; a pass
    that finds its block whole (the COMMIT pass) leaves the rows that stay
    and opens the next block. So a block of u undecided positions costs u
    fill passes and a commit pass. ``DEC_STEP`` is ``[S, 4 + B + blocks]``
    (`fill_block`: the block's first position, its end as the length, its
    first write row, the block's tokens as the host knows them) and
    ``DEC_TOKEN`` ``[S, 2 B]`` the block state the pass before left on the
    device (``token_fetch``: tokens, decided bits); ``counts_fetch`` opens
    with ``2 S`` integers, each slot's decided position (-1: a commit
    pass) and its token.

    **Layer groups.** ``groups`` lists the model's attention layers by the
    rows they keep (`KVGroup`), each with arenas and a block pool of its
    own. The first is made of ``state_names`` and ``num_blocks`` and sees
    the whole context; ``window_groups`` are the rest, each of a window. A
    slot's row of ``dec_step`` carries the first group's head and table,
    then for every further group ``length, low, write_row`` and a table of
    `window_table_blocks` block ids that starts at the slot's first LIVE
    block: the step's queries see rows ``[low, length)`` of what that table
    names (`fill_step`; ops/nn.py ``paged_window_feeds`` makes the bias and
    the row map of them). The chunk program takes a span, a row map and
    write rows a group (`chunk_feeds`; a further group's under the names
    `chunk_group_feeds` gives): the row map `chunk_rows` long from the
    group's first live block, the span's start counted from that block's
    first position. A model with one group is fed and run as it ever was.

    **An indexer's arena.** A layer whose queries attend to the
    ``index_topk`` rows an indexer chooses keeps a THIRD arena beside K and
    V (``index_names``, one a layer of ``state_names``: ``[R, index_width]``
    of ``kv_dtype``, a token's one index key), written at the rows K and V
    are and read through the same block table and row maps: the programs
    need no feed for it. The store zeroes it with the others and carries it
    nowhere (kvstate.py ``KVStore.check_carries``)."""

    # feed-name contract (fixed; the engine builds these arrays)
    DEC_TOKEN = "dec_token"
    DEC_STEP = "dec_step"
    DEC_MASK = "dec_mask"
    PRE_TOKENS = "pre_tokens"
    PRE_POSITIONS = "pre_positions"
    PRE_BIAS = "pre_bias"
    INJ_ROWS = "inj_rows"
    CHU_TOKENS = "chu_tokens"
    CHU_POSITIONS = "chu_positions"
    CHU_SPAN = "chu_span"
    CHU_ROWS = "chu_rows"
    CHU_WRITE_ROWS = "chu_write_rows"
    CHU_SLOT = "chu_slot"
    # columns of a slot's row of ``dec_step``; its block table follows
    STEP_TOKEN, STEP_POSITION, STEP_LENGTH, STEP_WRITE_ROW, STEP_TABLE = (
        range(5))

    def __init__(self, *, decode_program, prefill_program, inject_program,
                 startup_program, slots, max_len, vocab_size, hidden,
                 state_names, logits_fetch, prefill_logits_fetch,
                 prefill_kv_fetches, inject_kv_feeds, block_size,
                 num_blocks, chunk_program=None, chunk_tokens=None,
                 chunk_logits_fetch=None, eos_id=None, name="model",
                 version="1", builder=None, logits_mask=False,
                 token_fetch=None, kv_width=None, kv_dtype="float32",
                 slot_states=(), counts_fetch=None, count_names=(),
                 passes=1, block_len=1, mask_token=None, window_groups=(),
                 index_names=(), index_width=0, index_topk=0):
        self.decode_program = decode_program
        self.prefill_program = prefill_program
        self.inject_program = inject_program
        self.chunk_program = chunk_program
        self.startup_program = startup_program
        self.slots = int(slots)
        self.max_len = int(max_len)
        self.vocab_size = int(vocab_size)
        self.hidden = int(hidden)
        self.block_size = int(block_size)
        self.num_blocks = int(num_blocks)
        self.chunk_tokens = int(chunk_tokens) if chunk_tokens else None
        self.state_names = list(state_names)
        self.logits_fetch = logits_fetch
        self.token_fetch = token_fetch
        self.prefill_logits_fetch = prefill_logits_fetch
        self.chunk_logits_fetch = chunk_logits_fetch
        self.prefill_kv_fetches = list(prefill_kv_fetches)
        self.inject_kv_feeds = list(inject_kv_feeds)
        self.eos_id = eos_id
        self.name = str(name)
        self.version = str(version)
        self.builder = builder
        self.logits_mask = bool(logits_mask)
        self.kv_width = int(kv_width) if kv_width else self.hidden
        self.kv_dtype = str(kv_dtype)
        self.slot_states = [(n, tuple(int(d) for d in shape), str(dt))
                            for n, shape, dt in slot_states]
        self.counts_fetch = counts_fetch
        self.count_names = tuple(count_names)
        self.passes = int(passes)
        self.block_len = int(block_len)
        self.mask_token = mask_token
        self.window_groups = list(window_groups)
        # an indexer's keys (class docstring, "An indexer's arena")
        self.index_names = list(index_names)
        self.index_width = int(index_width)
        self.index_topk = int(index_topk)
        self.groups = [KVGroup("full", self.state_names, self.num_blocks,
                               None)] + self.window_groups
        if self.window_groups and (self.block_len > 1
                                   or not self.chunks_only):
            raise ValueError(
                "a model with window groups is served by chunks alone, a "
                "token a step: nothing re-injects rows that a group has "
                "given back")
        if self.block_len > 1 and (self.block_size % self.block_len
                                   or self.max_len % self.block_len
                                   or self.chunk_tokens % self.block_len):
            raise ValueError(
                f"block_len {self.block_len} has to divide block_size "
                f"{self.block_size} (a block's rows lie in one page), "
                f"max_len {self.max_len} and chunk_tokens "
                f"{self.chunk_tokens}")

    @property
    def recurrent(self):
        """Whether the model keeps per-slot recurrent state."""
        return bool(self.slot_states)

    @property
    def chunks_only(self):
        """Whether every prompt streams through the chunk program and
        nothing is ever re-injected: the model has no one-shot prefill
        and no inject program (per-slot recurrent state, or K/V rows of
        several passes a layer, which no host copy carries)."""
        return self.prefill_program is None

    @property
    def key(self):
        return (self.name, self.version)

    @property
    def label(self):
        return f"{self.name}@{self.version}"

    @property
    def rows(self):
        """Physical arena rows: the paged pool's capacity in tokens."""
        return self.num_blocks * self.block_size

    @property
    def arenas(self):
        """How many ``[R, kv_width]`` arenas hold the paged state: the
        names of every entry of ``state_names``."""
        return sum(len(names) for names in self.state_names)

    @property
    def all_state_names(self):
        """``state_names`` of every group, the first group's first, then
        the indexer's arenas, each alone."""
        return self.state_names + [
            names for g in self.window_groups for names in g.state_names
        ] + [(name,) for name in self.index_names]

    def window_table_blocks(self, group):
        """Blocks a slot can hold live in ``group`` when it STEPS: from the
        block of position ``p - window + 1`` to the block of ``p``, the
        width of the group's table in ``dec_step``. A whole slot's where
        the group sees the whole context, here and in a chunk."""
        if group.window is None:
            return self.blocks_per_slot
        return window_table_blocks(group.window, self.block_size,
                                   self.blocks_per_slot)

    def window_chunk_blocks(self, group):
        """Blocks a slot can hold live in ``group`` while a CHUNK runs:
        from the block of ``start - window + 1`` to the block of the
        chunk's last position; what a request's admission reserves there
        at most."""
        if group.window is None:
            return self.blocks_per_slot
        return window_chunk_blocks(group.window, self.chunk_tokens,
                                   self.block_size, self.blocks_per_slot)

    def chunk_rows(self, group):
        """The length of ``group``'s row map in the chunk program."""
        return (self.max_len if group.window is None
                else self.window_chunk_blocks(group) * self.block_size)

    @property
    def step_width(self):
        """Columns of ``dec_step``: the head, a block pass's tokens, the
        first group's table, then ``length, low, write_row`` and a table a
        window group."""
        return (self.step_table + self.blocks_per_slot
                + sum(3 + self.window_table_blocks(g)
                      for g in self.window_groups))

    @property
    def blocks_per_slot(self):
        """Blocks a slot at ``max_len`` holds: its block table's width."""
        return -(-self.max_len // self.block_size)

    @property
    def fills_blocks(self):
        """Whether the decode program is a block pass (class docstring)."""
        return self.block_len > 1

    @property
    def step_table(self):
        """The column of ``dec_step`` at which a slot's block table
        begins: a block pass carries the block's tokens before it."""
        return self.STEP_TABLE + (self.block_len if self.fills_blocks else 0)

    @property
    def step_state_width(self):
        """Columns of ``dec_token``: a token a slot, or a block's tokens
        and decided bits."""
        return 2 * self.block_len if self.fills_blocks else 1

    def step_feed(self):
        """``dec_step`` of a step that no slot takes (every length 0,
        every write row ``R``, every token left to ``dec_token``): the
        engine fills the rows of the slots that step (`fill_step`,
        `fill_block`)."""
        feed = np.zeros((self.slots, self.step_width), "int32")
        feed[:, self.STEP_TOKEN] = -1
        feed[:, self.STEP_WRITE_ROW] = self.rows
        at = self.step_table + self.blocks_per_slot
        for g in self.window_groups:
            feed[:, at + 2] = g.num_blocks * self.block_size
            at += 3 + self.window_table_blocks(g)
        return feed

    def fill_step(self, feed, slot, position, groups, token=-1, write=True):
        """Slot ``slot`` steps at ``position``, attending to positions ``<=
        position``: ``groups`` is its sequence's footing in each layer
        group (kvstate.py ``SeqKV``: ``base``, the position of its first live
        block; ``table``; ``row_of``). Its new K/V rows land where the
        footings say, or nowhere without ``write``; ``token`` is fed from
        the host, or -1 where ``dec_token`` holds it on the device. Counted
        from a further group's first live block, the step's queries see
        rows ``[low, length)``: ``low`` masks the rows of the oldest block
        that have left the window."""
        kv = groups[0]
        feed[slot, :self.STEP_TABLE] = (
            token, position, position + 1,
            kv.row_of(position) if write else self.rows)
        feed[slot, self.STEP_TABLE:self.STEP_TABLE + len(kv.table)] = kv.table
        at = self.step_table + self.blocks_per_slot
        for i, g in enumerate(self.window_groups, 1):
            w = groups[i]
            feed[slot, at:at + 3] = (
                position + 1 - w.base,
                max(position - g.window + 1 - w.base, 0), w.row_of(position))
            feed[slot, at + 3:at + 3 + len(w.table)] = w.table
            at += 3 + self.window_table_blocks(g)

    def fill_block(self, feed, slot, start, table, write_row, tokens=None):
        """Slot ``slot`` runs a pass over the block at positions ``[start,
        start + block_len)``, attending to every position below the
        block's end. Its K/V rows land at ``write_row`` and the rows after
        it. ``tokens`` is the block as the host knows it (a token, or -1
        where none is decided), or None where ``dec_token`` holds it on
        the device."""
        own = tokens is None
        feed[slot, :self.STEP_TABLE] = (-1 if own else 0, start,
                                        start + self.block_len, write_row)
        feed[slot, self.STEP_TABLE:self.step_table] = -1 if own else tokens
        feed[slot, self.step_table:] = table

    def chunk_span(self, start, real):
        """The chunk program's ``chu_span`` feed for ``real`` prompt
        positions from ``start``: the two integers its mask is made from,
        on the device."""
        return np.array([start, real], "int32")

    def chunk_bias(self, start, real):
        """The RULE of the chunk program's mask, stated in numpy for the
        tests (nothing feeds it: the device makes the same of
        ``chunk_span``, kernels/attention.py ``chunk_horizon``): the ``[1,
        C, L]`` additive bias for ``real`` prompt positions from ``start``,
        under which a position sees what lies at or before it, and with
        ``block_len`` B the whole of its own block."""
        at, B = start + np.arange(real), self.block_len
        bias = np.full((1, self.chunk_tokens, self.max_len), NEG_INF,
                       "float32")
        bias[0, :real] = np.where(
            np.arange(self.max_len)[None, :] // B <= (at // B)[:, None],
            np.float32(0.0), np.float32(NEG_INF))
        return bias

    @staticmethod
    def chunk_group_feeds(index):
        """The names of window group ``index``'s chunk feeds (-1: the first
        group's): its span, row map and write rows."""
        suffix = f".g{index + 1}" if index >= 0 else ""
        return tuple(name + suffix for name in (
            DecodeModel.CHU_SPAN, DecodeModel.CHU_ROWS,
            DecodeModel.CHU_WRITE_ROWS))

    def chunk_feeds(self, start, real, groups):
        """What the chunk program is fed of a sequence's footing in each
        layer group (``groups``) for ``real`` prompt positions from
        ``start``: a group's span counts ``start`` from its first live
        block's first position, as its row map does."""
        feeds = {}
        for i, w in enumerate(groups):
            span, rows, wrows = self.chunk_group_feeds(i - 1)
            feeds[span] = self.chunk_span(start - w.base, real)
            feeds[rows] = w.row_map
            feeds[wrows] = w.chunk_write_rows(start, start + real,
                                              self.chunk_tokens)
        return feeds

    def block_table(self, blocks):
        """The ``[blocks_per_slot]`` block ids of a slot's block list (its
        row of ``dec_step``'s table; 0 past the last block)."""
        table = np.zeros(self.blocks_per_slot, "int32")
        table[:len(blocks)] = [b.row0 // self.block_size for b in blocks]
        return table

    def arena_bytes(self):
        """Exact bytes of the model's state on the device: the paged KV
        pool (every arena ``state_names`` lists, two a layer or a latent
        cache's one, ``[R, kv_width]`` of ``kv_dtype`` each) and
        every per-slot state array — what `analysis/memory.py` sees as
        persistent state and what the HBM budget gate reasons about.
        The slotted design's ``S * max_len`` rows become
        ``num_blocks * block_size``, sized to USED tokens."""
        row = self.kv_width * _itemsize(self.kv_dtype)
        slot = sum(int(np.prod(shape)) * _itemsize(dt)
                   for _n, shape, dt in self.slot_states)
        groups = sum(g.num_blocks * self.block_size * row
                     * sum(len(names) for names in g.state_names)
                     for g in self.window_groups)
        index = (len(self.index_names) * self.rows * self.index_width
                 * _itemsize(self.kv_dtype))
        return self.rows * row * self.arenas + groups + slot + index

    def slotted_equivalent_bytes(self):
        """What the PR 10 dense design would reserve for the same
        ``(slots, max_len)`` grid — the paged-vs-slotted comparison
        baseline in DECODE_EVIDENCE."""
        per = (self.slots * self.max_len * self.kv_width
               * _itemsize(self.kv_dtype))
        return per * self.arenas

    # -- feed signatures (ordered like each program's feed list) ---------
    def decode_feed_sig(self):
        s = self.slots
        sig = [
            (self.DEC_TOKEN, (s, self.step_state_width), "int64"),
            (self.DEC_STEP, (s, self.step_width), "int32"),
        ]
        if self.logits_mask:
            # grammar-constrained decode: per-step [S, 1, V] additive
            # logits mask, fed as DATA (zeros when no grammar is active
            # — IEEE x + 0.0 == x keeps unconstrained slots bit-exact)
            sig.append((self.DEC_MASK, (s, 1, self.vocab_size), "float32"))
        return tuple(sig)

    def prefill_feed_sig(self):
        l = self.max_len
        return (
            (self.PRE_TOKENS, (1, l), "int64"),
            (self.PRE_POSITIONS, (1, l), "int64"),
            (self.PRE_BIAS, (1, l, l), "float32"),
        )

    def inject_feed_sig(self):
        l, h = self.max_len, self.hidden
        sig = [(self.INJ_ROWS, (l,), "int64")]
        for kn, vn in self.inject_kv_feeds:
            sig.append((kn, (1, l, h), "float32"))
            sig.append((vn, (1, l, h), "float32"))
        return tuple(sig)

    def chunk_feed_sig(self):
        c, l = self.chunk_tokens, self.max_len
        sig = [
            (self.CHU_TOKENS, (1, c), "int64"),
            (self.CHU_POSITIONS, (1, c), "int64"),
            (self.CHU_SPAN, (2,), "int32"),
            (self.CHU_ROWS, (l,), "int64"),
            (self.CHU_WRITE_ROWS, (c,), "int64"),
        ]
        if self.recurrent:
            # whose rows of the per-slot state arrays the chunk advances
            sig.append((self.CHU_SLOT, (1,), "int64"))
        for i, g in enumerate(self.window_groups):
            span, rows, wrows = self.chunk_group_feeds(i)
            sig += [(span, (2,), "int32"),
                    (rows, (self.chunk_rows(g),), "int64"),
                    (wrows, (c,), "int64")]
        return tuple(sig)


def _itemsize(dtype):
    return 2 if dtype in ("bfloat16", "float16") else np.dtype(dtype).itemsize


def _state_var(main_program, startup_program, name, shape, dtype="float32"):
    """A persistable state var declared in ``main_program`` and
    zero-initialized ONCE in the shared startup (create_global_var would
    append a duplicate fill per program that declares the arena)."""
    mblock = main_program.global_block()
    var = mblock.vars.get(name)
    if var is None:
        var = mblock.create_var(name=name, shape=list(shape),
                                dtype=dtype, persistable=True)
        var.stop_gradient = True
    sblock = startup_program.global_block()
    if name not in sblock.vars:
        sblock.create_var(name=name, shape=list(shape), dtype=dtype,
                          persistable=True)
        sblock.append_op(
            "fill_constant", {}, {"Out": [name]},
            {"shape": list(shape), "dtype": dtype, "value": 0.0},
        )
    return var


def build_decoder_model(vocab_size, hidden=16, num_layers=2, ffn_dim=None,
                        slots=4, max_len=32, eos_id=None, name="decoder",
                        version="1", block_size=None, num_blocks=None,
                        chunk_tokens=None, logits_mask=False):
    """Build the canonical cached-attention decoder as a paged
    DecodeModel.

    Residual transformer decoder: token+position embeddings, per layer
    (q/k/v projection -> paged cached attention -> output projection ->
    residual -> relu FFN -> residual), logits head. Offline/prefill and
    decode paths share every weight by explicit name, which is both the
    bit-exactness contract (one set of parameters, two access patterns)
    and the relaunch contract (rebuilding produces byte-identical
    programs, so the compile cache, not XLA, pays for the restart).

    ``block_size`` defaults to ``min(8, max_len)``; ``num_blocks``
    defaults to FULL capacity (``slots * ceil(max_len / block_size)``),
    so by default nothing can run out of blocks — size it DOWN (with
    the analysis/memory.py gate) to get the paged memory win.
    ``chunk_tokens`` >= 2 additionally builds the chunk-prefill program.

    ``logits_mask`` (default False: at a real vocabulary the mask is a
    ``[S, 1, V]`` float32 feed every step, so only models that serve
    grammars pay for it) adds a fixed-shape additive mask feed applied
    to the decode step's logits (``layers.logits_mask_add``): the
    grammar-constrained decode contract. Per-step masks enter as data —
    the compiled shape never changes, so constrained decode cannot
    retrace; an all-zeros mask is a bit-exact no-op for every
    unconstrained slot.

    The decode program ENDS in one ``arg_max`` over the vocabulary axis
    of those logits (after the mask, where there is one):
    ``next_token [S, 1]``, the model's ``token_fetch``. Greedy token
    choice happens there, so a step of greedy slots hands the host S
    integers and not ``S * V`` floats; the logits stay an output (index
    0 of the step's fetches) for the steps whose slots sample, search
    beams or mask on the host. Prefill, inject and chunk programs have
    no such op.

    The decode step's attention is ONE ``paged_attention`` op — the row
    map and bias that ``paged_step_feeds`` makes of the step's one host
    feed, and the block size, enter the op directly. Its
    reference lowering is the gather+attention composite
    (``paged_attention_composite``: the CPU path and the ``off`` path);
    on a TPU the blocked kernel of kernels/attention.py serves it,
    reading each slot's live blocks alone, within 1e-5 of the composite
    (tests/test_kernels.py, tests/test_paged_kernel_engine.py).
    """
    import paddle_tpu as fluid
    from paddle_tpu.core.ir import Program, program_guard
    from paddle_tpu.utils import unique_name

    V, H, S, L = int(vocab_size), int(hidden), int(slots), int(max_len)
    NL = int(num_layers)
    FFN = int(ffn_dim) if ffn_dim else 4 * H
    if L < 2:
        raise ValueError(f"max_len {L} leaves no room to generate")
    BS = int(block_size) if block_size else min(8, L)
    per_slot = -(-L // BS)                      # ceil: blocks per full slot
    NB = int(num_blocks) if num_blocks else S * per_slot
    R = NB * BS
    C = int(chunk_tokens) if chunk_tokens else None
    if C is not None and not (2 <= C <= L):
        # C == 1 would route the chunk's projections through the GEMV
        # path, whose summation order differs from the prefill GEMM —
        # the bit-exactness contract needs >= 2 rows per matmul
        raise ValueError(f"chunk_tokens must be in [2, {L}], got {C}")
    prefix = f"{name}_v{version}"

    def attr(suffix):
        return fluid.ParamAttr(name=f"{prefix}.{suffix}")

    def proj(h, size, suffix, act=None):
        return fluid.layers.fc(
            h, size, num_flatten_dims=2, act=act,
            param_attr=attr(suffix + ".w"), bias_attr=attr(suffix + ".b"),
        )

    def embed(toks, pos):
        te = fluid.layers.embedding(toks, size=(V, H),
                                    param_attr=attr("tok_emb"))
        pe = fluid.layers.embedding(pos, size=(L, H),
                                    param_attr=attr("pos_emb"))
        return fluid.layers.elementwise_add(te, pe)

    def ffn_block(h, i):
        ff = proj(h, FFN, f"l{i}.ffn1", act="relu")
        return fluid.layers.elementwise_add(h, proj(ff, H, f"l{i}.ffn2"))

    sm_scale = 1.0 / float(np.sqrt(H))
    state_names = [(f"{prefix}.kcache{i}", f"{prefix}.vcache{i}")
                   for i in range(NL)]
    startup = Program()

    # -- prefill: whole-prompt causal forward at [1, L] ------------------
    prefill = Program()
    kv_fetches = []
    # unique_name.guard(): auto-named temp vars restart per program, so a
    # rebuild ANYWHERE in a process (the breaker's relaunch, a second
    # engine) is textually identical and hits the compile cache instead
    # of retracing
    with unique_name.guard(), program_guard(prefill, startup):
        toks = fluid.data(DecodeModel.PRE_TOKENS, [1, L], dtype="int64")
        pos = fluid.data(DecodeModel.PRE_POSITIONS, [1, L], dtype="int64")
        bias = fluid.data(DecodeModel.PRE_BIAS, [1, L, L], dtype="float32")
        h = embed(toks, pos)
        for i in range(NL):
            q = proj(h, H, f"l{i}.q")
            k = proj(h, H, f"l{i}.k")
            v = proj(h, H, f"l{i}.v")
            scores = fluid.layers.matmul(q, k, transpose_y=True,
                                         alpha=sm_scale)
            att = fluid.layers.softmax(
                fluid.layers.elementwise_add(scores, bias), axis=-1)
            ctx = fluid.layers.matmul(att, v)
            h = fluid.layers.elementwise_add(h, proj(ctx, H, f"l{i}.out"))
            h = ffn_block(h, i)
            kv_fetches.append((k.name, v.name))
        pre_logits = proj(h, V, "head")

    # -- decode step: one token per slot at [S, 1], paged arena ----------
    decode = Program()
    with unique_name.guard(), program_guard(decode, startup):
        tok, pos, bias, rows, wrows = fluid.layers.paged_step_feeds(
            fluid.data(DecodeModel.DEC_STEP,
                       [S, DecodeModel.STEP_TABLE + per_slot], dtype="int32"),
            fluid.data(DecodeModel.DEC_TOKEN, [S, 1], dtype="int64"), L, BS)
        lmask = (fluid.data(DecodeModel.DEC_MASK, [S, 1, V],
                            dtype="float32") if logits_mask else None)
        h = embed(tok, pos)
        for i in range(NL):
            kc = _state_var(decode, startup, state_names[i][0], [R, H])
            vc = _state_var(decode, startup, state_names[i][1], [R, H])
            q = proj(h, H, f"l{i}.q")
            k = proj(h, H, f"l{i}.k")
            v = proj(h, H, f"l{i}.v")
            nk = fluid.layers.block_scatter_write(
                kc, wrows, fluid.layers.squeeze(k, [1]))
            nv = fluid.layers.block_scatter_write(
                vc, wrows, fluid.layers.squeeze(v, [1]))
            # persist: the lowering donates the arenas, so this is an
            # in-place device update, not a copy
            fluid.layers.assign(nk, output=kc)
            fluid.layers.assign(nv, output=vc)
            ctx = fluid.layers.paged_attention(
                fluid.layers.squeeze(q, [1]), nk, nv, rows, bias,
                S, L, sm_scale=sm_scale, block_size=BS)
            ctx = fluid.layers.unsqueeze(ctx, [1])
            h = fluid.layers.elementwise_add(h, proj(ctx, H, f"l{i}.out"))
            h = ffn_block(h, i)
        dec_logits = proj(h, V, "head")
        if lmask is not None:
            dec_logits = fluid.layers.logits_mask_add(dec_logits, lmask)
        # greedy choice on the device: first index of each row's maximum
        next_token = fluid.layers.argmax(dec_logits, axis=-1)

    # -- inject: scatter prefill rows into arbitrary arena rows ----------
    inject = Program()
    inj_feeds = []
    with unique_name.guard(), program_guard(inject, startup):
        irows = fluid.data(DecodeModel.INJ_ROWS, [L], dtype="int64")
        for i in range(NL):
            kc = _state_var(inject, startup, state_names[i][0], [R, H])
            vc = _state_var(inject, startup, state_names[i][1], [R, H])
            kn, vn = f"inj_k{i}", f"inj_v{i}"
            rk = fluid.data(kn, [1, L, H], dtype="float32")
            rv = fluid.data(vn, [1, L, H], dtype="float32")
            nk = fluid.layers.block_scatter_write(
                kc, irows, fluid.layers.squeeze(rk, [0]))
            nv = fluid.layers.block_scatter_write(
                vc, irows, fluid.layers.squeeze(rv, [0]))
            fluid.layers.assign(nk, output=kc)
            fluid.layers.assign(nv, output=vc)
            inj_feeds.append((kn, vn))

    # -- chunk prefill: [1, C] prompt chunk against the paged arena ------
    chunk = None
    chu_logits_name = None
    if C is not None:
        chunk = Program()
        with unique_name.guard(), program_guard(chunk, startup):
            toks = fluid.data(DecodeModel.CHU_TOKENS, [1, C], dtype="int64")
            pos = fluid.data(DecodeModel.CHU_POSITIONS, [1, C],
                             dtype="int64")
            bias = fluid.layers.chunk_mask_bias(
                fluid.data(DecodeModel.CHU_SPAN, [2], dtype="int32"), C, L)
            crows = fluid.data(DecodeModel.CHU_ROWS, [L], dtype="int64")
            cwrows = fluid.data(DecodeModel.CHU_WRITE_ROWS, [C],
                                dtype="int64")
            h = embed(toks, pos)
            for i in range(NL):
                kc = _state_var(chunk, startup, state_names[i][0], [R, H])
                vc = _state_var(chunk, startup, state_names[i][1], [R, H])
                q = proj(h, H, f"l{i}.q")
                k = proj(h, H, f"l{i}.k")
                v = proj(h, H, f"l{i}.v")
                nk = fluid.layers.block_scatter_write(
                    kc, cwrows, fluid.layers.squeeze(k, [0]))
                nv = fluid.layers.block_scatter_write(
                    vc, cwrows, fluid.layers.squeeze(v, [0]))
                fluid.layers.assign(nk, output=kc)
                fluid.layers.assign(nv, output=vc)
                # gather AFTER the scatter: the context view includes the
                # chunk's own rows; the bias opens exactly the causal
                # prefix per chunk position
                gk = fluid.layers.block_gather(nk, crows, 1, L)
                gv = fluid.layers.block_gather(nv, crows, 1, L)
                scores = fluid.layers.matmul(q, gk, transpose_y=True,
                                             alpha=sm_scale)
                att = fluid.layers.softmax(
                    fluid.layers.elementwise_add(scores, bias), axis=-1)
                ctx = fluid.layers.matmul(att, gv)
                h = fluid.layers.elementwise_add(
                    h, proj(ctx, H, f"l{i}.out"))
                h = ffn_block(h, i)
            chu_logits = proj(h, V, "head")
            chu_logits_name = chu_logits.name

    kwargs = dict(vocab_size=V, hidden=H, num_layers=NL, ffn_dim=FFN,
                  slots=S, max_len=L, eos_id=eos_id, name=name,
                  version=version, block_size=BS, num_blocks=NB,
                  chunk_tokens=C, logits_mask=logits_mask)
    return DecodeModel(
        decode_program=decode, prefill_program=prefill,
        inject_program=inject, chunk_program=chunk,
        startup_program=startup,
        slots=S, max_len=L, vocab_size=V, hidden=H,
        block_size=BS, num_blocks=NB, chunk_tokens=C,
        state_names=state_names, logits_fetch=dec_logits.name,
        token_fetch=next_token.name,
        prefill_logits_fetch=pre_logits.name,
        chunk_logits_fetch=chu_logits_name,
        prefill_kv_fetches=kv_fetches, inject_kv_feeds=inj_feeds,
        eos_id=eos_id, name=name, version=version,
        builder=lambda: build_decoder_model(**kwargs),
        logits_mask=logits_mask,
    )
