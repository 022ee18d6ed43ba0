"""Schedule compiler: per-(stage, microbatch, phase) slot tables.

The reference's PipelineOptimizer runs sections as host threads passing
scopes through queues (reference: python/paddle/fluid/optimizer.py:3414) —
the schedule is implicit in queue order. On TPU the schedule must be a
compile-time artifact: this module emits it as an explicit slot table that
(a) the runtime executes tick-for-tick, (b) the step accounting walks to
report the REALIZED bubble fraction, and (c) the memory analyzer walks to
price the activation stash pre-compile, exactly like remat.

Two kinds:

* ``gpipe`` — the classic fill/drain schedule (Huang et al.): microbatch m
  runs forward on stage d at tick m+d; backwards mirror after the flush.
  Per-stage busy time is 2m of a 2(m+s-1)-tick makespan, so the bubble is
  ``(s-1)/(m+s-1)``: 3/7 at s=4, m=4.

* ``1f1b`` — the interleaved schedule (Narayanan et al. / Megatron's
  virtual stages): every device hosts ``interleave`` model CHUNKS, so the
  ring has s*v virtual stages of 1/v the work and a microbatch laps it v
  times (the circular collective_permute ring in runtime.py). Fill/drain
  edges shrink by the chunk size: the table realizes
  ``((v-1)(s-m) + s-1) / (m + s*v - 1)`` — 3/11 at s=4, m=4, v=2, beating
  GPipe's 3/7. The backward is the reverse-mode transpose of
  the forward wave (generic vjp path), so bwd slots mirror fwd slots; the
  interleaving buys bubble, not stash — every chunk residual stays live
  across the fwd->bwd span and is priced that way (memory.py).

A slot table is exact, not aspirational: runtime.py derives its tick loop
from the same (stage, chunk, microbatch, tick) arithmetic, and
tests/test_pipeline_runtime.py::test_schedule_table_is_a_valid_schedule
walks the table (forward before backward, peak live residuals).
"""

from collections import namedtuple

from paddle_tpu.observability.lockdep import named_lock

__all__ = ["SCHEDULE_KINDS", "Slot", "Schedule", "compile_schedule",
           "predicted_bubble"]

SCHEDULE_KINDS = ("gpipe", "1f1b")

#: one unit of schedulable work: `phase` is 'fwd' or 'bwd', `chunk` the
#: virtual-stage chunk this device runs (always 0 under gpipe), `tick` the
#: global time slot (all slots of a tick run concurrently across stages)
Slot = namedtuple("Slot", ("tick", "stage", "chunk", "microbatch", "phase"))


def predicted_bubble(kind, num_stages, num_microbatches, interleave=1):
    """Closed-form bubble fraction for the circular-wave schedules this
    package executes. ``gpipe`` is (s-1)/(m+s-1); ``1f1b``
    with v chunks/device is ((v-1)(s-m) + s-1)/(m + s*v - 1) — equal to
    Megatron's (s-1)/(m*v + s-1) at the m == s operating point."""
    s, m = int(num_stages), int(num_microbatches)
    if s <= 1:
        return 0.0
    v = int(interleave) if kind == "1f1b" else 1
    return ((v - 1) * (s - m) + s - 1) / float(m + s * v - 1)


class Schedule:
    """An immutable compiled slot table plus its accounting views."""

    def __init__(self, kind, num_stages, num_microbatches, interleave,
                 slots):
        self.kind = kind
        self.num_stages = int(num_stages)
        self.num_microbatches = int(num_microbatches)
        self.interleave = int(interleave)
        self.slots = tuple(sorted(slots))
        self.num_ticks = 1 + max(s.tick for s in self.slots) if slots else 0

    # -- identity (joins the compile-cache fingerprint) -------------------
    def fingerprint(self):
        return (f"{self.kind}:s{self.num_stages}:m{self.num_microbatches}"
                f":v{self.interleave}")

    def __repr__(self):
        return (f"Schedule({self.fingerprint()}, ticks={self.num_ticks}, "
                f"bubble={self.realized_bubble():.6f})")

    # -- table views ------------------------------------------------------
    def slots_for_stage(self, stage):
        return tuple(s for s in self.slots if s.stage == stage)

    def fwd_slots(self):
        return tuple(s for s in self.slots if s.phase == "fwd")

    # -- step accounting --------------------------------------------------
    def realized_bubble(self):
        """Bubble fraction from walking the table the runtime executes:
        1 - busy-slots / (stages * makespan). Every slot costs one tick
        (under 1f1b a tick is a CHUNK of work, 1/v of a gpipe stage tick
        — the fraction is unit-invariant because all of a schedule's
        slots are equal cost)."""
        if self.num_ticks == 0 or self.num_stages <= 1:
            return 0.0
        busy = len(self.slots)
        return 1.0 - busy / float(self.num_stages * self.num_ticks)

    def predicted(self):
        return predicted_bubble(self.kind, self.num_stages,
                                self.num_microbatches, self.interleave)

    def stage_timeline(self, stage):
        """Per-tick occupancy of one stage: list of None (idle) or
        (phase, chunk, microbatch)."""
        line = [None] * self.num_ticks
        for s in self.slots_for_stage(stage):
            assert line[s.tick] is None, ("slot collision", s)
            line[s.tick] = (s.phase, s.chunk, s.microbatch)
        return line

    # -- activation-stash liveness (the memory analyzer's input) ----------
    def peak_stash_slots(self, stage=None):
        """Max concurrently-live forward residuals on a device, in CHUNK
        slots (one slot = one (chunk, microbatch) forward's stash; a chunk
        holds layers_per_stage/interleave layers, so bytes = slots *
        per-chunk activation bytes — memory.schedule_stash_bytes). A fwd
        slot goes live when it runs and dies when its bwd slot runs."""
        stages = (range(self.num_stages) if stage is None else (stage,))
        peak = 0
        for d in stages:
            live, d_peak = 0, 0
            for s in self.slots_for_stage(d):
                live += 1 if s.phase == "fwd" else -1
                d_peak = max(d_peak, live)
            peak = max(peak, d_peak)
        return peak


def _gpipe_slots(s, m):
    slots = []
    flush = m + s - 1  # first bwd tick group starts after the fwd drain
    for mb in range(m):
        for d in range(s):
            slots.append(Slot(mb + d, d, 0, mb, "fwd"))
            slots.append(Slot(flush + (m - 1 - mb) + (s - 1 - d),
                              d, 0, mb, "bwd"))
    return slots


def _interleaved_slots(s, m, v):
    """Circular wave: microbatch mb crosses virtual stage k = chunk*s +
    stage at tick mb + k; the backward is the exact mirror (the vjp
    transpose of the forward ring). Contention-free iff m <= s: device d's
    chunk-j window [d + j*s, d + j*s + m) never overlaps chunk j+1's."""
    k_total = s * v
    flush = m + k_total - 1
    slots = []
    for mb in range(m):
        for k in range(k_total):
            d, c = k % s, k // s
            slots.append(Slot(mb + k, d, c, mb, "fwd"))
            slots.append(Slot(flush + (m - 1 - mb) + (k_total - 1 - k),
                              d, c, mb, "bwd"))
    return slots


_cache = {}
_cache_lock = named_lock("pipeline.schedule")


def compile_schedule(kind, num_stages, num_microbatches, interleave=None):
    """Compile (and memoize) a slot table.

    ``interleave`` is the virtual-chunks-per-device degree: forced to 1
    for gpipe, default 2 for 1f1b. 1f1b requires num_microbatches <=
    num_stages (the contention-free circular window — beyond it two
    chunks of one device would claim the same tick; raise loudly rather
    than silently serialize)."""
    s, m = int(num_stages), int(num_microbatches)
    if kind not in SCHEDULE_KINDS:
        raise ValueError(
            f"unknown pipeline schedule {kind!r}; kinds are "
            f"{SCHEDULE_KINDS}")
    if s < 1 or m < 1:
        raise ValueError(f"need stages >= 1 and microbatches >= 1, got "
                         f"stages={s} microbatches={m}")
    if kind == "gpipe":
        v = 1
        if interleave not in (None, 1):
            raise ValueError("gpipe has no interleaving; "
                             "use schedule='1f1b' for interleave > 1")
    else:
        v = 2 if interleave is None else int(interleave)
        if v < 2:
            raise ValueError(
                f"1f1b is the interleaved schedule: interleave must be "
                f">= 2 (got {v}); interleave=1 is exactly gpipe")
        if m > s:
            raise ValueError(
                f"1f1b circular schedule needs num_microbatches <= "
                f"num_stages ({m} > {s}): a wider microbatch window "
                f"would put two chunks of one device in the same tick")
    key = (kind, s, m, v)
    with _cache_lock:
        sched = _cache.get(key)
    if sched is not None:
        return sched
    slots = _gpipe_slots(s, m) if kind == "gpipe" \
        else _interleaved_slots(s, m, v)
    sched = Schedule(kind, s, m, v, slots)
    with _cache_lock:
        if len(_cache) > 64:
            _cache.clear()
        _cache[key] = sched
    return sched
