"""The engine's own device lane: when the device FINISHED each launch.

An operator's instrument, asked for with the tracer (``tracing(path,
lanes=True)``); a capture that does not ask pays nothing for it.

`_ModelEntry._run` is the one place an entry's programs are launched, and it
knows when the host handed a launch over (the clock before and after the
executable's call). What no line of the host knows is when the device was
done with it. While lanes are on, `_run` hands every launch that has an
output to keep (a `fetches` entry; an arena is donated to the next launch)
to a READY WATCHER, one daemon thread a device: it takes the launches in
launch order, waits for that output, and reads the tracer's clock. The
device runs what it is given in launch order, so from a launch's stamps —
``t_call0`` and ``t_call1`` around the executable's call, ``ready`` — and
ONE question the launching thread asks as its call returns (is the output
of the launch before ready? `jax.Array.is_ready`, which does not block)
everything else follows (`Account`), with no profiler:

* launch k was QUEUED if the launch before was still running when the host
  had finished handing k over. Then, and only then, ``ready_k - ready_{k-1}``
  is the device's time for launch k (and for what rode with it), exact to
  the watcher's wake-up: the ``queued`` part;
* otherwise the device was seen done: ``t_call0_k - ready_{k-1}``, where
  positive, is certain IDLE (nothing was dispatched). The part of it the
  entry's loop slept in `_wait` since its launch before is ``idle_empty``
  (there was nothing to do), the rest ``idle_host`` (the host had work and
  was late); what is left of the interval, ``ready_k - max(t_call0_k,
  ready_{k-1})``, is dispatch latency plus device time that the host cannot
  part: ``unqueued``. Where an unstamped PROGRAM was launched in between,
  no part of the interval is certain idle: all of it is ``unqueued``.

The answer also bounds a stamp taken late: a watcher that wakes behind the
interpreter's lock would stamp ``ready_{k-1}`` after launch k's hand-over,
but the launching thread saw that output ready then, so the stamp counts as
no later than that (`Launch.seen_ns`), and an idle device is not called
busy. A late stamp of a launch that WAS still running when the next was
handed over cannot be bounded: it moves time from launch k to k-1, and the
sum over consecutive queued launches is exact but for its two ends.

The four parts add up to the wall time between the first and the last
stamp. A launch with no output to keep (the inject program), one made with
no live span (a draft's step from another entry's thread) and the admission
helpers (the row picker, the stack-and-trim) are not stamped: their time
lies in the interval of the next stamped launch, whose event names them
(``with=``). A launch whose wait raises (a lost arena) closes the account
there: the launch after it opens it again.

Each stamped launch is also an event ``device::<kind>`` on the tracer's
track ``device:<id>`` (`Tracer.record_lane`): the Chrome export shows the
device beside the host spans that launched it. The four parts go to the
launching entry's ``serving_device_*_seconds_total``. Device seconds by
PROGRAM are the profiler's to give: the device modules carry their
program's name (``core/lowering.py _named``).

Records are taken in the order they are handed over, after the executable's
call: ONE launching thread a device is what the account is exact for. Two
entries that launch on one device from two threads may hand over in another
order than the device runs them, and their two intervals then mix.

What the stamps cannot see: an idle device's dispatch latency (inside
``unqueued``) and the inject program alone.
"""

import collections
import threading
import time

from paddle_tpu.observability import lockdep
from paddle_tpu.observability.tracer import get_tracer

__all__ = ["Account", "DeviceLane", "Launch", "Parts"]

#: one stamped launch's share of the wall time since the stamp before it,
#: nanoseconds; ``start_ns`` is where its lane event begins
Parts = collections.namedtuple(
    "Parts", "queued device_ns idle_empty_ns idle_host_ns unqueued_ns "
             "start_ns")


class Launch:
    """One launch as the watcher is handed it. ``owner`` is the
    `DecodeMetrics` that is credited, ``launch`` the program's launch number
    by the entry's always-on counters, ``slept_ns`` what the owner's loop
    slept in `_wait` since its launch before, ``busy`` whether the launch
    before was still running when this one had been handed over, ``rode``
    the names of what was launched unstamped since the stamped launch
    before, ``programs`` how many of those were programs, ``out`` the output
    waited for, ``ready_ns`` the watcher's stamp (None: the wait raised),
    ``seen_ns`` when a launching thread saw the output ready (None: never
    before the stamp), a bound on a stamp taken late."""

    __slots__ = ("owner", "kind", "launch", "t_call0", "t_call1", "slept_ns",
                 "busy", "rode", "programs", "out", "ready_ns", "seen_ns")

    def __init__(self, owner, kind, launch, t_call0, t_call1, slept_ns=0,
                 busy=False, rode=(), programs=0, out=None, ready_ns=None,
                 seen_ns=None):
        self.owner = owner
        self.kind = kind
        self.launch = launch
        self.t_call0 = t_call0
        self.t_call1 = t_call1
        self.slept_ns = slept_ns
        self.busy = busy
        self.rode = tuple(rode)
        self.programs = programs
        self.out = out
        self.ready_ns = ready_ns
        self.seen_ns = seen_ns


class Account:
    """The account of a device's launches, in launch order: `add` gives
    each stamped launch its `Parts`. No clock and no thread inside."""

    def __init__(self):
        self.prev_ns = None     # when the launch before was done

    def add(self, rec):
        """The parts of ``rec``, or None where it has none: it opens the
        account (no stamp before it) or its wait failed (the account closes
        and the next stamped launch opens it again)."""
        ready = rec.ready_ns
        if ready is not None and rec.seen_ns is not None:
            ready = min(ready, rec.seen_ns)
        prev = self.prev_ns
        if ready is not None and prev is not None:
            # a bound written while the launch before was being accounted
            # can lie a little before that launch's stamp
            ready = max(ready, prev)
        self.prev_ns = ready
        if prev is None or ready is None:
            return None
        if rec.busy:
            return Parts(True, ready - prev, 0, 0, 0, prev)
        start = prev if rec.programs else max(rec.t_call0, prev)
        idle = start - prev
        empty = min(idle, rec.slept_ns)
        return Parts(False, 0, empty, idle - empty, ready - start, start)


class DeviceLane:
    """The ready watcher of ONE device and the names of what rides
    unstamped. Fed by every entry of the engine that owns it; the thread
    starts at the first launch handed over and is joined by `close`."""

    def __init__(self, device):
        self.track = f"device:{device.id}"
        self._cond = threading.Condition(lockdep.named_lock("decode.lane"))
        self._records = collections.deque()     # handed over, not accounted
        self._last = None                       # the launch handed over last
        self._rode = []
        self._programs = 0
        self._thread = None
        self._stop = False

    def rode(self, name, program=False):
        """Something was launched on the device that is not stamped: it
        rides in the interval of the next stamped launch."""
        with self._cond:
            self._rode.append(name)
            self._programs += bool(program)

    def launched(self, owner, kind, launch, t_call0, t_call1, slept_ns, out):
        """Hand launch ``launch`` of ``owner``'s ``kind`` program to the
        watcher, with ``out`` to wait for; called as the executable's call
        has returned, which is when the launch before is asked about."""
        rec = Launch(owner, kind, launch, t_call0, t_call1, slept_ns,
                     out=out)
        with self._cond:
            last, self._last = self._last, rec
            before = None if last is None else last.out    # None: stamped
            try:
                rec.busy = before is not None and not before.is_ready()
            except Exception:       # a lost arena: its wait will say so
                rec.busy = False
            if not rec.busy:
                # whatever was handed over before is done by now (the
                # device's order): no stamp of it counts as later
                now = time.perf_counter_ns()
                for r in self._records:
                    if r.seen_ns is None:
                        r.seen_ns = now
            rec.rode, self._rode = tuple(self._rode), []
            rec.programs, self._programs = self._programs, 0
            asleep = not self._records
            self._records.append(rec)
            if self._thread is None:
                self._stop = False
                self._thread = threading.Thread(
                    target=self._watch, name=f"decode-lane-{self.track}",
                    daemon=True)
                self._thread.start()
            elif asleep:    # else it finds the record when it is back
                self._cond.notify()

    def close(self, timeout=None):
        """Let the watcher stamp what it was handed, then join it, for at
        most ``timeout`` seconds. True if no thread is left."""
        with self._cond:
            self._stop = True
            self._cond.notify_all()
            thread = self._thread
        if thread is not None:
            thread.join(timeout)
            return not thread.is_alive()
        return True

    def _watch(self):
        acc = Account()
        while True:
            with self._cond:
                while not self._records and not self._stop:
                    self._cond.wait()
                if not self._records:
                    self._thread = None     # `launched` starts another
                    return
                rec = self._records[0]      # it leaves once it is accounted
            self._stamp(rec, acc)
            with self._cond:
                self._records.popleft()

    def _stamp(self, rec, acc):
        """Wait for ``rec``'s output, stamp it, and give it its part of
        the account ``acc``, its counters and its lane event."""
        try:
            rec.out.block_until_ready()
            rec.ready_ns = time.perf_counter_ns()
        except Exception:
            rec.ready_ns = None     # a lost arena: the account closes
        rec.out = None
        tracer = get_tracer()
        if acc.prev_ns is not None and acc.prev_ns < tracer.epoch_ns:
            # the stamp before is another capture's (launches went unstamped
            # in between): this launch opens the account anew
            acc.prev_ns = None
        parts = acc.add(rec)
        if rec.ready_ns is None:
            return
        args = {"launch": rec.launch, "with": list(rec.rode)}
        if parts is not None:
            rec.owner.observe_device(parts)
            args["queued"] = parts.queued
        tracer.record_lane(
            self.track, f"device::{rec.kind}",
            parts.start_ns if parts is not None else rec.t_call0,
            acc.prev_ns, args)
