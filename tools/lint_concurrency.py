#!/usr/bin/env python
"""Concurrency lint CLI: static lock-order + race analysis over sources.

CI contract (shared with tools/lint_program.py): exit 0 = clean,
1 = lint findings, 2 = internal error / bad invocation; ``--json`` emits
one machine-readable report line.

  python tools/lint_concurrency.py                  # lint paddle_tpu/
  python tools/lint_concurrency.py path/a.py dir/   # lint specific paths
  python tools/lint_concurrency.py --json
  python tools/lint_concurrency.py --smoke          # the fast-tier gate

``--smoke`` is the r11 CI gate:
  1. the repo-wide static lint is CLEAN — every remaining finding either
     fixed or carrying an attributed ``# lockdep: ok(reason)``;
  2. both synthetic positive controls FIRE with correct file:line and
     held-chain attribution (an injected ABBA pair and an unguarded-dict
     mutation; a blocking-under-lock control rides along) — the gate is
     proven live, not vacuously green;
  3. the runtime lockdep witness raises on a live ABBA inversion and on
     a declared-hierarchy violation (observability/lockdep.py).
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXIT_CLEAN, EXIT_FINDINGS, EXIT_INTERNAL = 0, 1, 2

# ---------------------------------------------------------------------------
# synthetic positive controls (imported by tests/test_concurrency.py too):
# if the analyzer ever stops firing on these, the smoke gate fails — a
# silently-dead linter must not read as a clean repo
# ---------------------------------------------------------------------------

ABBA_CONTROL = '''\
import threading


class Control:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def forward(self):
        with self._a:
            with self._b:
                pass

    def backward(self):
        with self._b:
            with self._a:
                pass
'''
# forward's inner `with self._b:` is control line 11; backward's inner
# `with self._a:` is control line 16 (asserted by the smoke)
ABBA_LINES = (11, 16)

UNGUARDED_CONTROL = '''\
import threading


class Worker:
    def __init__(self):
        self._lock = threading.Lock()
        self.counts = {}
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def _loop(self):
        self.counts["ticks"] = self.counts.get("ticks", 0) + 1

    def snapshot(self):
        with self._lock:
            return dict(self.counts)
'''
UNGUARDED_LINE = 11

BLOCKING_CONTROL = '''\
import threading


class Blocker:
    def __init__(self):
        self._lock = threading.Lock()
        self._thread = threading.Thread(target=self._loop)

    def _loop(self):
        pass

    def stop(self):
        with self._lock:
            self._thread.join()
'''
BLOCKING_LINE = 14

DEFAULT_PATHS = (os.path.join(REPO, "paddle_tpu"),)


def _scan(paths):
    from paddle_tpu.analysis.concurrency import scan_paths

    return scan_paths(list(paths))


def _print_report(rep, as_json, out=sys.stdout):
    if as_json:
        payload = rep.to_json()
        payload["pass"] = not rep.findings
        out.write(json.dumps(payload) + "\n")
        return
    for f in rep.findings:
        out.write(f"{f}\n")
    for f in rep.suppressed:
        out.write(f"{f}\n")
    for e in rep.edges:
        out.write(f"edge: {e.describe()}\n")
    out.write(
        f"[concurrency] {rep.files} files, {len(rep.locks)} locks, "
        f"{len(rep.edges)} hold-edges, {len(rep.cycles)} cycles, "
        f"{len(rep.findings)} findings "
        f"({len(rep.suppressed)} suppressed)\n"
    )


# ---------------------------------------------------------------------------
# smoke
# ---------------------------------------------------------------------------


def _smoke(as_json):
    from paddle_tpu.analysis.concurrency import scan_sources

    failures = []

    def check(ok, msg):
        if not ok:
            failures.append(msg)
            print(f"SMOKE FAIL: {msg}", file=sys.stderr)

    # 1. repo-wide lint must be clean (suppressions allowed + reported)
    rep = _scan(DEFAULT_PATHS)
    for f in rep.findings:
        print(f"SMOKE FAIL: unsuppressed finding: {f}", file=sys.stderr)
    check(not rep.findings,
          f"{len(rep.findings)} unsuppressed concurrency findings in "
          f"paddle_tpu/ (fix or add '# lockdep: ok(reason)')")
    check(not rep.cycles, f"static lock-order cycles: {rep.cycles}")

    # 2. positive controls fire with correct attribution
    abba = scan_sources({"<control-abba>": ABBA_CONTROL})
    cyc = [f for f in abba.findings if f.kind == "lock-order-cycle"]
    check(len(cyc) == 1, "ABBA control did not produce a cycle finding")
    if cyc:
        check(cyc[0].file == "<control-abba>"
              and cyc[0].line in ABBA_LINES,
              f"ABBA control attribution wrong: {cyc[0].file}:{cyc[0].line}")
        check("._a" in cyc[0].message and "._b" in cyc[0].message
              and "holding" in cyc[0].message,
              "ABBA control message lacks held-chain attribution")
        check(str(ABBA_LINES[0]) in cyc[0].message
              and str(ABBA_LINES[1]) in cyc[0].message,
              "ABBA control message lacks both edge lines")

    ung = scan_sources({"<control-unguarded>": UNGUARDED_CONTROL})
    mut = [f for f in ung.findings
           if f.kind == "unguarded-shared-mutation"]
    check(len(mut) == 1 and mut[0].line == UNGUARDED_LINE,
          f"unguarded-dict control did not fire at line {UNGUARDED_LINE}: "
          f"{[str(f) for f in ung.findings]}")

    blk = scan_sources({"<control-blocking>": BLOCKING_CONTROL})
    bf = [f for f in blk.findings if f.kind == "blocking-under-lock"]
    check(len(bf) == 1 and bf[0].line == BLOCKING_LINE
          and bf[0].held == ("<control-blocking>.Blocker._lock",),
          f"blocking control did not fire with held chain: "
          f"{[str(f) for f in blk.findings]}")

    # 3. the runtime witness is live: ABBA + declared-order violations
    from paddle_tpu.observability import lockdep

    was = lockdep.enabled()
    try:
        lockdep.enable()
        lockdep.reset()
        a = lockdep.named_lock("lintctl.a")
        b = lockdep.named_lock("lintctl.b")
        with a:
            with b:
                pass
        raised = False
        try:
            with b:
                with a:
                    pass
        except lockdep.LockOrderError as e:
            raised = "lintctl.a" in str(e) and "lintctl.b" in str(e)
        check(raised, "runtime witness did not raise on a live ABBA")
        lockdep.reset()
        # the repo's own declared hierarchy enforces (decode engine
        # declares serving.queue before decode.tenant at import)
        import paddle_tpu.serving.decode.engine  # noqa: F401 - declares

        q = lockdep.named_lock("serving.queue", rlock=True)
        t = lockdep.named_lock("decode.tenant")
        raised = False
        try:
            with t:
                with q:
                    pass
        except lockdep.LockOrderError as e:
            raised = "declared lock order" in str(e)
        check(raised,
              "runtime witness did not enforce the declared "
              "serving.queue -> decode.tenant hierarchy")
    finally:
        lockdep.reset()
        lockdep.enable(was)

    if not failures:
        print(f"smoke: concurrency lint clean over {rep.files} files "
              f"({len(rep.locks)} locks, {len(rep.suppressed)} attributed "
              f"suppressions), all 3 static controls + 2 runtime witness "
              f"controls fired")
    if as_json:
        print(json.dumps({"pass": not failures, "failures": failures,
                          "files": rep.files, "locks": len(rep.locks),
                          "suppressed": len(rep.suppressed)}))
    return EXIT_FINDINGS if failures else EXIT_CLEAN


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="static concurrency lint (lock order, blocking under "
        "lock, unguarded shared mutation)")
    ap.add_argument("paths", nargs="*",
                    help="files/directories (default: paddle_tpu/)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="one JSON report line")
    ap.add_argument("--smoke", action="store_true",
                    help="fast-tier CI gate: repo clean + positive "
                    "controls fire")
    try:
        args = ap.parse_args(argv)
        if args.smoke:
            return _smoke(args.as_json)
        rep = _scan(args.paths or DEFAULT_PATHS)
        _print_report(rep, args.as_json)
        return EXIT_FINDINGS if rep.findings else EXIT_CLEAN
    except SystemExit as e:
        # argparse errors exit 2 already; preserve the 0/1/2 contract
        raise SystemExit(EXIT_INTERNAL if e.code not in (0, 1, 2)
                         else e.code)
    except Exception:
        import traceback

        traceback.print_exc()
        return EXIT_INTERNAL


if __name__ == "__main__":
    sys.exit(main())
