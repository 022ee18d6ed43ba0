"""Fleet replica worker: one decode replica in its own process.

Run as ``python -m paddle_tpu.serving.fleet.worker --index N ...``: the
worker builds the canonical cached-attention decoder from its CLI
geometry, registers it with a GenerationEngine (compile cache dir from
``core/compile_cache.py cache_dir()`` — a warm disk tier means the
worker is serving-ready with ZERO traces), prints one ``FLEET_WORKER_READY``
JSON line naming its port and compile sources, and serves the router's
length-prefixed JSON RPC on a single connection.

Rolling deploys by REPLACEMENT (ROADMAP 3(b)): a builder closure cannot
cross a process boundary, so a subprocess replica never deploys
in-place. Instead the worker accepts any number of ``--model-spec
'{...}'`` JSON geometries (each a (name, version) registry entry served
concurrently by the multi-tenant engine), and the router rolls a new
version by spawning a REPLACEMENT worker hosting old+new specs into the
dead man's slot, stealing the old worker's backlog, and drain-retiring
it — then pass 2 retires the old version from the replacement via the
``retire`` RPC (a registry unregistration, which DOES cross the wire).
The legacy single-model flags stay byte-compatible.

Chaos contract: the worker fires the ``replica.kill`` fault site (rank
= ``--index``) at the top of EVERY RPC it serves, so a schedule entry
``{"site": "replica.kill", "action": "kill", "rank": N, "at_call": K}``
hard-exits this process (``os._exit`` — no flushes, no goodbyes) in the
middle of live traffic. The router observes the dropped connection,
marks the replica dead, and re-dispatches its in-flight requests — the
subprocess kill-a-replica test asserts the retried answers are
byte-identical.
"""

import argparse
import json
import os
import socket
import sys
import time


def _send(conn, obj):
    from paddle_tpu.distributed.ps import frame_send

    frame_send(conn, json.dumps(obj).encode())


def _result_payload(resp):
    err = resp.error()
    if err is not None:
        return {"error": err.to_dict()}
    return {"tokens": [int(t) for t in resp.result()["tokens"]]}


def model_specs(args):
    """The (possibly several) decoder geometries this worker hosts:
    every ``--model-spec`` JSON, each defaulted from the legacy single-
    model flags; no specs = exactly the legacy single model."""
    base = dict(vocab_size=args.vocab_size, hidden=args.hidden,
                num_layers=args.num_layers, slots=args.slots,
                max_len=args.max_len, eos_id=args.eos_id,
                name=args.name, version=args.version)
    if not args.model_spec:
        return [base]
    return [{**base, **json.loads(s)} for s in args.model_spec]


def serve(args):
    from paddle_tpu.resilience import faults
    from paddle_tpu.serving.decode import (
        GenerationEngine,
        build_decoder_model,
    )
    from paddle_tpu.serving.request import Priority

    engine = GenerationEngine(
        queue_depth=args.queue_depth, breaker_threshold=0,
        label=f"fleet-worker-{args.index}",
    )
    entries = []
    for spec in model_specs(args):
        entries.append(engine.register_model(
            lambda spec=spec: build_decoder_model(**spec)))
    engine.start()

    srv = socket.socket()
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", args.port))
    srv.listen(1)
    print("FLEET_WORKER_READY " + json.dumps({
        "port": srv.getsockname()[1],
        "pid": os.getpid(),
        "models": ["@".join(k) for k in engine.models()],
        "trace": sum(e.compile_sources.get("trace", 0) for e in entries),
        "compile_sources": entries[0].compile_sources,
    }), flush=True)

    conn, _addr = srv.accept()
    conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    from paddle_tpu.distributed.ps import frame_recv

    tickets = {}          # ticket -> inner Response
    next_ticket = 0
    while True:
        msg = json.loads(frame_recv(conn).decode())
        # THE chaos kill site: action "kill" never returns
        faults.fire("replica.kill", rank=args.index)
        cmd = msg.get("cmd")
        if cmd == "submit":
            budget = msg.get("deadline_budget_ms")
            deadline_at = (time.perf_counter() + budget / 1e3
                           if budget is not None else None)
            try:
                resp = engine.submit(
                    msg["prompt"], model=msg.get("model"),
                    version=msg.get("version"),
                    tenant=msg.get("tenant", "default"),
                    priority=msg.get("priority", Priority.NORMAL),
                    max_new_tokens=msg.get("max_new", 16),
                    deadline_at=deadline_at,
                )
            except Exception as e:
                payload = (e.to_dict() if hasattr(e, "to_dict")
                           else {"code": "request_failed",
                                 "message": str(e)})
                _send(conn, {"ok": False, "error": payload})
                continue
            next_ticket += 1
            tickets[next_ticket] = resp
            _send(conn, {"ok": True, "ticket": next_ticket})
        elif cmd == "poll":
            done = {}
            for t in msg.get("tickets", []):
                resp = tickets.get(int(t))
                if resp is not None and resp.done():
                    done[str(t)] = _result_payload(resp)
                    del tickets[int(t)]
            _send(conn, {"done": done})
        elif cmd == "ping":
            load = 0
            for key in engine.models():
                e = engine.entry(*key)
                load += e._queue.depth() + e._pool.active_count
            _send(conn, {
                "ok": True, "load": load,
                "models": ["@".join(k) for k in engine.models()],
                "trace": sum(engine.entry(*k).compile_sources.get(
                    "trace", 0) for k in engine.models()),
            })
        elif cmd == "steal":
            stolen = []
            for key in list(engine.models()):
                for r in engine.reroute_queued(*key):
                    for t, resp in list(tickets.items()):
                        if resp is r.response:
                            stolen.append(t)
                            del tickets[t]
                            break
            _send(conn, {"tickets": stolen})
        elif cmd == "retire":
            # rolling-deploy pass 2 over the wire: drain-before-retire
            # one hosted (name, version) from the multi-tenant registry
            try:
                engine.unregister_model(
                    msg["name"], msg["version"],
                    timeout=float(msg.get("timeout", 120.0)))
            except Exception as e:
                _send(conn, {"ok": False,
                             "error": {"code": "request_failed",
                                       "message": str(e)}})
                continue
            _send(conn, {"ok": True,
                         "models": ["@".join(k) for k in engine.models()]})
        elif cmd == "stop":
            engine.shutdown()
            _send(conn, {"ok": True})
            break
        else:
            _send(conn, {"ok": False,
                         "error": {"code": "request_failed",
                                   "message": f"unknown cmd {cmd!r}"}})
    conn.close()
    srv.close()
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--index", type=int, required=True,
                    help="replica index (the replica.kill rank selector)")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--vocab-size", type=int, default=32)
    ap.add_argument("--hidden", type=int, default=8)
    ap.add_argument("--num-layers", type=int, default=1)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--max-len", type=int, default=16)
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--name", type=str, default="fleet")
    ap.add_argument("--version", type=str, default="1")
    ap.add_argument("--model-spec", action="append", default=None,
                    help="JSON decoder geometry to host (repeatable; "
                         "each a (name, version) registry entry, "
                         "defaulted from the single-model flags)")
    ap.add_argument("--queue-depth", type=int, default=64)
    args = ap.parse_args(argv)
    try:
        return serve(args)
    except ConnectionError:
        # router went away: drain and exit clean (not a crash)
        return 0


if __name__ == "__main__":
    sys.exit(main())
