"""A served hybrid configuration (``nemotron3_nano_30b_a3b``,
``lfm2_24b_a2b``, ``ouro_2_6b``, ``sdar_30b_a3b``, ``granite_4_0_h_micro``,
``mistral_small_4_119b``, ``trinity_large_preview``:
the seven families of ``serving/decode/hybrid.py``, any whose file names a ``builder`` and a
``reference``) against its plain reference, outside any timed window, and
the readings the cell's limits are set from (its traffic file; PERF.md
section 2).

    python3 tools/check_hybrid_logits.py --seed <n>
        [--config nemotron3_nano_30b_a3b] [--traffic reasoning_steady]
        [--requests 32] [--steps 192]
        [--faults ssm,conv,kv,kv_all,positions,chunk_ssm,chunk_kv,chunks_kv,
                  again,window_early,window_early_block]
        [--references float8_e4m3fn,operands:bfloat16,attention_multiplier=0.125]
        [--state-dtype bfloat16] [--kernels off] [--pattern MEM*E]
        [--passes 3] [--share-passes] [--stale-arena 1,7]
        [--replays causal,left_to_right]
        [--dump chiprun_out/rows.npz] [--rehearse-cpu]

Requests of the cell's own length distribution go through the engine,
chunked prefill then decode steps with the logits fetched and the top token
taken (what a greedy request is served, with its row); the reference reads
the prompt and the served tokens in one pass. Per served token:
``row_sigma``, max |served row - reference row| over the reference row's
standard deviation, and ``behind``, how far the served token's logit lies
behind the reference row's top in those units: the quantity
``benchmark/serve.py`` holds to ``check_tolerance``. ``answers_wrong``
replays the cell's own comparison on these rows (a request is wrong when
one of its first ``check_tokens`` tokens is behind by more than
``check_tolerance``; ``--requests`` of them where the cell takes
``check_requests``).

The served tokens are read once as served and once for each of
``--faults``: one layer's SSM state (``ssm``), convolution tail (``conv``)
or K arena (``kv``; ``kv_all``: every attention layer's) put back to what it
was after every decode step (a stale row), or every decode step's
positions one too far (``positions``: a rotation is relative, so the fault
is the step's rows turned against the prompt's); ``chunk_ssm`` and
``chunk_kv`` go wrong at ONE chunk boundary of every prompt longer than a
chunk, the one before its last chunk: the first Mamba layer's SSM state of
the slot dropped there, or the first attention layer's K arena left a chunk
stale (``_chunk_fault``); ``chunks_kv`` puts that arena back after EVERY
chunk launch, as ``kv`` does after every decode step (no prompt's K rows
land in it: a chunk's queries find their own chunk's keys alone);
``window_early`` and ``window_early_block`` are a model's with WINDOW
GROUPS (``--config trinity_large_preview``): a sequence past its window
gives the oldest block of its window group back one STEP early (whenever
one of that block's rows is still inside the window) or one BLOCK early
(in every step), and the block is reused: its rows inside the window read
as another block's (the step's table names the next block in its place);
``again`` plants nothing and serves the prompts a second time AS THE POOL
HOLDS THEM (a model without per-slot state meets its prompts' full blocks
in place and prefills the rest alone): a served token may not depend on
what was served before it. Every other fault first zeroes the arenas and
empties the pool, so that its prompts are prefilled anew and a row that
does not land reads as zeros.
``--references``
reads the sound tokens again with the reference computed otherwise:
``<dtype>`` rounds its weights through that dtype (the precision below the
served one), ``<key>=<value>`` misreads one published key of a reference
that takes such controls (``attention_multiplier=0.125``), ``operands:<dtype>`` the left operand of its products (the
served program's own arithmetic, as near as a plain pass comes), and for
the latter the expert layers' choices are compared with the float32 pass's
(``flips``): the tokens whose chosen sets differ, a layer, and those of
them where a held expert comes or goes; its ``rows_from_float32`` is that
reference's rows against the float32 reference's (how far rounding alone
carries a row: the floor under every ``row_sigma``), and every fault's
tokens are read against it as well (``against_operands:<dtype>``).

A looped stack's controls (``--config ouro_2_6b``): ``--passes N`` reads the
sound tokens against the reference run N times through its stack and not
``total_ut_steps``; ``--stale-arena t,l`` puts the K arena of pass ``t``,
layer ``l`` alone (one of 192) back after every decode step;
``--share-passes`` builds the SERVED model with every pass of a layer on
pass 0's arena pair, so that a token's older rows are its last pass's for
every pass (the decode-time sharing the family's paper offers as an
approximation, which nothing serves): the whole run is then the control,
and its ``sound`` reading is what the comparison has to refuse.

A model that fills its answer a block at a time (``--config sdar_30b_a3b
--traffic chat_blocks``) is served greedy, as it only can be, and no row is
fetched: ``behind`` alone is read (``row_sigma`` is null), each served token
against the reference's row from the block state in which it was decided
(the builder's replay of the response's ``decided_at``), over the tokens
whose block lies wholly inside the answer. Its controls: ``--faults
skip_commit`` serves every block WITHOUT its commit pass's write (the
block's K/V rows stay as its last filling pass wrote them, one position of
them computed while it still held the mask token); ``--replays causal``
reads the sound tokens against the reference under a causal mask,
``--replays left_to_right`` against a replay that ignores ``decided_at``.
"""

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

OVER = (0.02, 0.05, 0.1, 0.2, 0.3, 0.5, 1.0)
#: the reference's readings of this run, by (prompt, tokens, how)
_READ = {}


def _stale(entry, fault):
    """``entry._run`` wrapped so that after every decode step one layer's
    state of kind ``fault`` is what it was before the step; returns the
    call that takes the wrap off."""
    import jax.numpy as jnp

    m = entry.model
    if fault == "again":
        # nothing planted: the same prompts served a second time, what a
        # fault's reading is held against beside the first serving's
        return lambda: None
    k_arenas = [kv[0] for kv in m.state_names]
    if fault == "kv_all":
        names = k_arenas
    elif fault == "kv":
        names = k_arenas[:1]
    elif fault.startswith("arena_"):
        tag = ".kcache.p%d.l%d" % tuple(
            int(x) for x in fault[len("arena_"):].split("_"))
        names = [n for n in k_arenas if n.endswith(tag)]
    else:
        names = [n for n, _s, _d in m.slot_states if "." + fault in n][:1]
    early = {"window_early": 1, "window_early_block": m.block_size}.get(fault)
    if early and not m.window_groups:
        raise ValueError(f"{fault!r} is a model's with window groups")
    if (fault not in ("positions", "skip_commit") and not early
            and not names):
        raise ValueError(f"no state of the model answers to {fault!r}")
    launch = entry._run

    def run(kind, feeds, span=None):
        if kind != "step":
            return launch(kind, feeds, span)
        if fault == "skip_commit":
            # a slot whose pass is its block's commit writes nowhere
            step = np.array(feeds[m.DEC_STEP])
            for s, st in enumerate(entry._slots):
                if (st is not None and st.mode == "decode"
                        and step[s, m.STEP_LENGTH]
                        and st.bpass == st.fills):
                    step[s, m.STEP_WRITE_ROW] = m.rows
            feeds = dict(feeds, **{m.DEC_STEP: step})
        if fault == "positions":
            step = np.array(feeds[m.DEC_STEP])
            step[:, 1] += 1
            feeds = dict(feeds, **{m.DEC_STEP: step})
        if early:
            # a stepping slot whose oldest live block of the window group
            # has left the window but for its last ``early`` rows at most:
            # given back early and reused, its rows are another block's
            step = np.array(feeds[m.DEC_STEP])
            at = m.step_table + m.blocks_per_slot
            length, low = step[:, at], step[:, at + 1]
            gone = (length > 0) & (low > 0) & (m.block_size - low <= early)
            step[gone, at + 3] = step[gone, at + 4]
            feeds = dict(feeds, **{m.DEC_STEP: step})
        kept = [jnp.array(entry._scope.find_var(n), copy=True)
                for n in names]
        out = launch(kind, feeds, span)
        for n, was in zip(names, kept):
            entry._scope.set(n, was)
        return out

    entry._run = run
    return lambda: setattr(entry, "_run", launch)


def _chunk_fault(entry, fault):
    """``entry._run`` wrapped so that ONE chunk boundary of every prompt
    longer than a chunk goes wrong, the one before its last chunk:
    ``chunk_ssm`` drops the first Mamba layer's SSM state of the slot there
    (the last chunk starts from zero in that layer), ``chunk_kv`` leaves
    the first attention layer's K arena a chunk stale (the last chunk's K
    rows never land). ``chunks_kv`` is ``chunk_kv`` at EVERY chunk launch.
    Returns the call that takes the wrap off."""
    import jax.numpy as jnp

    m = entry.model
    name = (m.state_names[0][0] if fault.endswith("_kv") else
            [n for n, _s, _d in m.slot_states if ".ssm" in n][0])
    launch = entry._run

    def run(kind, feeds, span=None):
        if kind != "chunk":
            return launch(kind, feeds, span)
        start = int(feeds[m.CHU_SPAN][0])
        # a model without per-slot state is not fed its slot: the chunk's
        # row map is its slot's own
        slot = (int(feeds[m.CHU_SLOT][0]) if m.CHU_SLOT in feeds else next(
            s for s, st in enumerate(entry._slots)
            if st is not None and st.kv.row_map is feeds[m.CHU_ROWS]))
        plen = entry._slots[slot].plen
        if fault != "chunks_kv" and (
                not start
                or start != (plen - 1) // m.chunk_tokens * m.chunk_tokens):
            return launch(kind, feeds, span)
        was = entry._scope.find_var(name)
        if fault == "chunk_ssm":
            entry._scope.set(name, was.at[slot].set(0))
            return launch(kind, feeds, span)
        kept = jnp.array(was, copy=True)
        out = launch(kind, feeds, span)
        entry._scope.set(name, kept)
        return out

    entry._run = run
    return lambda: setattr(entry, "_run", launch)


def _serve(system, prompts, steps):
    """The greedy answers to ``prompts`` with the row each token was the
    top of: ``[(tokens, rows [steps, V])]``."""
    from paddle_tpu.serving.decode import SamplingParams

    rows = {}

    def top(st, row, device_masked):
        row = np.array(row, np.float32)
        rows.setdefault(id(st.request.response), []).append(row)
        return int(row.argmax())

    entry = system.entry
    if entry.model.fills_blocks:
        # greedy is all such a model serves, and the comparison replays
        # the response's own filling order: no row is fetched
        responses = [system.engine.submit(p, max_new_tokens=steps)
                     for p in prompts]
        return [([int(t) for t in r.result(timeout=1800)["tokens"]], r)
                for r in responses]
    choose, entry._choose_token = entry._choose_token, top
    try:
        # a sampled policy brings every step's rows to the host
        responses = [system.engine.submit(
            p, max_new_tokens=steps, sampling=SamplingParams(seed=i))
            for i, p in enumerate(prompts)]
        return [([int(t) for t in r.result(timeout=1800)["tokens"]],
                 np.stack(rows[id(r)])) for r in responses]
    finally:
        entry._choose_token = choose


def _against(system, prompts, served, **how):
    """(row_sigma, behind), each ``[requests, steps]``, and what
    ``routing`` adds."""
    sigma, behind, extra = [], [], []
    tokens = how.pop("tokens", None)
    for prompt, (out, got) in zip(prompts, served):
        if not isinstance(got, np.ndarray):
            # a block-filling model's response in place of rows: the
            # builder's replay reads it under the prompt (a fault's serving
            # of the same prompts noted its own since)
            system.engine.noted[tuple(prompt)], got = got, None
        out = out[:tokens]
        first = len(prompt) - 1
        # (a pass over 32k positions takes its time: the same tokens read
        # the same way, a fault that moved no token, are not read twice)
        key = (tuple(prompt), tuple(out), repr(sorted(how.items())))
        if key not in _READ:
            _READ[key] = system.reference_logits(
                list(prompt) + out[:-1], range(first, first + len(out)),
                **how)
        want = _READ[key]
        if how.get("routing"):
            want, *more = want
            extra.append(more)
        std = want.std(1)
        sigma.append(np.full(len(out), np.nan) if got is None
                     else np.abs(got[:len(out)] - want).max(1) / std)
        behind.append((want.max(1) - want[np.arange(len(out)), out]) / std)
    return np.stack(sigma), np.stack(behind), extra


def _between(system, prompts, served, how):
    """The quantiles of max |row - float32 reference's row| over the
    latter's standard deviation, a served token, for the reference computed
    as ``how`` says: the ``row_sigma`` of one reference against the
    other."""
    sigma = []
    for prompt, (out, _got) in zip(prompts, served):
        first = len(prompt) - 1
        at = list(prompt) + out[:-1], range(first, first + len(out))
        want = system.reference_logits(*at)
        other = system.reference_logits(*at, **how)
        if isinstance(other, tuple):
            other = other[0]
        sigma.append(np.abs(other - want).max(1) / want.std(1))
    sigma = np.stack(sigma)
    return {"median": float(np.median(sigma)),
            "p90": float(np.percentile(sigma, 90)),
            "worst": float(sigma.max())}


def _summary(sigma, behind, traffic):
    flat = behind.reshape(-1)
    tokens = traffic["check_tokens"]
    worst = behind[:, :tokens].max(1)
    return {
        "row_sigma": None if np.isnan(sigma).all() else {
            "median": float(np.median(sigma)),
            "p90": float(np.percentile(sigma, 90)),
            "worst": float(sigma.max())},
        "behind": {"worst": float(flat.max()),
                   "share_of_tokens_over": {
                       str(t): float((flat > t).mean()) for t in OVER}},
        "answers_wrong": int((worst > traffic["check_tolerance"]).sum()),
        "answers": int(worst.size),
        "answers_worst_behind": [round(float(w), 4) for w in worst]}


def _flips(routing, other, held, k):
    """Per expert layer: tokens whose chosen sets differ between two
    passes, and those of them where a held expert comes or goes."""
    differ = held_differ = 0
    for (a, _sa), (b, _sb) in zip(routing, other):
        a, b = np.sort(a[..., :k], -1), np.sort(b[..., :k], -1)
        moved = (a != b).any(-1)                      # [layers, tokens]
        mine = lambda x: np.where(  # noqa: E731
            (x >= held[0]) & (x < held[1]), x, -1)
        held_moved = (np.sort(mine(a), -1) != np.sort(mine(b), -1)).any(-1)
        differ = differ + moved.sum(1)
        held_differ = held_differ + held_moved.sum(1)
    return {"tokens_differing_by_layer": [int(n) for n in differ],
            "with_a_held_expert_by_layer": [int(n) for n in held_differ]}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--config", default="nemotron3_nano_30b_a3b")
    ap.add_argument("--traffic", default="reasoning_steady")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--steps", type=int, default=192)
    ap.add_argument("--faults", default="")
    ap.add_argument("--references", default="")
    ap.add_argument("--state-dtype", default=None)
    ap.add_argument("--kernels", default=None, choices=("off", "interpret"))
    ap.add_argument("--pattern", default=None, help="another layer pattern "
                    "(a diagnosis by kind of layer; widths as configured)")
    ap.add_argument("--passes", type=int, default=None, help="a looped "
                    "stack: the reference run this many times through it")
    ap.add_argument("--share-passes", action="store_true", help="a looped "
                    "stack: SERVE every pass of a layer from one arena pair")
    ap.add_argument("--stale-arena", default=None, metavar="PASS,LAYER",
                    help="a looped stack: that one K arena a step stale")
    ap.add_argument("--replays", default="", help="a model that fills "
                    "blocks: the sound tokens against the reference under "
                    "a causal mask (causal), against a replay that ignores "
                    "the served filling order (left_to_right)")
    ap.add_argument("--dump", default=None, help="an .npz of every "
                    "reading's row_sigma and behind, [requests, steps]")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    import importlib

    from benchmark import manifest, workgen
    from paddle_tpu import kernels

    bench = manifest.load_manifest()
    config = manifest.load_config(bench, args.config)
    builder = importlib.import_module(
        "benchmark.builders." + config["builder"])
    traffic = manifest.sizes(manifest.load_traffic(args.traffic),
                             args.rehearse_cpu)
    if not args.rehearse_cpu:
        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                              os.path.join(ROOT, ".jax_cache"))
    if args.state_dtype:
        config = dict(config, settings=dict(config["settings"],
                                            state_dtype=args.state_dtype))
    if args.pattern:
        config = dict(config, hybrid_override_pattern=args.pattern,
                      num_hidden_layers=len(args.pattern))
    if args.share_passes:
        from paddle_tpu.serving.decode import hybrid

        own = hybrid._Parts.arenas
        hybrid._Parts.arenas = lambda parts, program, key: own(
            parts, program, (0, key[1]))
    faults = [f for f in args.faults.split(",") if f]
    if args.stale_arena:
        faults.append("arena_" + args.stale_arena.replace(",", "_"))
    mode = args.kernels or ("interpret" if args.rehearse_cpu else None)
    rng = np.random.default_rng(args.seed)
    with kernels.scoped_mode(mode or kernels.mode()):
        system = builder.build(config, traffic, args.seed,
                               args.rehearse_cpu)
        lengths = workgen.stratified_lengths(traffic["prompt_len"],
                                             args.requests)
        steps = min(args.steps, traffic["max_total_len"] - max(lengths))
        prompts = [workgen.prompt_tokens(rng, n, system.vocab_size)
                   for n in lengths]
        system.engine.start()
        served = {"sound": _serve(system, prompts, steps)}
        for fault in faults:
            if fault != "again":
                # every request is over and the loop waits: zero the arenas
                # and empty the pool. A model whose blocks the pool shares
                # (one with no per-slot state) would meet its prompts' rows
                # in place and prefill nothing; and a row that never lands
                # has to read as zeros, not as the same prompt's row of the
                # serving before (the pool hands the same blocks out again)
                system.entry.kv.reset()
            undo = (_chunk_fault if fault.startswith("chunk")
                    else _stale)(system.entry, fault)
            served["stale_" + fault] = _serve(system, prompts, steps)
            undo()
        system.engine.shutdown()
    keys = system.config
    # how many experts are held here: the count the configuration cut
    # (none for a configuration without routed experts)
    cut = [k for k in config["reduced"] if k.endswith("experts")]
    held = (system.expert_offset,
            system.expert_offset + keys[cut[0]]) if cut else None
    report = {"seed": args.seed, "config": args.config,
              "requests": len(prompts), "steps": steps,
              "prompt_lengths": lengths,
              "state_dtype": config["settings"].get("state_dtype"),
              "passes_share_arenas": bool(args.share_passes),
              "kernels": mode or "auto",
              "check_tokens": traffic["check_tokens"],
              "check_tolerance": traffic["check_tolerance"]}
    dump = {}
    rounded = [ref for ref in args.references.split(",")
               if ref.startswith("operands:")]
    blocks = system.entry.model.fills_blocks
    # the expert layers' choices, of a reference that hands them over
    import inspect

    routes = held and not blocks and "routing" in inspect.signature(
        system.reference.logits).parameters
    # a block's replay needs every position of the block in the answer
    whole = ({"tokens": steps - system.entry.model.block_len + 1}
             if blocks else {})
    for name, answers in served.items():
        sigma, behind, routing = _against(
            system, prompts, answers, **whole,
            **({"routing": True} if routes and name == "sound" else {}))
        report[name] = _summary(sigma, behind, traffic)
        dump[name + ".row_sigma"], dump[name + ".behind"] = sigma, behind
        if name != "sound":
            # answers whose tokens are the first serving's, token for token
            report[name]["answers_as_sound"] = sum(
                out == first for (out, _g), (first, _f) in zip(
                    answers, served["sound"]))
            # a fault's tokens against the rounded reference too: whether
            # a comparison with rounding's share taken out would tell it
            for ref in rounded:
                report[name]["against_" + ref] = _summary(*_against(
                    system, prompts, answers,
                    round_operands=ref.split(":")[1])[:2], traffic)
            continue
        if routes:
            gap = np.concatenate([(s[..., -2] - s[..., -1]).reshape(-1)
                                  for _ids, s in routing])
            report["reference_margin_share_under"] = {
                str(t): float((gap < t).mean()) for t in (1e-3, 3e-3, 1e-2)}
            dump["sound.margin"] = np.stack(
                [s[..., -2] - s[..., -1] for _ids, s in routing])
        refs = [(ref, dict({"round_operands": ref.split(":")[1]},
                           **({"routing": True} if routes else {}))
                 if ref.startswith("operands:")
                 else {ref.split("=")[0]: json.loads(ref.split("=")[1])}
                 if "=" in ref else {"round_to": ref})
                for ref in filter(None, args.references.split(","))]
        if args.passes is not None:
            refs.append((f"passes_{args.passes}", {"passes": args.passes}))
        for replay in filter(None, args.replays.split(",")):
            refs.append(("replay_" + replay,
                         {"mask": "causal"} if replay == "causal"
                         else {"order": replay}))
        for ref, how in refs:
            _sigma, behind, other = _against(system, prompts, answers,
                                             **whole, **how)
            report["reference_" + ref] = _summary(_sigma, behind, traffic)
            dump[f"reference_{ref}.behind"] = behind
            if ref.startswith("operands:"):
                # how far rounding alone carries a row: this reference's
                # rows against the float32 reference's, in its units
                report["reference_" + ref]["rows_from_float32"] = _between(
                    system, prompts, answers, how)
            if other:
                report["reference_" + ref]["flips"] = _flips(
                    routing, other, held, keys["num_experts_per_tok"])
    if args.dump:
        os.makedirs(os.path.dirname(os.path.abspath(args.dump)),
                    exist_ok=True)
        np.savez_compressed(args.dump, **dump)
    print(json.dumps(report), flush=True)


if __name__ == "__main__":
    main()
