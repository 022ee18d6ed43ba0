"""The serving drivers: an open loop on a schedule made before the window,
and a closed loop of clients that each wait for their answer.

What makes the open loop repeat (ISSUE 23): the same stratified work in
every run (``workgen.py``); a pre-roll at the cell's rate so that the slots
are in steady state when the window opens; the sample is every request that
FINISHES inside the window, whenever it was due; latency runs from the time
a request was DUE, never from a late send, over the tokens returned, and is
averaged per request before any quantile is taken; the generator thread does
nothing between sends but sleep, and reports how late it ran.
"""

import collections
import itertools
import threading
import time

import numpy as np

from benchmark import workgen


_Sent = collections.namedtuple(
    "_Sent", "due sent prompt answer response error")


def _submit(system, sent_log, due, prompt, answer, deadline):
    from paddle_tpu.serving.request import ServingError

    sent = time.perf_counter()
    try:
        response = system.engine.submit(
            prompt, max_new_tokens=answer, deadline_at=deadline)
        sent_log.append(_Sent(due, sent, prompt, answer, response, None))
    except ServingError as e:
        sent_log.append(_Sent(due, sent, prompt, answer, None, e))


def _open_loop(system, schedule, t_start, t_close, deadline, sent_log):
    """The generator thread: sleep to each due time, never send early."""
    for offset, prompt, answer in schedule:
        due = t_start + offset
        if due >= t_close:
            return
        delay = due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        _submit(system, sent_log, due, prompt, answer, deadline)


def _closed_loop(system, requests, clients, t_close, deadline, sent_log):
    """One thread stands for ``clients`` callers: each has one request out
    and sends its next when that one is answered (polled every 2 ms)."""
    out = [None] * clients
    todo = itertools.cycle(requests)
    while time.perf_counter() < t_close:
        for c in range(clients):
            if out[c] is None or out[c].response is None \
                    or out[c].response.done():
                prompt, answer = next(todo)
                _submit(system, sent_log, time.perf_counter(), prompt,
                        answer, deadline)
                out[c] = sent_log[-1]
        time.sleep(0.002)


def _warm_up(system, traffic, seed):
    """Runs every program the window uses once (a prompt short enough for
    the one-shot prefill and inject, the longest one for the chunked
    prefill, decode steps for both) and returns the seconds one decode step
    took."""
    rng = np.random.default_rng(seed)
    lengths = workgen.stratified_lengths(traffic["prompt_len"], 8)
    responses = [
        system.engine.submit(workgen.prompt_tokens(rng, n, system.vocab_size),
                             max_new_tokens=8)
        for n in (lengths[0], lengths[-1])]
    for r in responses:
        r.result(timeout=600)
    return system.entry.metrics.snapshot()["decode_step_avg_s"]


def _check_against_reference(system, finished, traffic, seed):
    """A seeded handful of the answers against the configuration's plain
    reference (``references/``: the whole sequence in one pass, no cache, no
    slots, matmuls at the highest precision), on their first
    ``check_tokens`` tokens. The reference reads the prompt and the served
    tokens and gives, at each position, the logits of the next token; greedy
    decoding has to have served the top one. The program computes at the
    TPU's default matmul precision, so a served token counts as the top one
    when no logit of the reference's row is above its own by more than
    ``check_tolerance`` standard deviations of that row: closer than that,
    the two are a tie that rounding decides. Returns (requests checked,
    requests right, the worst token's distance in standard deviations)."""
    rng = np.random.default_rng(seed)
    n = min(traffic["check_requests"], len(finished))
    picks = rng.choice(len(finished), n, replace=False) if n else []
    right, worst = 0, 0.0
    for i in picks:
        s = finished[int(i)]
        served = [int(t) for t in s.response.result()["tokens"]]
        served = served[:traffic["check_tokens"]]
        first = len(s.prompt) - 1
        rows = system.reference_logits(
            list(s.prompt) + served[:-1], range(first, first + len(served)))
        behind = (rows.max(axis=1) - rows[np.arange(len(served)), served]
                  ) / rows.std(axis=1)
        worst = max(worst, float(behind.max()))
        right += bool(behind.max() <= traffic["check_tolerance"])
    return int(n), right, worst


def run(system, traffic, args, clock0, compiles, tracer):
    """Runs the cell's window; returns what ``train.run`` does."""
    from paddle_tpu.observability import metrics as obs_metrics

    closed = traffic["kind"] == "closed_loop"
    system.engine.start()
    t_warm = time.perf_counter()
    step_s = _warm_up(system, traffic, args.seed)
    print(f"# set-up: warm-up requests {time.perf_counter() - t_warm:.2f} s, "
          f"then a pre-roll of {traffic['preroll_s']} s at the cell's load",
          flush=True)
    preroll = traffic["preroll_s"]
    duration = preroll + args.seconds
    if closed:
        # enough for clients that are answered as fast as one decode step
        # per token allows, twice over
        mean_answer = traffic["answer_len"]["median"]
        n = 2 * traffic["clients"] * duration / (mean_answer * step_s)
        work = workgen.closed_loop_requests(
            traffic, args.seed, int(n) + 1, system.vocab_size)
    else:
        work = workgen.open_loop_schedule(
            traffic, args.seed, duration, system.vocab_size)

    sent_log = []
    t_start = time.perf_counter() + 0.05
    t_open = t_start + preroll
    t_close = t_open + args.seconds
    # what is still in flight when the window closes is abandoned: its
    # deadline passes and the engine drops it at its next step, so the
    # drain takes seconds, not the longest answer's half minute
    deadline = t_close + traffic["abandon_after_s"]
    if closed:
        target, targs = _closed_loop, (system, work, traffic["clients"],
                                       t_close, deadline, sent_log)
    else:
        target, targs = _open_loop, (system, work, t_start, t_close,
                                     deadline, sent_log)
    generator = threading.Thread(target=target, args=targs,
                                 name="bench-generator", daemon=True)
    generator.start()

    registry = obs_metrics.registry()
    time.sleep(max(0.0, t_open - time.perf_counter()))
    opened = time.perf_counter()
    before = (registry.snapshot(), compiles.snapshot())
    stretch = None
    if tracer is not None:
        with tracer():
            # the registry at the traced stretch's own edges: what a
            # counter moved by while the device events of the trace ran
            stretch = [registry.snapshot()]
            time.sleep(min(traffic["trace_seconds"], args.seconds))
            stretch.append(registry.snapshot())
    time.sleep(max(0.0, t_close - time.perf_counter()))
    after = (registry.snapshot(), compiles.snapshot())
    generator.join(timeout=60)
    system.engine.shutdown(timeout=traffic["abandon_after_s"] + 120)
    if generator.is_alive():
        raise RuntimeError("the generator did not stop")

    # results are collected only now, after the window. ``failed`` counts
    # every operation of the window that did not end well: refused at the
    # door, answered with an error, or malformed; only the last is a wrong
    # OUTPUT, and only it (with the reference, below) decides ``correct``
    finished, failed, malformed, attempted = [], 0, 0, 0
    for s in sent_log:
        if s.response is None:
            inside = t_open <= s.sent < t_close
            attempted += inside
            failed += inside
            continue
        done = s.response.finish_time
        if done is None or not t_open <= done < t_close:
            continue
        attempted += 1
        if s.response.error() is not None:
            failed += 1
            continue
        tokens = s.response.result()["tokens"]
        if len(tokens) != s.answer or not all(
                0 <= int(t) < system.vocab_size for t in tokens):
            failed += 1
            malformed += 1
        else:
            finished.append(s)
    if not finished:
        raise RuntimeError("no request finished inside the window")
    per_request = [
        (s.response.finish_time - s.due) * 1e3 / s.answer for s in finished]
    late = [s.sent - s.due for s in sent_log if t_open <= s.sent < t_close]
    lateness = float(np.median(late)) if late else 0.0
    moved = compiles.moved(before[1], after[1])
    checked, equal, worst = _check_against_reference(
        system, finished, traffic, args.seed)
    print(f"# generator lateness: median {lateness * 1e3:.3f} ms, worst "
          f"{max(late, default=0.0) * 1e3:.3f} ms over {len(late)} sends "
          f"(one decode step: {step_s * 1e3:.1f} ms); {len(finished)} "
          f"requests finished in the window, {failed} failed ({malformed} "
          f"of them answers of the wrong length or vocabulary); {equal} of "
          f"{checked} checked answers are the plain reference's (worst token "
          f"{worst:.2e} standard deviations behind its top); {moved} "
          "compilations in the window", flush=True)
    # a late generator or a refusal at the door makes a run a poor
    # measurement (both are reported: ``generator_lateness_ms``,
    # ``failed``), not a wrong answer
    correct = (malformed == 0 and moved == 0
               and equal >= traffic["check_min_equal"] * checked)
    wrong_allowed = int(checked - traffic["check_min_equal"] * checked)
    compared = {
        "worst_token_sigma_behind": (
            worst, traffic["check_tolerance"],
            worst <= traffic["check_tolerance"]),
        "checked_answers_wrong": (checked - equal, wrong_allowed,
                                  checked - equal <= wrong_allowed),
        "malformed_answers": (malformed, 0, malformed == 0),
        "compiles_in_window": (moved, 0, moved == 0),
    }
    end_to_end = {
        "serve_token_latency_p50": float(np.percentile(per_request, 50)),
        "setup_s": opened - clock0,
    }
    facts = {
        "window_s": args.seconds,
        "compiles_in_window": moved,
        "load_s": system.load_s,
        "lateness_median_ms": lateness * 1e3,
        "requests_finished": len(finished),
        # the tail of the same quantity: a dozen requests of a window's
        # hundred, so a per-layer reading beside the median, with no bound
        "token_latency_p90_ms": float(np.percentile(per_request, 90)),
        "output_tokens_per_s": sum(s.answer for s in finished)
        / args.seconds,
    }
    return {"end_to_end": end_to_end, "facts": facts,
            "registry": (before[0], after[0]), "stretch_registry": stretch,
            "correct": correct, "compared": compared,
            "attempted": attempted, "failed": failed}
