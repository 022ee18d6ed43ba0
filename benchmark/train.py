"""The ``train_steps`` driver: ``bench.py``'s loop, kept. N steps are
dispatched without a sync between them and one ``jax.block_until_ready``
waits on the last fetched loss; N is fixed before the window from the step
time seen in warm-up, so that the window lasts about ``--seconds``."""

import math
import time

import numpy as np


def _timed(system, n):
    import jax

    t0 = time.perf_counter()
    losses = [system.step() for _ in range(n)]
    jax.block_until_ready(losses[-1])
    return time.perf_counter() - t0, losses


def run(system, traffic, args, clock0, compiles, tracer):
    """Runs the cell's window. Returns a dict: ``end_to_end`` values by
    metric name, ``facts`` the driver measured itself, ``registry`` (the
    program's metrics registry before and after the window),
    ``stretch_registry`` (the same at the edges of the traced stretch, or
    None), ``correct``, ``compared`` (each number that decided it, as
    (value, its limit, whether it holds)), ``attempted`` and ``failed``.
    ``tracer`` is None, or a function that gives the context manager of a
    traced window."""
    from paddle_tpu.observability import metrics as obs_metrics

    registry = obs_metrics.registry()
    first_s, first = _timed(system, 1)
    warm_n = traffic["warmup_steps"]
    warm_s, warm = _timed(system, warm_n)
    step_s = warm_s / warm_n
    n = max(1, round(args.seconds / step_s))
    traced_steps = 0
    stretch = None

    before = compiles.snapshot()
    registry_before = registry.snapshot()
    t_open = time.perf_counter()
    if tracer is not None:
        # a short traced stretch first, then the rest of the window
        # untraced: the rate that train_mfu reads is the untraced one
        traced_steps = max(2, min(n - 1, round(
            traffic["trace_seconds"] / step_s)))
        with tracer():
            stretch = [registry.snapshot()]
            _timed(system, traced_steps)
            stretch.append(registry.snapshot())
    rest = max(1, n - traced_steps)
    rest_s, losses = _timed(system, rest)
    t_close = time.perf_counter()
    moved = compiles.moved(before, compiles.snapshot())

    values = [float(np.asarray(x).reshape(-1)[0])
              for x in first + warm + losses[-1:]]
    not_finite = sum(not math.isfinite(v) for v in values)
    compared = {
        "losses_not_finite": (not_finite, 0, not_finite == 0),
        "last_loss_below_first": (values[-1], values[0],
                                  values[-1] < values[0]),
        "compiles_in_window": (moved, 0, moved == 0),
    }
    correct = all(holds for _value, _limit, holds in compared.values())
    print(f"# set-up: first step (compile or load) {first_s:.2f} s, "
          f"{warm_n} warm-up steps {warm_s:.2f} s", flush=True)
    print(f"# losses: first {values[0]:.4f}, warm-up "
          f"{[round(v, 4) for v in values[1:-1]]}, last of the window "
          f"{values[-1]:.4f}; {n} steps of {step_s * 1e3:.1f} ms; "
          f"{moved} compilations in the window", flush=True)
    steps = traced_steps + rest
    end_to_end = {
        "train_throughput": steps * system.samples_per_step
        / (t_close - t_open),
        "setup_s": t_open - clock0,
    }
    facts = {
        "window_s": t_close - t_open,
        "steps_per_s": rest / rest_s,
        "traced_steps": traced_steps,
        "compiles_in_window": moved,
        "load_s": first_s - step_s,
    }
    return {"end_to_end": end_to_end, "facts": facts,
            "registry": (registry_before, registry.snapshot()),
            "stretch_registry": stretch,
            "correct": correct, "compared": compared,
            "attempted": steps, "failed": 0}
