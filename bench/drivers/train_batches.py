"""How a training cell runs (traffic kind ``train_batches``).

The mix: ``batch`` rows in each of ``batches`` distinct batches, cycled.
Each entry of ``inputs`` is an array per row: ``{"dist": "normal",
"shape": [...]}`` (float32), ``{"dist": "uniform_int", "high": n, "shape":
[...]}`` (int32 in [0, n)), ``{"next_of": name}`` (the named input shifted
one place to the left, the last place wrapping) or ``{"ones_of": name}``
(float32 ones of its shape).

Set-up builds the compiled step and its state once, and drives it through
its first three steps on three distinct batches, through the same call and
feed as the window; their losses, the first gradient as the optimizer took
it and the change of the parameters over the three are kept for the
comparison. The window then dispatches steps back to back, at most two in
flight, for ``--seconds``. Once it has closed and the peak memory is read,
the program's state is freed and the plain reference follows the same
three steps from the same weights and batches.
"""
from __future__ import annotations

import collections
import time

from bench import compare, generator, harness, tracing
from bench.harness import Check, log

#: Steps that set-up drives and the reference follows.
CHECKED_STEPS = 3
#: Length of the traced window of a ``--trace 1`` run, in seconds.
TRACE_SECONDS = 3.0


def make_batches(spec: dict, key):
    """``spec["batches"]`` distinct batches on the default device, made in
    one jitted call from the PRNG ``key``: a list of dicts of arrays."""
    import jax
    import jax.numpy as jnp

    b, n = spec["batch"], spec["batches"]
    inputs = spec["inputs"]

    def make(key):
        out = {}
        for i, (name, s) in enumerate(sorted(inputs.items())):
            if "dist" not in s:
                continue
            k = jax.random.fold_in(key, i)
            shape = (n, b, *s.get("shape", ()))
            if s["dist"] == "normal":
                out[name] = jax.random.normal(k, shape, jnp.float32)
            elif s["dist"] == "uniform_int":
                out[name] = jax.random.randint(k, shape, 0, s["high"],
                                               jnp.int32)
            else:
                raise ValueError(f"input {name}: unknown dist {s['dist']!r}")
        for name, s in inputs.items():
            if "next_of" in s:
                out[name] = jnp.roll(out[s["next_of"]], -1, axis=2)
            elif "ones_of" in s:
                out[name] = jnp.ones(out[s["ones_of"]].shape, jnp.float32)
        return out

    stacked = jax.jit(make)(key)
    return [{k: v[i] for k, v in stacked.items()} for i in range(n)]


def run(*, cell: dict, cfg: dict, mod, traffic: dict, seed: int,
        seconds: float, trace: bool, devices, limits: dict, t0: float,
        trace_dir: str) -> dict:
    import jax
    import jax.numpy as jnp

    key = generator.seed_key(seed)
    prog = mod.Program(cfg, traffic)
    state = prog.init_state(jax.random.fold_in(key, 0))
    batches = make_batches(traffic, jax.random.fold_in(key, 1))
    if len(batches) < CHECKED_STEPS:
        raise ValueError(f"the checked steps need {CHECKED_STEPS} distinct "
                         f"batches; the mix makes {len(batches)}")
    before = jax.tree.map(jnp.copy, prog.params_of(state))
    step = jax.jit(prog.step_fn(), donate_argnums=0).lower(
        state, batches[0]).compile()
    log(f"{cell['name']}: step compiled at {time.perf_counter() - t0:.1f} s")

    state, program = checked_steps(prog, step, state, batches, before)
    del before
    setup_s = time.perf_counter() - t0
    log(f"{cell['name']}: set-up {setup_s:.1f} s; first losses "
        f"{program['losses']}")

    fed = CHECKED_STEPS
    losses = []

    def window(limit_s: float, traced: bool):
        nonlocal state, fed
        inflight = collections.deque()
        steps, start = 0, time.perf_counter()
        while True:
            with tracing.span("bench.train_step", traced):
                state, loss = step(state, batches[fed % len(batches)])
            fed += 1
            steps += 1
            losses.append(loss)
            inflight.append(loss)
            if len(inflight) > 2:
                with tracing.span("bench.sync", traced):
                    inflight.popleft().block_until_ready()
            if time.perf_counter() - start >= limit_s:
                break
        with tracing.span("bench.sync", traced):
            inflight[-1].block_until_ready()
        return steps, time.perf_counter() - start

    steps, window_s = window(seconds, False)
    step_s = window_s / steps
    log(f"{cell['name']}: {steps} steps in {window_s:.3f} s, "
        f"{step_s * 1e3:.2f} ms per step")
    traced = None
    if trace:
        tracing.start(trace_dir)
        with tracing.span(tracing.WINDOW_SPAN, True):
            traced_steps, _ = window(min(TRACE_SECONDS, seconds), True)
        jax.profiler.stop_trace()
        traced = tracing.Trace(tracing.read_xplane(trace_dir))
    failed = sum(1 for x in jax.device_get(losses) if not jnp.isfinite(x))
    device = harness.device_record(devices)
    del state, step, losses

    ref = reference_numbers(mod.Reference(cfg), key, batches)
    numbers = compare.train_numbers(program, ref)
    log(f"{cell['name']}: {numbers}")
    checks = [Check(k, numbers[k], v) for k, v in limits.items()]

    records = {"step_s": step_s, "steps": steps}
    if traced is not None:
        records["traced_steps"] = traced_steps
    metrics = {"train_step_ms": {"value": step_s * 1e3, "unit": "ms"},
               "setup_s": {"value": setup_s, "unit": "s"}}
    ctx = harness.Context(cfg=cfg, traffic=traffic, program=prog,
                          kind="train", device_kind=device["kind"],
                          chips=len(devices), records=records, trace=traced)
    return {"attempted": steps, "failed": failed, "checks": checks,
            "device": device, "end_to_end": metrics, "context": ctx}


def checked_steps(prog, step, state, batches: list, before):
    """Drive the compiled ``step`` through the first steps; returns the
    state and the program's losses, first gradient norms (as the optimizer
    took them) and change norms of each leaf."""
    first = []
    for i in range(CHECKED_STEPS):
        state, loss = step(state, batches[i])
        first.append(loss)
        if i == 0:
            grad = compare.leaf_norms(prog.first_grads_of(state))
    change = compare.change_norms(prog.params_of(state), before)
    return state, {"losses": [float(x) for x in first], "grad": grad,
                   "change": change}


def reference_numbers(ref, key, batches: list) -> dict:
    """The reference's losses, first clipped gradient norms and change
    norms over the checked steps, from the weights of ``key``."""
    import jax

    t = time.perf_counter()
    losses, grad, before, after = ref.steps(jax.random.fold_in(key, 0),
                                            batches[:CHECKED_STEPS])
    out = {"losses": losses, "grad": compare.leaf_norms(grad),
           "change": compare.change_norms(after, before)}
    log(f"reference: {CHECKED_STEPS} steps in {time.perf_counter() - t:.1f} "
        f"s; losses {losses}")
    return out


#: What ``calibrate.py`` can read for a training cell.
VARIANTS = ("program", "control", "half_batch")


def readings(cfg: dict, mod, traffic: dict, seeds: list, *,
             variants=VARIANTS, seconds: float = 0.0):
    """For each seed, the numbers of each variant against the float32
    reference: the program's first steps; the ``control`` (the reference
    one precision below what the configuration states); ``half_batch``
    (the reference with half of every batch left out). ``seconds`` is not
    used: training needs no window."""
    import jax
    import jax.numpy as jnp

    prog = mod.Program(cfg, traffic)
    truth_ref = mod.Reference(cfg)
    control_ref = mod.Reference(cfg, control=True)
    step = None
    for seed in seeds:
        key = generator.seed_key(seed)
        batches = make_batches(traffic, jax.random.fold_in(key, 1))
        read = {}
        if "program" in variants:
            state = prog.init_state(jax.random.fold_in(key, 0))
            before = jax.tree.map(jnp.copy, prog.params_of(state))
            if step is None:
                step = jax.jit(prog.step_fn(), donate_argnums=0).lower(
                    state, batches[0]).compile()
            state, read["program"] = checked_steps(prog, step, state,
                                                   batches, before)
            del state, before
        if "control" in variants:
            read["control"] = reference_numbers(control_ref, key, batches)
        if "half_batch" in variants:
            half = [{k: v[:len(v) // 2] for k, v in b.items()}
                    for b in batches]
            read["half_batch"] = reference_numbers(truth_ref, key, half)
        truth = reference_numbers(truth_ref, key, batches)
        yield seed, {k: compare.train_numbers(v, truth)
                     for k, v in read.items()}
