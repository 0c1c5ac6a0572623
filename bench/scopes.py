"""Device time by layer: which layer scope each device op of a traced
training step belongs to.

The program lowers each part of its train step under a layer scope
(``jax.named_scope``). XLA keeps the scope in the ``op_name`` metadata of
every HLO instruction the part lowers to, forward and backward
(``jit(train)/transpose(jvp(swin.attention))/mul``), and a fusion keeps
its root op's. A device op in the profiler's trace is named after its HLO
instruction (``fusion.103``), so the map from instruction names to
``op_name`` paths, read from the optimized HLO text of the step, gives
each op its layer: the innermost layer scope on its path, matched as a
whole segment inside any ``jvp(`` / ``transpose(`` wrapper. An op on no
layer scope's path is unattributed.

The layer scopes are those the benchmark reads: each per-layer metric's
reader names the scopes it sums in ``SCOPES``, so a new scope comes with
its own metric file and entry, and no list here changes.

The trace directory is gone by the time a metric reads, so the map comes
from the step itself: ``step_hlo`` lowers the cell's step again from its
program, with the same argument shapes, and compiles it, which the
persistent compilation cache that the run filled serves without compiling.
Every device op in the traced window has to be an instruction of that
module; where one is not, the module is not the one that ran, and there is
no split.

A program whose step carries none of the scopes (one older than them) has
nothing to split: every reader here then returns None, and its metric is
left out of the line.
"""
from __future__ import annotations

import functools
import os
import re
import weakref
from typing import Iterable, Optional

from bench import harness

#: An HLO instruction: its name, and its ``op_name`` metadata if any.
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+) = ')
_OP_NAME = re.compile(r'\bmetadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"')


def hlo_op_names(text: str) -> dict[str, str]:
    """instruction name -> ``op_name`` path ("" where the instruction
    carries no such metadata), for every instruction of an HLO module's
    text."""
    out = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            path = _OP_NAME.search(line, m.end())
            out[m.group(1)] = path.group(1) if path else ""
    return out


@functools.lru_cache(maxsize=None)
def layer_scopes() -> tuple[str, ...]:
    """The layer scopes the benchmark reads: the ``SCOPES`` of every
    per-layer metric's reader, in the order of ``per_layer``."""
    out: list[str] = []
    for m in harness.load_benchmark()["per_layer"]:
        mod = harness.load_module(
            os.path.join(harness.HERE, "metrics", f"{m['name']}.py"),
            m["name"])
        out += [s for s in getattr(mod, "SCOPES", ()) if s not in out]
    return tuple(out)


def layer_of(path: str, scopes: Iterable[str]) -> Optional[str]:
    """The innermost of ``scopes`` on an ``op_name`` path, or None.
    ``jit(f)/transpose(jvp(swin.attention))/swin.norm/mul`` gives
    ``swin.norm``; a scope counts only as a whole segment."""
    found = None
    for seg in re.split(r"[/()]", path):
        if seg in scopes:
            found = seg
    return found


def layer_ns(ops: list[tuple], names: dict[str, str],
             window: tuple[int, int], scopes: Iterable[str]
             ) -> dict[Optional[str], int]:
    """{layer scope or None: summed device time within ``window``} of one
    device's ops ``[(name, start_ns, end_ns)]``; ``names`` maps op names to
    ``op_name`` paths, and None collects the unattributed ops."""
    out: dict[Optional[str], int] = {}
    for name, s, e in ops:
        s, e = max(s, window[0]), min(e, window[1])
        if e > s:
            layer = layer_of(names.get(name, ""), scopes)
            out[layer] = out.get(layer, 0) + (e - s)
    return out


def split_s(trace, names: dict[str, str], scopes: Iterable[str]
            ) -> Optional[dict]:
    """{layer scope or None: device seconds in the window, mean over the
    trace's devices}; None where no instruction of the map names a scope,
    or where an op in the window is not an instruction of the map."""
    scopes = tuple(scopes)
    if not any(layer_of(p, scopes) for p in names.values()):
        return None
    lo, hi = trace.window
    if any(n not in names for ops in trace.ops.values()
           for n, s, e in ops if min(e, hi) > max(s, lo)):
        return None
    total: dict[Optional[str], float] = {}
    for ops in trace.ops.values():
        for layer, ns in layer_ns(ops, names, trace.window, scopes).items():
            total[layer] = total.get(layer, 0.0) + ns / 1e9 / len(trace.ops)
    return total


def step_hlo(ctx) -> str:
    """The optimized HLO text of the training cell's step: lowered again
    from ``ctx.program`` at the shapes the run fed it."""
    import jax

    from bench import generator
    from bench.drivers import train_batches

    prog, key = ctx.program, generator.seed_key(0)
    state = jax.eval_shape(prog.init_state, key)
    batch = jax.eval_shape(
        lambda k: train_batches.make_batches(ctx.traffic, k)[0], key)
    step = jax.jit(prog.step_fn(), donate_argnums=0)
    return step.lower(state, batch).compile().as_text()


#: Each traced window's split, read once for all of its metrics.
_SPLITS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def layer_ms(ctx, *scopes: Optional[str]) -> Optional[float]:
    """Device ms per traced step of the ops whose innermost layer scope is
    one of ``scopes`` (None: the unattributed ops), in a training cell's
    traced window; None where the run was not traced, its step carries
    no layer scope, or the trace holds an op the step's module lacks."""
    if ctx.kind != "train" or ctx.trace is None:
        return None
    if ctx.trace not in _SPLITS:
        _SPLITS[ctx.trace] = split_s(
            ctx.trace, hlo_op_names(step_hlo(ctx)), layer_scopes())
    split = _SPLITS[ctx.trace]
    if split is None:
        return None
    seconds = sum(split.get(s, 0.0) for s in scopes)
    return 1e3 * seconds / ctx.records["traced_steps"]
