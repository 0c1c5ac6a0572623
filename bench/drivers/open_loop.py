"""How a serving cell runs under an open loop (traffic kind ``open_loop``).

The mix:

``rate_per_s``
    arrivals per second, fixed in the cell; the gaps between arrivals are
    those of a Poisson process.
``slots``
    the server's slots.
``prompt_len``, ``output_len``
    length distributions (see ``generator.py``); the prompt tokens are
    uniform over ``[0, vocab)``, and decoding is greedy.
``grid``
    every seed gets the same ``grid`` prompt lengths, output lengths and
    gaps (each distribution's quantiles), in an order drawn from the seed.
``in_flight``
    requests already decoding when the window opens, as in the steady
    state: prompt lengths from ``prompt_len``, and what is left of their
    outputs from the residual of ``output_len`` (a request seen at a
    random moment of its decode).

Set-up submits the in-flight requests and ticks the server until each has
its first token, which compiles every program the window uses. The window
submits each arrival at its scheduled time and ticks the server
(``PagedServer.run(max_steps=1)``: admission, one prefill chunk, one decode
step over every decoding slot) for ``--seconds``; when the server has
nothing to do it waits for the next arrival. Times are the harness's
clock: a token is stamped when the tick that produced it returns, and a
request's time to first token runs from its scheduled arrival.

Once the window has closed and the peak memory is read, the server is
freed and the plain reference runs over a sample of the requests finished
in the window, drawn from the seed and holding the longest of them.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import numpy as np

from bench import compare, generator, harness, tracing
from bench.harness import Check, log

#: Length of the traced window of a ``--trace 1`` run, in seconds.
TRACE_SECONDS = 3.0
#: Served tokens the correctness sample gathers at least, and the most
#: requests it takes.
SAMPLE_TOKENS = 512
SAMPLE_REQUESTS = 8


class Stream:
    """The requests of an ``open_loop`` mix, from the seed."""

    def __init__(self, spec: dict, seed: int, vocab: int):
        rng = np.random.default_rng(seed)
        n, warm = spec["grid"], spec["in_flight"]
        self.rate = spec["rate_per_s"]
        self.prompt_lens = rng.permutation(
            generator.quantile_grid(spec["prompt_len"], n))
        self.output_lens = rng.permutation(
            generator.quantile_grid(spec["output_len"], n))
        self.gaps = rng.permutation(generator.exponential_grid(n))
        self.warm_prompt_lens = rng.permutation(
            generator.quantile_grid(spec["prompt_len"], warm))
        self.warm_output_lens = rng.permutation(
            generator.residual_grid(spec["output_len"], warm))
        self.rng, self.vocab, self.sent = rng, vocab, 0

    @property
    def max_total(self) -> int:
        """The most tokens a request can hold: prompt plus output."""
        return int(self.prompt_lens.max() + self.output_lens.max())

    def tokens(self, n: int) -> np.ndarray:
        return self.rng.integers(0, self.vocab, n, dtype=np.int32)

    def warm(self) -> list:
        """The in-flight requests: ``(prompt tokens, output length)``."""
        return [(self.tokens(p), int(o)) for p, o in
                zip(self.warm_prompt_lens, self.warm_output_lens)]

    def next(self) -> tuple[float, np.ndarray, int]:
        """The next arrival: ``(seconds since the one before, prompt
        tokens, output length)``."""
        i = self.sent % len(self.prompt_lens)
        self.sent += 1
        return (float(self.gaps[i]) / self.rate,
                self.tokens(self.prompt_lens[i]), int(self.output_lens[i]))


@dataclasses.dataclass
class Track:
    req: object
    submit_t: float
    seen: int = 0
    prefilled: int = 0
    first_t: Optional[float] = None
    last_t: Optional[float] = None


class Loop:
    """The open loop around one server: arrivals on their schedule, ticks,
    and the bookkeeping of every request's tokens."""

    def __init__(self, server, stream: Stream):
        self.server, self.stream = server, stream
        self.tracks: dict[int, Track] = {}
        self.rid = 0
        self.slot_rid: dict[int, int] = {}
        self.n_events = 0
        self.due: Optional[float] = None    # the next arrival's time
        self.pending = None                 # its (prompt, output length)

    def submit(self, prompt, max_new: int, at: float, traced: bool = False):
        from repro.launch.serve import Request

        req = Request(rid=self.rid, prompt=prompt, max_new=max_new)
        with tracing.span("bench.submit", traced):
            self.server.submit(req)
        self.tracks[self.rid] = Track(req, at)
        self.rid += 1

    def open(self, now: float):
        """Start the arrivals' clock at ``now``."""
        gap, *self.pending = self.stream.next()
        self.due = now + gap

    def arrive(self, now: float, traced: bool = False):
        """Submit every arrival due by ``now``."""
        while self.due <= now:
            self.submit(*self.pending, at=self.due, traced=traced)
            gap, *self.pending = self.stream.next()
            self.due += gap

    def idle(self) -> bool:
        return not self.server.queue and all(
            s is None for s in self.server.slots)

    def waiting(self) -> int:
        """Requests submitted that have no first token yet."""
        return sum(1 for tr in self.tracks.values() if tr.first_t is None)

    def tick(self, traced: bool = False) -> dict:
        """Submit what is due (waiting for the next arrival when the server
        has nothing to do), then one tick of the server. Returns what it
        did: tokens made, the time to first token of each request that got
        its first, requests finished, the context of every decoded slot
        and the (rows, start, last) of the prefill chunk."""
        if self.due is not None:
            self.arrive(time.perf_counter(), traced)
            if self.idle():
                time.sleep(max(0.0, self.due - time.perf_counter()))
                self.arrive(time.perf_counter(), traced)
        with tracing.span("bench.tick", traced):
            done = self.server.run(max_steps=1)
        now = time.perf_counter()
        out = {"tokens": 0, "first": [], "done": [], "decode": [],
               "prefill": []}
        events = self.server.events[self.n_events:]
        self.n_events += len(events)
        for ev in events:
            kind, *args = ev["legacy"]
            if kind == "admit":
                self.slot_rid[args[1]] = args[0]
            elif kind == "prefill_chunk":
                rid, _, n = args
                tr = self.tracks[rid]
                start, tr.prefilled = tr.prefilled, tr.prefilled + n
                out["prefill"].append(
                    (n, start, tr.prefilled == len(tr.req.prompt)))
            elif kind == "decode":
                for slot in args[0]:
                    req = self.tracks[self.slot_rid[slot]].req
                    out["decode"].append(len(req.prompt) + len(req.out) - 1)
        for rid, tr in self.tracks.items():
            n = len(tr.req.out)
            if n > tr.seen:
                out["tokens"] += n - tr.seen
                if tr.first_t is None:
                    tr.first_t = now
                    out["first"].append(now - tr.submit_t)
                tr.seen, tr.last_t = n, now
        for req in done:
            out["done"].append(self.tracks.pop(req.rid))
        return out

    def warm_up(self) -> int:
        """Submit the in-flight requests and tick until each has its first
        token, so that they are decoding and every program the window uses
        is compiled. Returns the ticks taken."""
        first = range(self.rid, self.rid + len(self.stream.warm_prompt_lens))
        now = time.perf_counter()
        for prompt, max_new in self.stream.warm():
            self.submit(prompt, max_new, at=now)
        ticks = 0
        while any(rid in self.tracks and self.tracks[rid].first_t is None
                  for rid in first):
            self.tick()
            ticks += 1
        return ticks

    def window(self, limit_s: float, traced: bool = False) -> dict:
        """Tick for ``limit_s`` seconds; the record of what the window saw."""
        server = self.server
        rec = {"ticks": [], "ttft": [], "tpot": [], "done": [], "tokens": 0}
        first_decode = len(server.decode_times_s)
        start = time.perf_counter()
        if self.due is None:
            self.open(start)
        while time.perf_counter() - start < limit_s:
            t = self.tick(traced)
            rec["ticks"].append({"decode": t["decode"],
                                 "prefill": t["prefill"]})
            rec["tokens"] += t["tokens"]
            rec["ttft"] += t["first"]
            for tr in t["done"]:
                rec["done"].append(tr)
                if len(tr.req.out) > 1:
                    rec["tpot"].append((tr.last_t - tr.first_t)
                                       / (len(tr.req.out) - 1))
        rec["window_s"] = time.perf_counter() - start
        rec["decode_times_s"] = server.decode_times_s[first_decode:]
        return rec


def start(cfg: dict, mod, traffic: dict, seed: int):
    """The program, its server and the loop around it, warmed up: (program,
    stream, loop, ticks taken to warm up)."""
    prog = mod.Program(cfg, traffic)
    stream = Stream(traffic, seed, cfg["vocab_size"])
    server = prog.make_server(weights_key(seed), slots=traffic["slots"],
                              max_tokens=stream.max_total)
    loop = Loop(server, stream)
    return prog, stream, loop, loop.warm_up()


def run(*, cell: dict, cfg: dict, mod, traffic: dict, seed: int,
        seconds: float, trace: bool, devices, limits: dict, t0: float,
        trace_dir: str) -> dict:
    import jax

    prog, stream, loop, warm = start(cfg, mod, traffic, seed)
    setup_s = time.perf_counter() - t0
    log(f"{cell['name']}: set-up {setup_s:.1f} s, {warm} ticks to prefill "
        f"{len(stream.warm_prompt_lens)} in-flight requests")

    rec = loop.window(seconds)
    log(f"{cell['name']}: {len(rec['ticks'])} ticks, {rec['tokens']} tokens, "
        f"{len(rec['done'])} requests done, {len(rec['ttft'])} first tokens "
        f"in {rec['window_s']:.3f} s; {loop.waiting()} waiting at the close")
    traced = traced_rec = None
    if trace:
        tracing.start(trace_dir)
        with tracing.span(tracing.WINDOW_SPAN, True):
            traced_rec = loop.window(min(TRACE_SECONDS, seconds), True)
        jax.profiler.stop_trace()
        traced = tracing.Trace(tracing.read_xplane(trace_dir))
    device = harness.device_record(devices)
    failed = len(loop.server.failed)
    del loop

    sample = pick_sample(rec["done"], seed)
    numbers = reference_numbers(mod.Reference(cfg, stream.max_total),
                                mod.make_params(cfg, weights_key(seed)),
                                sample)
    checks = [Check(k, numbers[k], v) for k, v in limits.items()]

    metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
    if rec["ttft"]:
        metrics["ttft_p90_ms"] = {
            "value": compare.percentile(rec["ttft"], 90) * 1e3, "unit": "ms"}
    if rec["tpot"]:
        metrics["tpot_p90_ms"] = {
            "value": compare.percentile(rec["tpot"], 90) * 1e3, "unit": "ms"}
    if rec["tokens"]:
        metrics["serve_out_tokens_per_s"] = {
            "value": rec["tokens"] / rec["window_s"], "unit": "tokens/s"}
    records = {"window": summary(rec)}
    if traced_rec is not None:
        records["traced"] = summary(traced_rec)
    ctx = harness.Context(cfg=cfg, traffic=traffic, program=prog,
                          kind="serve", device_kind=device["kind"],
                          chips=len(devices), records=records, trace=traced)
    return {"attempted": len(rec["done"]), "failed": failed,
            "checks": checks, "device": device, "end_to_end": metrics,
            "context": ctx}


def weights_key(seed: int):
    """The PRNG key of the weights of ``seed``'s run."""
    import jax

    return jax.random.fold_in(generator.seed_key(seed), 0)


def reference_numbers(ref, params, sample: list,
                      control: bool = False) -> dict:
    """The served tokens of ``sample`` against the plain reference ``ref``
    over the weights ``params`` (with ``control``, the fp8 control's first
    tokens in their place)."""
    t = time.perf_counter()
    numbers = compare.served_numbers([
        ref.served_gaps(params, tr.req.prompt, tr.req.out, control=control)
        for tr in sample])
    log(f"reference{' (control)' if control else ''}: {len(sample)} "
        f"requests, {sum(len(tr.req.out) for tr in sample)} served tokens "
        f"in {time.perf_counter() - t:.1f} s; {numbers}")
    return numbers


def summary(rec: dict) -> dict:
    """What the metric readers need of a window's record."""
    return {k: rec[k] for k in ("ticks", "window_s", "decode_times_s",
                                "tokens")}


def pick_sample(done: list, seed: int) -> list:
    """Finished requests to check: the longest (prompt and output) and then
    others drawn from the seed, until ``SAMPLE_TOKENS`` served tokens or
    ``SAMPLE_REQUESTS`` requests."""
    if not done:
        raise RuntimeError("no request finished in the window")
    order = sorted(range(len(done)), key=lambda i: -(
        len(done[i].req.prompt) + len(done[i].req.out)))
    rest = list(np.random.default_rng(seed).permutation(order[1:]))
    sample, tokens = [], 0
    for i in [order[0]] + rest:
        sample.append(done[i])
        tokens += len(done[i].req.out)
        if tokens >= SAMPLE_TOKENS or len(sample) >= SAMPLE_REQUESTS:
            break
    return sample


#: What ``calibrate.py`` can read for a serving cell.
VARIANTS = ("program", "control")


def readings(cfg: dict, mod, traffic: dict, seeds: list, *,
             variants=VARIANTS, seconds: float = 20.0):
    """For each seed, a window of ``seconds`` at the cell's own load, then
    the numbers of the requests it finished: the program's served tokens,
    and the fp8 control's first tokens at the same positions."""
    ref = None
    for seed in seeds:
        _, stream, loop, _ = start(cfg, mod, traffic, seed)
        done = loop.window(seconds)["done"]
        del loop
        sample = pick_sample(done, seed)
        ref = ref or mod.Reference(cfg, stream.max_total)
        params = mod.make_params(cfg, weights_key(seed))
        out = {name: reference_numbers(ref, params, sample,
                                       control=name == "control")
               for name in variants}
        del params
        out["sample"] = {"requests": len(sample), "served_tokens": sum(
            len(tr.req.out) for tr in sample)}
        yield seed, out
