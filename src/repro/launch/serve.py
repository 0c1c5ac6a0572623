"""Serving drivers: continuous batching over a dense KV cache (baseline)
and over the paged KV cache (DESIGN.md §7).

Both servers batch around the same shape-stable decode macro-step — every
occupied slot advances one token per step (a prompt token while prefilling,
the fed-back greedy token while decoding), inactive slots are masked — so
finished slots refill between steps without retracing.

``BatchedServer`` is the dense baseline: a ``(num_slots, max_seq)`` KV
rectangle allocated up front, every prompt token paying a full-batch step.
``PagedServer`` is the production engine: fixed-size pages in a shared pool
(``parallel.cache.PagePool``), per-slot page tables, admission by free-page
budget (worst-case pages reserved up front, so preemption-free FIFO decode
never starves the pool mid-request), chunked batch-1 prefill
(``launch.steps.make_paged_prefill_step``) interleaved with decode
macro-steps — pages granted a chunk's worth at a time from the
reservation — and on-demand page allocation at decode page boundaries.

Heterogeneous serving (paper §4.4, DESIGN.md §6/§7): for the dense baseline
``--hetero-latencies`` builds an Eq. 1 plan over the slot dim (tail slots
masked); for the paged engine the same plan becomes per-device PAGE-POOL
shares (``parallel.cache.page_shares``) — all slots stay schedulable, each
device group's admissions are budgeted against its share of pages.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs as cfglib
from repro import obs
from repro.common import cdiv, enable_compile_cache, tree_bytes
from repro.core import hetero as hetero_lib
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_mesh, split_model_axis
from repro.models import lm
from repro.parallel.cache import PagePool, PrefixIndex, page_shares
from repro.parallel.sharding import ParallelConfig, split_tree, tree_shardings
from repro.runtime import faults as faults_lib


@dataclass
class Request:
    """One serving request: prompt tokens in, up to ``max_new`` generated
    tokens out, sampled greedily at ``temperature`` 0 (the default) or
    categorically under the request's own ``seed``.

    Robustness fields (DESIGN.md §9): ``priority`` orders preemption under
    page exhaustion (lower preempts first); ``deadline_s`` bounds wall time
    from submission; a faulted request retries up to the engine's
    ``max_retries`` with ``aborts``/``preemptions`` counting the restarts.
    A retry clears ``out`` and replays from the prompt — sampling keys
    derive only from ``(seed, len(out))``, so the replayed stream is
    token-identical to an unfaulted run. A permanently failed request
    carries the reason in ``error``."""
    rid: int
    prompt: np.ndarray           # (S_prompt,)
    max_new: int
    out: list = field(default_factory=list)
    temperature: float = 0.0     # 0 = greedy argmax
    seed: int = 0                # per-request sampling seed
    priority: int = 0            # higher admits over lower under pressure
    deadline_s: Optional[float] = None   # wall-clock budget from submit
    submit_t: float = 0.0        # stamped by submit() (engine clock)
    aborts: int = 0              # fault/NaN retries consumed
    preemptions: int = 0         # page-pressure evictions (not retries)
    error: Optional[str] = None  # permanent failure reason


def argmax_token(logits_row) -> int:
    """THE engine-wide greedy convention (DESIGN.md §11): upcast the row
    to f32 FIRST, then argmax; ties resolve to the LOWEST index (first
    occurrence — ``np.argmax`` and ``jnp.argmax`` both guarantee this, so
    the host-side argmax here and the batched device argmax in
    ``_greedy`` agree on every row). Every greedy selection —
    ``next_token``, the batch ``_greedy`` helper, and the draft-side
    greedy in ``launch.spec`` — routes through this one convention, so
    draft-vs-target acceptance and reference replay can never diverge on
    a row where bf16 downcasting manufactures a tie the f32 original
    breaks (regression-pinned in tests/test_spec.py). Host-side on
    purpose: the speculative verify loop calls this per accepted row, and
    a device dispatch per row would eat the speedup it exists to measure."""
    row = np.asarray(logits_row, np.float32).reshape(-1)
    return int(np.argmax(row))


def _greedy(logits) -> np.ndarray:
    # batch form of argmax_token: f32 upcast BEFORE the device argmax,
    # lowest index on ties — one convention across all engines.
    rows = jnp.asarray(logits)[..., -1, :].astype(jnp.float32)
    return np.asarray(jnp.argmax(rows, axis=-1)).reshape(-1)


def next_token(logits_row, req: Request) -> int:
    """Engine-independent next-token selection: greedy argmax at
    ``temperature <= 0`` (``argmax_token`` — the shared f32-upcast device
    convention), else categorical sampling at a key derived ONLY from
    ``(req.seed, len(req.out))`` — the same seed threading in
    ``BatchedServer``, ``PagedServer``, the batch-1 reference, and the
    speculative verify loop (which appends each accepted token before
    sampling the next, so its keys advance identically), so a request's
    sampled stream is a pure function of its own logits and seed, never
    of its batch-mates, slot id, or engine
    (tests/test_serve_parity.py pins this)."""
    if req.temperature <= 0.0:
        return argmax_token(logits_row)
    row = np.asarray(logits_row, np.float32).reshape(-1)
    key = jax.random.fold_in(
        jax.random.PRNGKey(req.seed), len(req.out))
    return int(jax.random.categorical(
        key, jnp.asarray(row) / req.temperature))


def reference_stream(cfg, pcfg, mesh, params, req: Request, *,
                     max_seq: int, step=None) -> list[int]:
    """One-request-at-a-time dense-cache reference stream: batch-1 prefill
    (token by token) then decode through ``next_token`` — the ground truth
    the parity matrix pins both batched servers against, for greedy AND
    sampled requests."""
    if step is None:
        step = jax.jit(steps_lib.make_serve_step(
            cfg, pcfg, mesh, (1, 1, cfg.d_model)))
    ref = dataclasses.replace(req, out=[])
    cache = lm.init_cache(cfg, 1, max_seq)
    logits = None
    for tok in ref.prompt:
        logits, cache = step(
            params, {"tokens": jnp.asarray([[tok]], jnp.int32)}, cache)
    ref.out.append(next_token(logits[0, -1], ref))
    while len(ref.out) < ref.max_new:
        logits, cache = step(
            params, {"tokens": jnp.asarray([[ref.out[-1]]], jnp.int32)},
            cache)
        ref.out.append(next_token(logits[0, -1], ref))
    return ref.out


def greedy_reference(cfg, pcfg, mesh, params, prompt, max_new, *,
                     max_seq: int, step=None) -> list[int]:
    """Greedy ``reference_stream`` under the pre-sampling signature."""
    return reference_stream(
        cfg, pcfg, mesh, params,
        Request(rid=-1, prompt=np.asarray(prompt), max_new=max_new),
        max_seq=max_seq, step=step)


# ---------------------------------------------------------------------------
# dense baseline
# ---------------------------------------------------------------------------

@dataclass
class _Slot:
    req: Request
    pos: int = 0        # prompt tokens consumed


class BatchedServer:
    """Dense-cache continuous batching: the KV rectangle
    ``(num_slots, max_seq)`` is allocated up front (the memory
    over-allocation the paged engine exists to kill) and every prompt token
    of every request costs one full-batch macro-step."""

    def __init__(self, cfg, pcfg, mesh, *, num_slots: int, max_seq: int,
                 params, valid_slots: Optional[list] = None):
        self.cfg, self.pcfg, self.mesh = cfg, pcfg, mesh
        self.num_slots = num_slots
        self.max_seq = max_seq
        self.params = params
        self.cache = lm.init_cache(cfg, num_slots, max_seq)
        self.serve_step = jax.jit(steps_lib.make_serve_step(
            cfg, pcfg, mesh, (num_slots, 1, cfg.d_model)))
        self.slots: list[Optional[_Slot]] = [None] * num_slots
        self.queue: deque[Request] = deque()
        # Heterogeneous plan over the slot dim (DESIGN.md §6): only each
        # device's Eq. 1 share of slots is schedulable.
        self.free = sorted(valid_slots if valid_slots is not None
                           else range(num_slots), reverse=True)
        self.decode_times_s: list = []
        self.admissions = 0

    def submit(self, req: Request):
        if len(req.prompt) + req.max_new - 1 > self.max_seq:
            raise ValueError(
                f"request {req.rid} needs {len(req.prompt) + req.max_new - 1}"
                f" cache rows > max_seq {self.max_seq}")
        self.queue.append(req)

    def _admit(self):
        while self.free and self.queue:
            slot = self.free.pop()
            req = self.queue.popleft()
            self.cache = lm.reset_slot(self.cfg, self.cache, slot)
            self.slots[slot] = _Slot(req)
            self.admissions += 1

    def _macro_step(self) -> list[Request]:
        tokens = np.zeros((self.num_slots, 1), np.int32)
        active = np.zeros((self.num_slots,), bool)
        for slot, st in enumerate(self.slots):
            if st is None:
                continue
            active[slot] = True
            tokens[slot, 0] = (st.req.prompt[st.pos]
                               if st.pos < len(st.req.prompt)
                               else st.req.out[-1])
        t0 = time.perf_counter()
        logits, self.cache = self.serve_step(
            self.params,
            {"tokens": jnp.asarray(tokens), "active": jnp.asarray(active)},
            self.cache,
        )
        nxt = np.asarray(logits)
        self.decode_times_s.append(time.perf_counter() - t0)
        done = []
        for slot, st in enumerate(self.slots):
            if st is None:
                continue
            st.pos += 1
            if st.pos >= len(st.req.prompt):
                st.req.out.append(next_token(nxt[slot, -1], st.req))
                if len(st.req.out) >= st.req.max_new:
                    done.append(st.req)
                    self.slots[slot] = None
                    self.free.append(slot)
        return done

    def run(self, max_steps: int = 100000) -> list[Request]:
        done = []
        steps = 0
        while (self.queue or any(self.slots)) and steps < max_steps:
            self._admit()
            done.extend(self._macro_step())
            steps += 1
        return done


# ---------------------------------------------------------------------------
# paged engine
# ---------------------------------------------------------------------------

@dataclass
class _PagedSlot:
    req: Request
    group: int
    order: int           # admission sequence (FIFO prefill priority)
    need: int            # worst-case pages for the request
    reserved: int        # pages reserved from the pool at admission
    pages: list = field(default_factory=list)  # phys page per logical (0 =
    pos: int = 0         # prompt tokens consumed       # reclaimed)
    length: int = 0      # tokens resident in the paged cache
    reclaimed: int = 0   # leading logical pages released behind the window
    allocated: int = 0   # pool.alloc calls (reservations consumed)
    matched: int = 0     # prefix-cache pages mapped in at refcount+1


def derive_roles(token_counts) -> list[str]:
    """Disaggregated prefill/decode role per device class (DESIGN.md §7):
    the fastest class(es) — largest Eq. 1 token share — take the
    compute-bound prefill role, the rest take the bandwidth-bound decode
    role. Uniform (or single-class) plans collapse to ``"both"`` for every
    class, which reduces the server to the single-loop engine."""
    counts = list(token_counts)
    if len(set(counts)) < 2:
        return ["both"] * len(counts)
    top = max(counts)
    return ["prefill" if c == top else "decode" for c in counts]


class PagedServer:
    """Continuous batching over the paged KV cache (DESIGN.md §7).

    Admission is by free-page budget: a request is admitted only when its
    worst-case page count ``ceil((prompt + max_new - 1) / page_size)`` can
    be reserved (per device group under a hetero plan), which makes the
    preemption-free FIFO safe — every physical ``alloc`` draws from the
    reservation and cannot fail. Prefill grants a chunk's worth of pages
    before each ``prefill_chunk``-token batch-1 chunk (interleaved with
    the decode macro-steps of the already-running slots); decode grants
    one page per boundary crossing; on all-windowed stacks pages wholly
    behind the window return to the pool mid-request.
    """

    def __init__(self, cfg, pcfg, mesh, *, num_slots: int, page_size: int,
                 num_pages: int, max_pages_per_slot: int, params,
                 prefill_chunk: int = 16, plan=None, kv_quant=None,
                 prefix_cache: bool = False, disagg: bool = False,
                 max_retries: int = 2, audit: bool = False,
                 clock=time.perf_counter):
        self.cfg, self.mesh = cfg, mesh
        self.max_retries = max_retries
        self.audit = audit
        self.clock = clock
        self.kv_quant = None if kv_quant in (None, "none") else kv_quant
        # The plan's Eq. 1 shares are honored as page budgets (below), not
        # as masked tail rows — every slot is schedulable, so only the
        # token_counts half is stripped from the step config. The Eq. 2
        # half (tp_latencies / hidden_splits) stays: the auto-mode roofline
        # keeps pricing layers with the uneven-tile term, matching the
        # plan-padded weights the caller initialised.
        self.pcfg = pcfg
        if pcfg.hetero_plan is not None:
            self.pcfg = dataclasses.replace(
                pcfg,
                hetero_plan=dataclasses.replace(
                    pcfg.hetero_plan, token_counts=None, token_capacity=None),
            )
        self.num_slots = num_slots
        self.page_size = page_size
        self.max_pages_per_slot = max_pages_per_slot
        self.prefill_chunk = prefill_chunk
        self.params = params
        self.cache = lm.init_paged_cache(cfg, num_slots, num_pages, page_size,
                                         kv_quant=self.kv_quant)

        # int8 paged-KV (DESIGN.md §8): admission budgets in the SMALLER
        # page bytes, so an equal-HBM pool holds proportionally more pages
        # and admits more concurrent requests.
        self.page_bytes = lm.paged_kv_page_bytes(cfg, page_size,
                                                 kv_quant=self.kv_quant)
        shares = None
        self.groups = [0] * num_slots
        # Per-class Eq. 1 weights, kept for elastic shrink: after a device
        # dropout the surviving classes' weights re-derive the pool shares
        # and roles (DESIGN.md §9).
        self.class_weights = (list(plan.token_counts)
                              if plan is not None else [1])
        if plan is not None:
            shares = page_shares(plan.token_counts, num_pages - 1)
            n_g = len(shares)
            if num_slots < n_g:
                raise ValueError(
                    f"{num_slots} slots cannot cover {n_g} device groups "
                    f"(a group without slots could never admit)")
            self.groups = [s * n_g // num_slots for s in range(num_slots)]
        self.pool = PagePool(num_pages, page_bytes=self.page_bytes,
                             shares=shares)

        # Window page reclamation: when EVERY attention layer is windowed
        # (e.g. mixtral's all-SWA stack), a page wholly behind the window
        # is dead — no layer will ever read it — and goes back to the pool
        # mid-request. Mixed local/global stacks keep everything (the
        # global layers read the full history through the shared table).
        attn_idx = [i for i in range(cfg.num_layers)
                    if cfg.layer_kind(i) == "attn"]
        self.reclaim_window = (
            cfg.window
            if cfg.window > 0 and attn_idx
            and all(cfg.attn_kind(i) == "local" for i in attn_idx)
            else None
        )

        # Prefix sharing (DESIGN.md §7): a radix index over FULL prompt
        # pages, each node holding one pool refcount. Only valid when every
        # period layer is attention — recurrent layers carry per-slot state
        # that pages do not capture, so a skipped prefix would silently
        # decode from a zero recurrent state.
        self.index = None
        if prefix_cache:
            if any(cfg.layer_kind(i) != "attn"
                   for i in range(cfg.num_layers)):
                raise ValueError(
                    "prefix_cache requires an all-attention stack: "
                    "recurrent layers keep per-slot state outside the KV "
                    "pages, so a shared prefix cannot be skipped")
            self.index = PrefixIndex(page_size)

        # Disaggregated prefill/decode roles (DESIGN.md §7): each slot is
        # tagged "prefill", "decode", or "both". Under a hetero plan the
        # tag comes from the slot's device class via derive_roles; without
        # one, an even half/half split. Single-role-class plans collapse to
        # "both" everywhere == the single-loop engine (pinned by
        # tests/test_disagg.py).
        self.disagg = disagg
        self.roles = ["both"] * num_slots
        if disagg:
            if plan is not None:
                group_roles = derive_roles(plan.token_counts)
                self.roles = [group_roles[self.groups[s]]
                              for s in range(num_slots)]
            else:
                if num_slots < 2:
                    raise ValueError("disagg needs >= 2 slots")
                self.roles = ["prefill" if s < num_slots // 2 else "decode"
                              for s in range(num_slots)]
            if "prefill" in self.roles and "decode" not in self.roles:
                raise ValueError(
                    "disaggregated plan has prefill-only slots but no "
                    "decode-capable slot — finished prefills could never "
                    "hand off")

        # Speculative decoding (DESIGN.md §11): constructing a
        # launch.spec.SpecDecoder over this server attaches itself here;
        # when set, _decode_tick delegates whole verify rounds to it.
        self.spec = None

        self.table = np.zeros((num_slots, max_pages_per_slot), np.int32)
        self._build_steps()
        self.slots: list[Optional[_PagedSlot]] = [None] * num_slots
        self.queue: deque[Request] = deque()
        self.free = sorted(range(num_slots), reverse=True)
        self.decode_times_s: list = []
        self.admissions = 0
        self.admission_log: list[int] = []   # rids, in admission order
        self._order = 0
        # Scheduler events (DESIGN.md §12): each entry holds the legacy
        # positional tuple — exposed unchanged through the ``trace``
        # property, the observable schedule the disagg invariants,
        # degenerate-reduction, and chaos tests pin — plus a monotonic
        # timestamp and a ``reason`` field, mirrored into the process-wide
        # obs event log when enabled. Tuple kinds: ("admit", rid, slot),
        # ("prefill_chunk", rid, slot, n), ("decode", (slots...)),
        # ("transfer", rid, src, dst), ("finish", rid, slot), ("abort",
        # rid, slot, reason), ("preempt", rid, slot), ("recover",),
        # ("shrink", survivors), ("rollback", rid, slot, n),
        # ("spec_verify", rid, slot, n_valid, accepted), ("fail", rid,
        # reason).
        self.events: list[dict] = []
        self.ttft_s: dict[int, float] = {}   # rid -> first-token latency
        # Router telemetry drain (collect_router_stats): step outputs
        # grow a stats pytree, pushed here and flushed at dump time.
        self.router_drain = None
        if self.pcfg.collect_router_stats and cfg.moe is not None:
            self.router_drain = obs.RouterStatsDrain(
                obs.registry, cfg.moe.num_experts, phase="serve")
        # Periodic Prometheus dumps from inside run() (0 = final only,
        # driven by the CLI's --metrics/--metrics-interval).
        self.obs_dump_every = 0
        self.obs_dump_path: Optional[str] = None
        obs.maybe_register(self)
        obs.maybe_register(self.pool)
        if self.index is not None:
            obs.maybe_register(self.index)
        self.transfers = 0
        self.failed: list[Request] = []      # permanently failed requests
        self.aborts = 0                      # fault/NaN slot aborts
        self.preemptions = 0                 # page-pressure evictions
        self.engine_recoveries = 0           # step-fn rebuilds
        self._run_t0 = 0.0

    def _build_steps(self):
        """(Re)build the jitted decode/prefill steps. Called at
        construction and by engine-level recovery (``_recover_engine``),
        which re-jits after an injected step failure — the page tables,
        pool, and cache are host/functional state that survives the
        rebuild, so live requests resume where they were."""
        self.serve_step = jax.jit(steps_lib.make_paged_serve_step(
            self.cfg, self.pcfg, self.mesh,
            (self.num_slots, 1, self.cfg.d_model), self.page_size))
        self.prefill_step = jax.jit(steps_lib.make_paged_prefill_step(
            self.cfg, self.pcfg, self.mesh, self.page_size))
        # Handoff/CoW-copy steps are built lazily on first use: most runs
        # never transfer a slot or copy a page, and tests monkeypatch the
        # two eager steps above.
        self._handoff_step = None
        self._copy_step = None
        # the speculative score step lives on the SpecDecoder; drop it so
        # engine recovery re-jits it too
        spec = getattr(self, "spec", None)
        if spec is not None:
            spec.reset_steps()

    def _event(self, name: str, *args, reason: Optional[str] = None):
        """Record one scheduler event: the legacy positional tuple (the
        ``trace`` view), a monotonic-clock stamp, and an optional reason —
        mirrored into the process-wide obs event log when enabled."""
        t = self.clock()
        self.events.append({"name": name, "t": t,
                            "legacy": (name, *args), "reason": reason})
        obs.events.emit(f"serve.{name}", reason=reason, t=t,
                        detail=list(args))

    @property
    def trace(self) -> list[tuple]:
        """The legacy timestamp-free event tuples, in order (the schedule
        view the invariant tests compare across engine configurations)."""
        return [e["legacy"] for e in self.events]

    def obs_metrics(self) -> dict:
        """Scheduler counters for registry snapshot polling."""
        return {
            "repro_serve_admissions_total": self.admissions,
            "repro_serve_transfers_total": self.transfers,
            "repro_serve_aborts_total": self.aborts,
            "repro_serve_preemptions_total": self.preemptions,
            "repro_serve_engine_recoveries_total": self.engine_recoveries,
            "repro_serve_failed_requests_total": len(self.failed),
            "repro_serve_queue_depth": len(self.queue),
            "repro_serve_live_slots": sum(
                s is not None for s in self.slots),
        }

    def _unpack_step(self, out):
        """Normalise a step's flag-dependent arity: with
        ``collect_router_stats`` every jitted step returns a trailing
        stats pytree, pushed (asynchronously) onto the router drain."""
        if self.pcfg.collect_router_stats:
            a, cache, rstats = out
            if self.router_drain is not None:
                self.router_drain.push(rstats)
            return a, cache
        return out

    def _dump_metrics(self):
        """Flush the router drain and write a Prometheus snapshot (used
        for the periodic in-run dumps; the CLI also dumps at exit)."""
        if self.router_drain is not None:
            self.router_drain.flush()
        if self.obs_dump_path:
            obs.dump_prometheus(obs.registry, self.obs_dump_path)

    def _need_pages(self, req: Request) -> int:
        # cache rows written = prompt + fed-back outputs (the last
        # generated token is never fed back).
        return cdiv(len(req.prompt) + req.max_new - 1, self.page_size)

    def submit(self, req: Request):
        if len(req.prompt) < 1 or req.max_new < 1:
            raise ValueError(f"request {req.rid}: empty prompt or max_new")
        if self._need_pages(req) > self.max_pages_per_slot:
            raise ValueError(
                f"request {req.rid} needs {self._need_pages(req)} pages "
                f"> max_pages_per_slot {self.max_pages_per_slot}")
        if self._need_pages(req) > max(self.pool.shares):
            raise ValueError(
                f"request {req.rid} needs {self._need_pages(req)} pages "
                f"> largest group share {max(self.pool.shares)} — it could "
                f"never admit (FIFO would deadlock behind it)")
        req.submit_t = self.clock()
        self.queue.append(req)

    # -- scheduling ticks -----------------------------------------------------

    def _try_reserve_evicting(self, n: int, group: int) -> bool:
        """``try_reserve`` with prefix-cache backpressure: when the free
        budget is short, evict LRU refcount-1 trie nodes — pages only the
        index still holds — back into the pool until the reservation fits
        or the index runs dry."""
        while not self.pool.try_reserve(n, group):
            if self.index is None or not self.index.evict_lru(self.pool):
                return False
        return True

    def _admit(self):
        """Strict FIFO: the queue head admits as soon as ANY free
        prefill-capable slot's group can reserve its worst-case pages;
        nothing overtakes it (head-of-line blocking is what makes FIFO
        starvation-free).

        With the prefix cache on, admission first matches the prompt
        against the radix index (capped at ``(plen - 1) // page_size``
        pages so at least one suffix token always prefills and produces
        the first-token logits), forks the matched pages — refcount+1,
        zero budget cost, and eviction-proof from that moment — and only
        reserves pages for the uncached remainder."""
        while self.queue and self.free:
            req = self.queue[0]
            need = self._need_pages(req)
            matched: list[int] = []
            if self.index is not None:
                plen = len(req.prompt)
                matched = self.index.match(
                    req.prompt, (plen - 1) // self.page_size)
                if matched:
                    self.pool.fork(matched)
            reserve_n = need - len(matched)
            slot = None
            for s in reversed(self.free):        # lowest slot id first
                if self.roles[s] == "decode":
                    continue
                if self._try_reserve_evicting(reserve_n, self.groups[s]):
                    slot = s
                    break
            if slot is None:
                if matched:
                    self.pool.release(matched)   # undo the admission forks
                # Graceful degradation under page exhaustion (DESIGN.md
                # §9): rather than stalling admission behind a full pool,
                # evict the lowest-priority decoding request (strictly
                # below the head's priority) back into the queue and retry
                # the head. A preemption is not a fault: it does not
                # consume the victim's retry budget, and the victim
                # re-admits right behind the head.
                if self._preempt_for(req):
                    continue
                return
            self.queue.popleft()
            self.free.remove(slot)
            m = len(matched) * self.page_size
            self.cache = lm.reset_slot(self.cfg, self.cache, slot, length=m)
            st = _PagedSlot(req, self.groups[slot], self._order, need,
                            reserved=reserve_n, pages=list(matched),
                            pos=m, length=m, matched=len(matched))
            self._order += 1
            self.admissions += 1
            self.admission_log.append(req.rid)
            self.table[slot, :] = 0
            self.table[slot, :len(matched)] = matched
            self.slots[slot] = st
            self._event("admit", req.rid, slot)

    def _cow_page(self, slot: int, st: _PagedSlot, j: int):
        """Copy-on-write guard: logical page ``j`` is about to be written
        but its physical page is shared (refcount > 1). Reserve+alloc a
        private replacement, copy the payload, repoint table and slot, and
        surrender the shared reference. The scheduler's own write pattern
        never triggers this — decode writes land strictly past the full
        prompt pages the index shares — so this is the defensive pool-level
        guarantee (exercised directly by tests/test_page_refcount.py)."""
        if not self._try_reserve_evicting(1, st.group):
            raise RuntimeError(
                f"slot {slot}: cannot reserve a CoW page for logical "
                f"page {j}")
        st.reserved += 1
        src = st.pages[j]
        dst = self.pool.cow(src, st.group)
        st.allocated += 1
        if self._copy_step is None:
            self._copy_step = jax.jit(
                steps_lib.make_page_copy_step(self.cfg))
        self.cache = self._copy_step(
            self.cache, jnp.int32(src), jnp.int32(dst))
        st.pages[j] = dst
        self.table[slot, j] = dst

    def _ensure_pages(self, slot: int, st: _PagedSlot, length: int):
        """Back every position below ``length`` with a physical page,
        drawing from the request's admission reservation: a chunk's worth
        at once before a prefill tick (the bulk grant), one page at a
        decode boundary. Granting at use (not all at admission) is what
        lets window reclamation bound an SWA request's live pages below
        its total page count. The first page the coming write touches is
        CoW-resolved if shared."""
        j = st.length // self.page_size
        if j < len(st.pages) and st.pages[j] != 0 \
                and self.pool.refcount(st.pages[j]) > 1:
            self._cow_page(slot, st, j)
        while (length - 1) // self.page_size >= len(st.pages):
            st.pages.append(self.pool.alloc(st.group))
            st.allocated += 1
            self.table[slot, len(st.pages) - 1] = st.pages[-1]

    def _reclaim(self, slot: int, st: _PagedSlot):
        """Release pages wholly behind the attention window: logical page
        ``j`` is dead once ``(j+1) * page_size <= length - window`` (the
        next read starts at ``length + 1 - window``, so this is
        conservative). The table entry drops to the sink; attention masks
        the positions regardless, so a reused page's new contents are
        never observable."""
        if self.reclaim_window is None:
            return
        dead = (st.length - self.reclaim_window) // self.page_size
        while st.reclaimed < dead:
            j = st.reclaimed
            self.pool.release([st.pages[j]], st.group)
            st.pages[j] = 0
            self.table[slot, j] = 0
            st.reclaimed += 1

    def _rollback(self, slot: int, n: int):
        """Un-write the last ``n`` speculative cache rows by truncation
        only (DESIGN.md §11): shrink the slot's device ``len`` (paged
        attention masks every row at and past it — ``lm.rollback_slot``),
        pop now-unbacked tail pages back to the request's own admission
        RESERVATION (``PagePool.rollback``, never the free budget: the
        request is still live and must re-grow grant-by-grant), and zero
        their table entries. The popped pages are strictly decode-region
        — past any prefix-matched prompt page — so they are always
        refcount-1; a shared page here trips the pool's hard error rather
        than corrupting a CoW sibling. The sampling key needs no explicit
        re-derivation: keys are a pure function of ``(seed, len(out))``
        and rejected tokens were never appended to ``out``."""
        st = self.slots[slot]
        if n <= 0:
            return
        new_len = st.length - n
        assert new_len >= len(st.req.prompt), (new_len, len(st.req.prompt))
        if self.reclaim_window is not None and st.reclaimed:
            # reclamation must only ever have run at committed lengths
            # (the spec tick reclaims AFTER rollback), so no reclaimed
            # page can re-enter the rolled-back window
            assert st.reclaimed * self.page_size <= max(
                new_len - self.reclaim_window, 0), \
                "rollback would rewind into window-reclaimed pages"
        keep = cdiv(new_len, self.page_size)
        dropped = []
        while len(st.pages) > keep:
            p = st.pages.pop()
            self.table[slot, len(st.pages)] = 0
            if p != 0:
                dropped.append(p)
        if dropped:
            self.pool.rollback(dropped, st.group)
            st.allocated -= len(dropped)
        self.cache = lm.rollback_slot(self.cfg, self.cache, slot, new_len)
        st.length = new_len
        self._event("rollback", st.req.rid, slot, n, reason="speculative rows rejected")

    def _finish(self, slot: int, st: _PagedSlot, done: list):
        done.append(st.req)
        self.pool.release([p for p in st.pages if p != 0], st.group,
                          unused_reserved=st.reserved - st.allocated)
        self.table[slot, :] = 0
        self.slots[slot] = None
        self.free.append(slot)
        if self.spec is not None:
            self.spec.forget(st.req.rid)
        self._event("finish", st.req.rid, slot)

    # -- failure handling (DESIGN.md §9) --------------------------------------

    def _release_slot(self, slot: int, st: _PagedSlot):
        """Return EVERYTHING a live slot holds to the pool: one reference
        per non-reclaimed page (window-reclaimed entries are already 0)
        plus the unconsumed tail of its admission reservation — the same
        accounting as ``_finish``, so refcounts, owner-group budgets, and
        the prefix trie stay consistent on every abort path (the
        structural oracle in tests/test_page_refcount.py pins this)."""
        self.pool.release([p for p in st.pages if p != 0], st.group,
                          unused_reserved=st.reserved - st.allocated)
        self.table[slot, :] = 0
        self.slots[slot] = None
        self.free.append(slot)
        if self.spec is not None:
            self.spec.forget(st.req.rid)

    def _fail_request(self, req: Request, reason: str):
        req.error = reason
        req.out.clear()
        self.failed.append(req)
        self._event("fail", req.rid, reason, reason=reason)

    def _abort_slot(self, slot: int, *, reason: str, requeue_at: int = 0,
                    count_retry: bool = True):
        """Tear a live request out of its slot: release all pages +
        reservations, clear the generated stream (sampling keys depend
        only on ``(seed, len(out))``, so the replay is token-identical),
        and either re-enqueue at ``requeue_at`` or fail permanently once
        the retry budget is spent. Re-admission goes through the prefix
        cache, so a retry re-prefills only the uncached suffix."""
        st = self.slots[slot]
        req = st.req
        self._release_slot(slot, st)
        req.out.clear()
        self._event("abort", req.rid, slot, reason, reason=reason)
        if count_retry:
            req.aborts += 1
            self.aborts += 1
            if req.aborts > self.max_retries:
                self._fail_request(
                    req, f"retries exhausted ({self.max_retries}) "
                         f"after {reason}")
                return
        self.queue.insert(min(requeue_at, len(self.queue)), req)

    def _preempt_for(self, head: Request) -> bool:
        """Evict the lowest-priority (ties: youngest) decoding request
        strictly below ``head.priority``, re-enqueueing it directly behind
        the head — bounded by the strict-inequality rule, so equal-priority
        traffic can never livelock-thrash. False when no victim exists."""
        victims = [(st.req.priority, -st.order, slot, st)
                   for slot, st in enumerate(self.slots)
                   if st is not None and st.pos >= len(st.req.prompt)
                   and st.req.priority < head.priority]
        if not victims:
            return False
        _, _, slot, st = min(victims)
        st.req.preemptions += 1
        self.preemptions += 1
        self._event("preempt", st.req.rid, slot, reason="page pressure")
        self._abort_slot(slot, reason="preempted", requeue_at=1,
                         count_retry=False)
        return True

    def _expire_deadlines(self):
        """Permanently fail requests past their wall-clock deadline, both
        queued and in-flight (their pages release like any abort)."""
        now = self.clock()

        def expired(req):
            return (req.deadline_s is not None
                    and now - req.submit_t > req.deadline_s)

        for req in [r for r in self.queue if expired(r)]:
            self.queue.remove(req)
            self._fail_request(req, "deadline exceeded in queue")
        for slot, st in enumerate(self.slots):
            if st is not None and expired(st.req):
                req = st.req
                self._release_slot(slot, st)
                self._event("abort", req.rid, slot, "deadline", reason="deadline")
                self._fail_request(req, "deadline exceeded")

    def _recover_engine(self):
        """Engine-level recovery after an injected step failure: re-jit
        the step fns and resume from the surviving page tables. Step fns
        are functional (inputs are never donated), so a step that raised
        left ``self.cache``/``self.table`` at the pre-step state, and
        every tick is idempotent on retry."""
        self.engine_recoveries += 1
        self._build_steps()
        self._event("recover", reason="engine step failure")

    def _on_fault(self, err: faults_lib.FaultError):
        """Route an injected fault: a ``{"slot": k}`` payload is a
        request-level failure (abort + bounded retry of that request,
        front of queue); anything else is engine-level
        (``_recover_engine``)."""
        payload = err.fault.payload if err.fault is not None else {}
        slot = payload.get("slot")
        if slot is not None and self.slots[slot] is not None:
            self._abort_slot(slot, reason=f"injected fault: {err}")
        else:
            self._recover_engine()

    def _shrink(self, survivors):
        """Elastic shrink after device dropout (DESIGN.md §9): abort every
        live slot back to the queue in admission order (no retry charge —
        the device died, not the request), drain the prefix index, rebind
        the pool's group shares to the surviving classes' Eq. 1 weights,
        and re-derive slot groups + disagg roles. Live requests carry
        across: their cleared streams replay token-identically on the
        shrunken engine. Queued requests whose worst case no longer fits
        any surviving share fail permanently (FIFO would deadlock behind
        them)."""
        survivors = sorted(survivors if survivors is not None
                           else range(len(self.pool.shares) - 1))
        if not survivors:
            raise RuntimeError("device dropout left no survivors")
        live = sorted(
            (st.order, slot) for slot, st in enumerate(self.slots)
            if st is not None)
        for i, (_, slot) in enumerate(live):
            self._abort_slot(slot, reason="device dropout",
                             requeue_at=i, count_retry=False)
        if self.index is not None:
            self.index.clear(self.pool)
        weights = [self.class_weights[g] for g in survivors]
        self.class_weights = weights
        n_g = len(weights)
        shares = (page_shares(weights, self.pool.num_pages - 1)
                  if n_g > 1 else None)
        self.pool.reshare(shares if shares is not None
                          else [self.pool.num_pages - 1])
        self.groups = [s * n_g // self.num_slots
                       for s in range(self.num_slots)]
        if self.disagg:
            group_roles = derive_roles(weights)
            self.roles = [group_roles[self.groups[s]]
                          for s in range(self.num_slots)]
        self._event("shrink", tuple(survivors), reason="device dropout")
        for req in [r for r in self.queue
                    if self._need_pages(r) > max(self.pool.shares)]:
            self.queue.remove(req)
            self._fail_request(
                req, f"needs {self._need_pages(req)} pages > largest "
                     f"surviving share {max(self.pool.shares)}")

    def assert_page_invariants(self):
        """Structural oracle (DESIGN.md §9): on top of the pool's own
        conservation checks, every live page's refcount must equal its
        holder count — slot page-table entries + prefix-trie nodes — and
        every group's reserved balance must equal the unconsumed
        reservations of its live slots. Run after every abort path when
        ``audit=True`` (the chaos tests) and cheap enough to leave on."""
        self.pool.assert_consistent()
        holders: dict[int, int] = {}
        for st in self.slots:
            if st is None:
                continue
            for p in st.pages:
                if p != 0:
                    holders[p] = holders.get(p, 0) + 1
        if self.index is not None:
            for p in self.index.pages():
                holders[p] = holders.get(p, 0) + 1
        refs = {p: self.pool.refcount(p)
                for p in range(1, self.pool.num_pages)
                if self.pool.refcount(p) > 0}
        assert refs == holders, (
            f"refcount/holder mismatch (leak or dangler): "
            f"{refs} vs {holders}")
        per_group = [0] * len(self.pool.shares)
        for st in self.slots:
            if st is not None:
                per_group[st.group] += st.reserved - st.allocated
        for g, want in enumerate(per_group):
            assert self.pool._reserved[g] == want, (
                g, self.pool._reserved[g], want)

    def _index_prompt(self, st: _PagedSlot):
        """Insert the request's FULL prompt pages into the radix index at
        prefill completion. Only whole pages go in (a partial page would
        later be written by decode), and a window-reclaimed slot skips
        insertion entirely — its leading pages are gone, so the chain from
        the root would dangle. Decode writes land strictly past
        ``plen // page_size`` pages, so indexed pages are immutable."""
        if self.index is None or st.reclaimed > 0:
            return
        full = len(st.req.prompt) // self.page_size
        if full > 0:
            self.index.insert(st.req.prompt, st.pages[:full], self.pool)

    def _prefill_tick(self, done: list) -> bool:
        """One chunk of the FIFO-oldest prefilling request, restricted to
        prefill-capable slots (all slots unless disaggregated)."""
        cand = [(st.order, slot, st) for slot, st in enumerate(self.slots)
                if st is not None and st.pos < len(st.req.prompt)
                and self.roles[slot] != "decode"]
        if not cand:
            return False
        faults_lib.inject("serve.prefill")
        _, slot, st = min(cand)
        n = min(self.prefill_chunk, len(st.req.prompt) - st.pos)
        self._ensure_pages(slot, st, st.length + n)
        toks = np.zeros((self.prefill_chunk,), np.int32)
        toks[:n] = st.req.prompt[st.pos: st.pos + n]
        # .copy(): self.table is a persistent host buffer the scheduler
        # mutates (reclaim, grants) while steps are still in flight — CPU
        # jax aliases numpy inputs zero-copy, so an async read of the live
        # buffer could observe a FUTURE table state (a real, hash-seed-
        # timing-dependent token corruption caught by the parity tests).
        with obs.tracer.span("serve.prefill_chunk", rid=st.req.rid,
                             slot=slot, n=n):
            last, self.cache = self._unpack_step(self.prefill_step(
                self.params, jnp.asarray(toks), jnp.int32(n),
                jnp.int32(slot),
                jnp.asarray(self.table[slot].copy()), self.cache,
            ))
        st.pos += n
        st.length += n
        self._reclaim(slot, st)
        self._event("prefill_chunk", st.req.rid, slot, n)
        if st.pos == len(st.req.prompt):
            self._index_prompt(st)
            last = np.asarray(last, np.float32)
            for f in faults_lib.inject("serve.prefill_logits"):
                if f.kind == "nan":
                    last = np.full_like(last, np.nan)
            # NaN watchdog: non-finite first-token logits fail THIS
            # request (bounded retry), never the engine.
            if not np.all(np.isfinite(last)):
                self._abort_slot(slot, reason="non-finite prefill logits")
                return True
            st.req.out.append(next_token(last, st.req))
            # one clock read for BOTH the legacy dict and the trace
            # instant, so the span-derived TTFT is bitwise the legacy
            # value (tests/test_obs.py pins the equality)
            now = time.perf_counter()
            self.ttft_s[st.req.rid] = now - self._run_t0
            obs.tracer.instant("serve.first_token", t=now, rid=st.req.rid)
            if len(st.req.out) >= st.req.max_new:
                self._finish(slot, st, done)
        return True

    def _handoff(self, src: int, dst: int):
        if self._handoff_step is None:
            self._handoff_step = jax.jit(
                steps_lib.make_paged_handoff_step(self.cfg))
        with obs.tracer.span("serve.handoff", src=src, dst=dst):
            self.cache = self._handoff_step(
                self.cache, jnp.int32(src), jnp.int32(dst))

    def _transfer_tick(self) -> bool:
        """Disaggregated handoff: move every prefill-role slot that has
        finished its prompt into a free decode-capable slot. The KV pages
        never move — the transfer is the page-table row plus the jitted
        per-slot metadata (``len`` and any recurrent state), so its cost
        is independent of context length."""
        if not self.disagg:
            return False
        ready = sorted(
            (st.order, src, st) for src, st in enumerate(self.slots)
            if st is not None and self.roles[src] == "prefill"
            and st.pos >= len(st.req.prompt))
        moved = False
        for _, src, st in ready:
            dst = None
            for s in sorted(self.free):
                if self.roles[s] != "prefill":
                    dst = s
                    break
            if dst is None:
                break
            self.free.remove(dst)
            self._handoff(src, dst)
            self.table[dst, :] = self.table[src]
            self.table[src, :] = 0
            self.slots[dst] = st
            self.slots[src] = None
            self.free.append(src)
            self.transfers += 1
            self._event("transfer", st.req.rid, src, dst)
            moved = True
        return moved

    def _decode_tick(self, done: list) -> bool:
        """One decode macro-step over every decode-capable slot past
        prefill (a strict prefill-role slot waits for _transfer_tick).
        With a SpecDecoder attached the whole tick is a speculative
        draft/verify round instead (DESIGN.md §11)."""
        if self.spec is not None:
            return self.spec.decode_tick(done)
        dec = [(slot, st) for slot, st in enumerate(self.slots)
               if st is not None and st.pos >= len(st.req.prompt)
               and self.roles[slot] != "prefill"]
        if not dec:
            return False
        faults_lib.inject("serve.decode")
        tokens = np.zeros((self.num_slots, 1), np.int32)
        active = np.zeros((self.num_slots,), bool)
        for slot, st in dec:
            self._ensure_pages(slot, st, st.length + 1)
            tokens[slot, 0] = st.req.out[-1]
            active[slot] = True
        t0 = time.perf_counter()
        with obs.tracer.span("serve.decode", t0=t0, slots=len(dec)):
            logits, self.cache = self._unpack_step(self.serve_step(
                self.params,
                {"tokens": jnp.asarray(tokens),
                 # .copy() — see _prefill_tick: the live table buffer must
                 # not be aliased by an asynchronously-executing step
                 "page_table": jnp.asarray(self.table.copy()),
                 "active": jnp.asarray(active)},
                self.cache,
            ))
            nxt = np.array(logits, np.float32)  # owned: faults may poison
        dt = time.perf_counter() - t0
        self.decode_times_s.append(dt)
        obs.registry.histogram(
            "repro_serve_decode_step_seconds",
            "paged decode macro-step latency").observe(dt)
        self._event("decode", tuple(slot for slot, _ in dec))
        for f in faults_lib.inject("serve.logits"):
            if f.kind == "nan":
                nxt[int(f.payload.get("slot", dec[0][0]))] = np.nan
        for slot, st in dec:
            # NaN watchdog: a non-finite logits row fails (and retries)
            # the offending request only — the batch-mates' rows are
            # independent outputs of the same macro-step and their
            # streams proceed untouched (pinned by tests/test_chaos.py).
            if not np.all(np.isfinite(nxt[slot, -1])):
                self._abort_slot(slot, reason="non-finite decode logits")
                continue
            st.length += 1
            st.req.out.append(next_token(nxt[slot, -1], st.req))
            obs.tracer.instant("serve.token", rid=st.req.rid)
            self._reclaim(slot, st)
            if len(st.req.out) >= st.req.max_new:
                self._finish(slot, st, done)
        return True

    def run(self, max_steps: int = 100000) -> list[Request]:
        """Drive admission + ticks to completion. Injected faults route
        through ``_on_fault`` (request abort/retry or engine re-jit) and
        ``_shrink`` (device dropout); permanently failed requests land in
        ``self.failed`` with ``error`` set, never in the return value."""
        done: list[Request] = []
        steps = 0
        self._run_t0 = time.perf_counter()
        # span start pinned to _run_t0 so TTFT derived from the trace
        # subtracts the exact stamp the legacy ttft_s dict subtracts
        with obs.tracer.span("serve.run", t0=self._run_t0):
            while (self.queue or any(s is not None for s in self.slots)) \
                    and steps < max_steps:
                try:
                    self._expire_deadlines()
                    self._admit()
                    advanced = self._transfer_tick()
                    advanced |= self._prefill_tick(done)
                    advanced |= self._decode_tick(done)
                except faults_lib.DeviceLostError as e:
                    self._shrink(e.survivors)
                    advanced = True
                except faults_lib.FaultError as e:
                    self._on_fault(e)
                    advanced = True
                if self.audit:
                    self.assert_page_invariants()
                if not advanced and not self.queue:
                    break
                steps += 1
                if self.obs_dump_every and steps % self.obs_dump_every == 0:
                    self._dump_metrics()
        self._dump_metrics()
        return done

    def drop_prefix_cache(self) -> int:
        """Release every page the radix index holds back to the pool
        (leak-check draining; also the operator's cache-flush)."""
        if self.index is None:
            return 0
        return self.index.clear(self.pool)

    def stats(self) -> dict:
        out = {**self.pool.stats(), "admissions": self.admissions,
               "transfers": self.transfers, "aborts": self.aborts,
               "preemptions": self.preemptions,
               "engine_recoveries": self.engine_recoveries,
               "failed": len(self.failed)}
        if self.index is not None:
            out["prefix"] = self.index.stats()
        return out


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main(argv=None):
    """CLI serving driver: dense or paged continuous batching with
    optional hetero plan, weight/KV quantization, prefix cache, and
    disaggregated roles."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--paged", action="store_true",
                    help="serve from the paged KV pool (DESIGN.md §7) "
                         "instead of the dense (slots, max_seq) rectangle")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--pages", type=int, default=0,
                    help="shared pool size incl. the sink page "
                         "(0 -> slots * ceil(max_seq/page)/2 + 1: half the "
                         "dense rectangle, the paged engine's whole point)")
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--mesh", default=None)
    ap.add_argument("--topology", default=None,
                    help="intra_bw:inter_bw:node_size — two-level "
                         "interconnect (DESIGN.md §10): prices the auto "
                         "chooser per level and, on a mesh whose model "
                         "extent spans multiple nodes, serves with the "
                         "hierarchical dispatch schedule")
    ap.add_argument("--mode", default="auto",
                    choices=["hybrid", "model_centric", "data_centric",
                             "auto", "ep"],
                    help="parallel mode; 'auto' (default) lets each MoE "
                         "layer pick data-/model-centric dispatch from the "
                         "roofline — decode steps (few tokens) resolve "
                         "model-centric, large prefills data-centric")
    ap.add_argument("--cache-layers", type=int, default=0,
                    help="pipeline-shared prefetch cache residency bound "
                         "(gathered MoE periods) for the decode forward; "
                         ">0 unrolls the layer loop")
    ap.add_argument("--hetero-latencies", default=None,
                    help="comma-separated t_i per batch-group member: an "
                         "Eq. 1 plan — uneven SLOT shares for the dense "
                         "server, uneven PAGE-POOL shares for --paged "
                         "(DESIGN.md §6/§7). Requires --mesh for dense")
    ap.add_argument("--hetero-tp-latencies", default=None,
                    help="comma-separated t_i per TP-group member: Eq. 2 "
                         "uneven hidden tiles")
    ap.add_argument("--quant", default="none",
                    choices=["none", "int8", "fp8"],
                    help="quantize expert weights to block-wise int8/fp8 "
                         "payloads served through the fused-dequant ES "
                         "kernels (DESIGN.md §8)")
    ap.add_argument("--kv-quant", default="none", choices=["none", "int8"],
                    help="store paged-KV pages as int8 + per-row scales — "
                         "smaller pages, more admitted requests per HBM "
                         "byte (--paged only, DESIGN.md §8)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share full prompt pages across requests through "
                         "a CoW radix index — repeated prefixes admit at "
                         "refcount+1 and only prefill their uncached "
                         "suffix (--paged only, DESIGN.md §7)")
    ap.add_argument("--fault-spec", default=None,
                    help="chaos fault plan: inline JSON or a JSON file "
                         "({'seed': 0, 'faults': [{'site', 'kind', ...}]},"
                         " runtime.faults) — deterministic injection into "
                         "the serving ticks (DESIGN.md §9)")
    ap.add_argument("--audit", action="store_true",
                    help="run the page-pool structural oracle "
                         "(refcounts == slot holders + prefix-trie nodes) "
                         "after every scheduler step")
    ap.add_argument("--disagg", action="store_true",
                    help="split slots into prefill and decode roles; "
                         "finished prefills hand off by page-table "
                         "transfer, no KV copy (--paged only, DESIGN.md "
                         "§7). Role shares follow --hetero-latencies "
                         "classes, else half/half")
    ap.add_argument("--spec-ngram", action="store_true",
                    help="speculative decoding with self-speculative "
                         "n-gram drafting from each request's own token "
                         "history — no draft model (--paged only, "
                         "all-attention stacks, DESIGN.md §11)")
    ap.add_argument("--spec-draft", default=None, metavar="ARCH",
                    help="speculative decoding with a small draft model "
                         "(any all-attention non-windowed config, e.g. "
                         "gemma_2b drafting for a MoE target); resolved "
                         "with the same --smoke switch as --arch "
                         "(--paged only, DESIGN.md §11)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft length per verify round: up to k drafted "
                         "tokens + 1 correction commit per forward")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="enable the metrics registry and dump a "
                         "Prometheus text snapshot to PATH at exit "
                         "(DESIGN.md §12); with --paged the step outputs "
                         "also carry per-expert router telemetry")
    ap.add_argument("--metrics-interval", type=int, default=0,
                    metavar="N",
                    help="also dump the Prometheus snapshot every N "
                         "scheduler steps (0 = exit-only)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record spans/instants and write a Chrome "
                         "trace-event JSON (Perfetto-loadable) to PATH")
    ap.add_argument("--events-out", default=None, metavar="PATH",
                    help="write the structured scheduler event log "
                         "(JSONL, monotonic timestamps + reasons) to PATH")
    args = ap.parse_args(argv)
    if (args.spec_ngram or args.spec_draft) and not args.paged:
        ap.error("--spec-ngram/--spec-draft require --paged")
    if args.spec_ngram and args.spec_draft:
        ap.error("--spec-ngram and --spec-draft are mutually exclusive")
    if args.kv_quant != "none" and not args.paged:
        ap.error("--kv-quant requires --paged")
    if (args.prefix_cache or args.disagg) and not args.paged:
        ap.error("--prefix-cache/--disagg require --paged")
    if (args.fault_spec or args.audit) and not args.paged:
        ap.error("--fault-spec/--audit require --paged (the recovery "
                 "machinery lives in the paged engine)")
    if args.fault_spec:
        faults_lib.install(faults_lib.load_plan(args.fault_spec))

    obs_on = bool(args.metrics or args.trace_out or args.events_out)
    if obs_on:
        obs.configure(metrics=bool(args.metrics),
                      tracing=bool(args.trace_out),
                      event_log=bool(args.events_out), reset=True)

    cfg = (cfglib.get_smoke_config(args.arch) if args.smoke
           else cfglib.get_config(args.arch))
    topo = None
    if args.topology:
        from repro.parallel.autotune import Topology
        try:
            topo = Topology.parse(args.topology)
        except (ValueError, TypeError) as e:
            ap.error(f"--topology: {e}")
    mesh = None
    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split(","))
        axes = ("pod", "data", "model")[-len(dims):]
        if topo is not None:
            dims, axes = split_model_axis(dims, axes, topo.node_size)
        mesh = make_mesh(dims, axes)

    plan = None
    num_slots, valid_slots = args.slots, None
    if args.hetero_latencies:
        if mesh is None and not args.paged:
            ap.error("--hetero-latencies requires --mesh (dense server)")
        tok_lat = tuple(float(t) for t in args.hetero_latencies.split(","))
        tp_lat = (tuple(float(t) for t in args.hetero_tp_latencies.split(","))
                  if args.hetero_tp_latencies else None)
        plan = hetero_lib.make_hetero_plan(
            tok_lat,
            global_batch=args.slots,
            hidden_size=(cfg.moe.d_ff
                         if tp_lat is not None and cfg.moe is not None
                         else None),
            tp_latencies=tp_lat,
        )
        if args.paged:
            print(f"[serve] hetero plan: page-pool shares proportional to "
                  f"{plan.token_counts} (all {num_slots} slots schedulable)")
        else:
            # Dense: padded slot layout, device i's Eq. 1 share schedulable.
            cap = plan.batch_capacity
            num_slots = len(plan.token_counts) * cap
            valid_slots = [i * cap + j
                           for i, c in enumerate(plan.token_counts)
                           for j in range(c)]
            print(f"[serve] hetero plan: slot shares {plan.token_counts} "
                  f"({num_slots} padded slots), hidden {plan.hidden_splits}")

    pcfg = ParallelConfig(
        mode=args.mode, blk=16,
        cache_layers=args.cache_layers,
        scan_layers=args.cache_layers <= 0,
        hetero_plan=plan,
        # auto-mode roofline prices the served weight width (the island
        # itself skips QAT fake-quant when the params carry true payloads)
        quant=args.quant,
        topology=topo,
        # --metrics adds router telemetry outputs to the paged engine's
        # jitted steps (the dense baseline keeps its 2-tuple contract)
        collect_router_stats=(bool(args.metrics) and args.paged
                              and cfg.moe is not None),
    )

    params, specs = split_tree(
        lm.init_params(jax.random.PRNGKey(0), cfg, plan=plan))
    if args.quant != "none":
        if mesh is not None:
            ap.error("--quant serves whole-expert int8/fp8 payloads; "
                     "combine with --mesh is not supported (the scales "
                     "do not shard congruently)")
        from repro.quant import quantize_lm_params

        before = tree_bytes(params)
        params = quantize_lm_params(params, cfg, mode=args.quant)
        print(f"[serve] expert weights -> {args.quant}: params "
              f"{before / 1e6:.1f}MB -> {tree_bytes(params) / 1e6:.1f}MB")
    if mesh is not None:
        params = jax.tree.map(
            jax.device_put, params, tree_shardings(params, specs, pcfg, mesh)
        )
    if args.paged:
        pages = args.pages or (
            num_slots * cdiv(args.max_seq, args.page_size) // 2 + 1)
        server = PagedServer(
            cfg, pcfg, mesh, num_slots=num_slots,
            page_size=args.page_size, num_pages=pages,
            max_pages_per_slot=cdiv(args.max_seq, args.page_size),
            params=params, prefill_chunk=args.prefill_chunk, plan=plan,
            kv_quant=args.kv_quant, prefix_cache=args.prefix_cache,
            disagg=args.disagg, audit=args.audit,
        )
        if args.metrics:
            server.obs_dump_path = args.metrics
            server.obs_dump_every = args.metrics_interval
        if args.spec_ngram or args.spec_draft:
            # lazy import: spec imports serve (the shared sampling
            # helpers), so serve must never import spec at module level
            from repro.launch import spec as spec_lib
            if args.spec_draft:
                dcfg = (cfglib.get_smoke_config(args.spec_draft)
                        if args.smoke else cfglib.get_config(args.spec_draft))
                dparams, _ = split_tree(
                    lm.init_params(jax.random.PRNGKey(1), dcfg))
                drafter = spec_lib.ModelDrafter(
                    dcfg, ParallelConfig(blk=16), None, dparams,
                    max_seq=args.max_seq)
            else:
                drafter = spec_lib.NGramDrafter()
            spec_lib.SpecDecoder(server, drafter, k=args.spec_k)
    else:
        server = BatchedServer(cfg, pcfg, mesh, num_slots=num_slots,
                               max_seq=args.max_seq, params=params,
                               valid_slots=valid_slots)
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        server.submit(Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab_size, size=8).astype(np.int32),
            max_new=args.max_new,
        ))
    t0 = time.time()
    done = server.run()
    dt = time.time() - t0
    total_tokens = sum(len(r.out) for r in done)
    print(f"[serve] {len(done)} requests, {total_tokens} tokens "
          f"in {dt:.2f}s ({total_tokens / max(dt, 1e-9):.1f} tok/s)")
    if server.decode_times_s:
        ts = np.asarray(server.decode_times_s[1:] or server.decode_times_s)
        print(f"[serve] measured decode step: median "
              f"{np.median(ts) * 1e3:.1f}ms p90 "
              f"{np.percentile(ts, 90) * 1e3:.1f}ms over {len(ts)} steps")
    if args.paged:
        st = server.stats()
        server.drop_prefix_cache()
        print(f"[serve] page pool: {st['peak_in_use_pages']} peak pages "
              f"({st['peak_in_use_bytes'] / 1024:.1f} KiB KV resident) of "
              f"{st['num_pages'] - 1} allocatable; "
              f"{st['total_allocs']} allocs, leak-free="
              f"{server.pool.stats()['free_pages'] == st['num_pages'] - 1}")
        if st["aborts"] or st["preemptions"] or st["engine_recoveries"] \
                or st["failed"]:
            print(f"[serve] recovery: {st['aborts']} aborts, "
                  f"{st['preemptions']} preemptions, "
                  f"{st['engine_recoveries']} engine recoveries, "
                  f"{st['failed']} failed")
        if "prefix" in st:
            pf = st["prefix"]
            hit = pf["hit_tokens"] / max(pf["lookup_tokens"], 1)
            print(f"[serve] prefix cache: {hit:.0%} token hit-rate over "
                  f"{pf['lookups']} lookups, {pf['cached_pages']} pages "
                  f"held at peak, {pf['evictions']} LRU evictions")
        if args.disagg:
            print(f"[serve] disagg: roles {server.roles}, "
                  f"{server.transfers} page-table handoffs")
        if server.spec is not None:
            sp = server.spec.stats()
            print(f"[serve] speculative: {sp['rounds']} verify rounds, "
                  f"{sp['accepted_drafts']}/{sp['drafted']} drafts "
                  f"accepted ({sp['acceptance_rate']:.0%}), "
                  f"{sp['rollback_tokens']} rows rolled back")
    if obs_on:
        if args.metrics:
            if getattr(server, "router_drain", None) is not None:
                server.router_drain.flush()
            obs.registry.collect()
            obs.dump_prometheus(obs.registry, args.metrics)
            print(f"[serve] metrics -> {args.metrics}")
        if args.trace_out:
            obs.tracer.write(args.trace_out)
            cov = obs.span_coverage(obs.tracer.events)
            ttft, tpot = obs.derive_request_latencies(obs.tracer.events)
            print(f"[serve] trace -> {args.trace_out} "
                  f"({len(obs.tracer.events)} events, "
                  f"{cov:.0%} span coverage)")
            if ttft:
                ms = sorted(v * 1e3 for v in ttft.values())
                line = (f"[serve] TTFT from spans: median "
                        f"{ms[len(ms) // 2]:.1f}ms over {len(ms)} requests")
                if tpot:
                    tp = sorted(v * 1e3 for v in tpot.values())
                    line += f"; TPOT median {tp[len(tp) // 2]:.2f}ms"
                print(line)
        if args.events_out:
            obs.events.write_jsonl(args.events_out)
            print(f"[serve] events -> {args.events_out} "
                  f"({len(obs.events.records)} records)")
    for r in done[:3]:
        print(f"  req {r.rid}: {r.out[:8]}...")
    faults_lib.install(None)
    return done


if __name__ == "__main__":
    enable_compile_cache()
    main()
