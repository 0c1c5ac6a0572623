"""Cross-config decode parity matrix (ISSUE 4): batched continuous-batching
decode — dense baseline AND paged engine (pallas-interpret paged attention)
— must emit token-for-token the same streams as the one-request-at-a-time
dense-cache reference, including mid-run slot refill and with an attached
heterogeneous plan.

The matrix covers the paper-relevant families: mixtral (SWA windowed MoE,
softmax_after_topk), qwen3 (fine-grained MoE + qk-norm), gemma-2b (dense
GeGLU MQA), and the swin-moe expert configuration (expert-MLP, layernorm,
gelu — swin itself is a vision classifier with no decode path, so its MoE
block is grafted onto a tiny decode-capable LM)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as cfglib
from repro.configs.base import ModelConfig, MoEConfig
from repro.core import hetero as hetero_lib
from repro.launch import serve, spec as spec_lib, steps as steps_lib
from repro.models import lm
from repro.parallel.sharding import ParallelConfig, split_tree

#: swin-moe-small's expert configuration (4 experts, top-2, expert-MLP with
#: gelu + layernorm, MoE on alternating blocks) on a decode-capable LM.
SWIN_MOE_LM = ModelConfig(
    name="swin-moe-lm-smoke",
    family="vision-moe",
    num_layers=2,
    d_model=32,
    num_heads=2,
    num_kv_heads=2,
    head_dim=16,
    d_ff=48,
    vocab_size=64,
    act="gelu",
    glu=False,
    norm="layernorm",
    tie_embeddings=True,
    moe=MoEConfig(num_experts=4, top_k=2, d_ff=48, period=2, offset=1),
)

ARCHS = ["mixtral-8x7b", "qwen3-moe-30b-a3b", "gemma-2b", "swin_moe_small"]


def _config(arch):
    if arch == "swin_moe_small":
        cfg = SWIN_MOE_LM
    else:
        cfg = cfglib.get_smoke_config(arch)
    # f32 keeps greedy argmax margins far above cross-batch reduction noise
    return dataclasses.replace(cfg, dtype="float32")


def _requests(cfg, n, seed):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(2, 14))
        reqs.append(serve.Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab_size, size=plen).astype(np.int32),
            max_new=int(rng.integers(1, 6)),
        ))
    return reqs


def _reference_streams(cfg, pcfg, params, reqs, max_seq):
    step = jax.jit(steps_lib.make_serve_step(
        cfg, pcfg, None, (1, 1, cfg.d_model)))
    return {
        r.rid: serve.greedy_reference(
            cfg, pcfg, None, params, r.prompt, r.max_new,
            max_seq=max_seq, step=step)
        for r in reqs
    }


MAX_SEQ = 32
NUM_SLOTS = 3    # < num requests -> guaranteed mid-run slot refill
N_REQ = 6


@pytest.mark.parametrize("arch", ARCHS)
def test_paged_decode_parity(arch):
    """Paged continuous batching (pallas-interpret paged attention, chunked
    prefill, slot refill) is token-identical to the batch-1 dense
    reference on every config in the matrix."""
    cfg = _config(arch)
    pcfg = ParallelConfig(blk=8, impl="pallas")
    params, _ = split_tree(lm.init_params(jax.random.PRNGKey(0), cfg))
    reqs = _requests(cfg, N_REQ, seed=11)
    refs = _reference_streams(cfg, pcfg, params, reqs, MAX_SEQ)

    maxp = MAX_SEQ // 4
    server = serve.PagedServer(
        cfg, pcfg, None, num_slots=NUM_SLOTS, page_size=4,
        num_pages=1 + NUM_SLOTS * maxp, max_pages_per_slot=maxp,
        params=params, prefill_chunk=5,
    )
    for r in reqs:
        server.submit(dataclasses.replace(r, out=[]))
    done = server.run()
    assert len(done) == N_REQ
    assert server.admissions > NUM_SLOTS, "no mid-run slot refill happened"
    for r in done:
        assert r.out == refs[r.rid], (
            f"{arch}: paged stream for rid={r.rid} diverged")
    # no page leaks, table fully cleared
    server.pool.assert_consistent()
    assert server.pool.free_pages == NUM_SLOTS * maxp
    assert (server.table == 0).all()


@pytest.mark.parametrize("arch", ARCHS)
def test_dense_decode_parity(arch):
    """The dense continuous-batching baseline (masked macro-steps, slot
    refill) matches the same reference — the two servers differ only in
    cache layout, never in tokens."""
    cfg = _config(arch)
    pcfg = ParallelConfig(blk=8, impl="pallas")
    params, _ = split_tree(lm.init_params(jax.random.PRNGKey(0), cfg))
    reqs = _requests(cfg, N_REQ, seed=13)
    refs = _reference_streams(cfg, pcfg, params, reqs, MAX_SEQ)

    server = serve.BatchedServer(
        cfg, pcfg, None, num_slots=NUM_SLOTS, max_seq=MAX_SEQ, params=params)
    for r in reqs:
        server.submit(dataclasses.replace(r, out=[]))
    done = server.run()
    assert len(done) == N_REQ
    assert server.admissions > NUM_SLOTS
    for r in done:
        assert r.out == refs[r.rid], (
            f"{arch}: dense stream for rid={r.rid} diverged")


def test_paged_parity_with_hetero_plan():
    """An attached Eq. 1/2 plan (uneven page-pool shares + padded FFN
    hidden tiles) must not change a single token: the plan reshapes WHERE
    pages and hidden columns live, never what is computed."""
    cfg = _config("qwen3-moe-30b-a3b")
    plan = hetero_lib.make_hetero_plan(
        (1.0, 2.0), global_batch=4,
        hidden_size=cfg.moe.d_ff, tp_latencies=(1.0, 3.0))
    pcfg = ParallelConfig(blk=8, impl="pallas", hetero_plan=plan)
    params, _ = split_tree(
        lm.init_params(jax.random.PRNGKey(0), cfg, plan=plan))
    reqs = _requests(cfg, 5, seed=17)
    refs = _reference_streams(cfg, pcfg, params, reqs, MAX_SEQ)

    maxp = MAX_SEQ // 4
    server = serve.PagedServer(
        cfg, pcfg, None, num_slots=4, page_size=4,
        num_pages=1 + 4 * maxp, max_pages_per_slot=maxp,
        params=params, prefill_chunk=4, plan=plan,
    )
    # uneven shares actually materialised (t=1 vs t=2 -> 2:1 page budget)
    assert len(server.pool.shares) == 2
    assert server.pool.shares[0] > server.pool.shares[1]
    for r in reqs:
        server.submit(dataclasses.replace(r, out=[]))
    done = server.run()
    assert len(done) == 5
    for r in done:
        assert r.out == refs[r.rid], f"hetero rid={r.rid} diverged"
    server.pool.assert_consistent()
    assert server.pool.free_pages == sum(server.pool.shares)


def test_paged_cache_specs_mirror_cache_tree():
    """``paged_cache_logical_specs`` must stay structurally congruent with
    ``init_paged_cache`` (leaf-for-leaf), and each logical entry must have
    one axis per array dim — that is what lets ``tree_shardings`` place
    the pool (page dim over "dp") on a real mesh."""
    for arch in ("mixtral-8x7b", "jamba-1.5-large-398b"):
        cfg = _config(arch) if arch != "jamba-1.5-large-398b" else (
            dataclasses.replace(
                cfglib.get_smoke_config(arch), dtype="float32"))
        cache = lm.init_paged_cache(cfg, num_slots=3, num_pages=9,
                                    page_size=4)
        specs = lm.paged_cache_logical_specs(cfg, cache)
        flat_c, tree_c = jax.tree_util.tree_flatten(cache)
        # specs' leaves are tuples; flatten up to the cache structure
        flat_s = tree_c.flatten_up_to(specs)
        assert len(flat_s) == len(flat_c)
        for arr, spec in zip(flat_c, flat_s):
            assert isinstance(spec, tuple) and len(spec) == arr.ndim, (
                arch, arr.shape, spec)


def test_paged_parity_recurrent_scan_prefill():
    """Hybrid attn+mamba (jamba): recurrent state can't prefill a chunk in
    one forward, so the engine falls back to the in-jit scan of decode
    steps — per-slot state slicing, freezing, and reset must all still
    produce reference-identical streams through slot refill."""
    cfg = dataclasses.replace(
        cfglib.get_smoke_config("jamba-1.5-large-398b"), dtype="float32")
    assert any(cfg.layer_kind(i) != "attn" for i in range(cfg.period))
    pcfg = ParallelConfig(blk=8, impl="pallas")
    params, _ = split_tree(lm.init_params(jax.random.PRNGKey(0), cfg))
    reqs = _requests(cfg, 4, seed=23)
    refs = _reference_streams(cfg, pcfg, params, reqs, MAX_SEQ)
    maxp = MAX_SEQ // 4
    server = serve.PagedServer(
        cfg, pcfg, None, num_slots=2, page_size=4,
        num_pages=1 + 2 * maxp, max_pages_per_slot=maxp,
        params=params, prefill_chunk=4,
    )
    for r in reqs:
        server.submit(dataclasses.replace(r, out=[]))
    done = server.run()
    assert len(done) == 4 and server.admissions > 2
    for r in done:
        assert r.out == refs[r.rid], f"jamba rid={r.rid} diverged"
    server.pool.assert_consistent()


def test_window_page_reclamation():
    """On an all-SWA stack (mixtral) pages wholly behind the window return
    to the pool mid-request: live pages stay bounded by the window, and
    the reused pages never perturb the token stream."""
    cfg = _config("mixtral-8x7b")
    assert cfg.window == 16
    pcfg = ParallelConfig(blk=8, impl="pallas")
    params, _ = split_tree(lm.init_params(jax.random.PRNGKey(0), cfg))
    prompt = np.arange(40, dtype=np.int32) % cfg.vocab_size
    req = serve.Request(rid=0, prompt=prompt, max_new=8)
    ref = serve.greedy_reference(
        cfg, pcfg, None, params, prompt, 8, max_seq=64)

    page, maxp = 4, 12  # 48 rows per slot -> covers 40 + 8 - 1
    server = serve.PagedServer(
        cfg, pcfg, None, num_slots=2, page_size=page,
        num_pages=1 + 2 * maxp, max_pages_per_slot=maxp,
        params=params, prefill_chunk=8,
    )
    assert server.reclaim_window == 16
    server.submit(dataclasses.replace(req, out=[]))
    done = server.run()
    assert done[0].out == ref
    # the request wrote 47 rows (12 pages) but never held more than the
    # window + one prefill chunk's worth of them at once
    window_pages = cfg.window // page + server.prefill_chunk // page + 1
    assert server.pool.peak_in_use_pages <= window_pages
    assert server.pool.total_allocs == 12
    server.pool.assert_consistent()
    assert server.pool.free_pages == 2 * maxp


def test_int8_kv_decode_parity_and_capacity():
    """int8 paged-KV (ISSUE 5, DESIGN.md §8): the quantized-cache server
    stays token-identical to its own full-precision run under greedy
    sampling (per-row scales keep the quantization error far inside the
    pinned greedy-argmax margin on this matrix), while the smaller page
    bytes make an equal-HBM PagePool admit measurably more concurrent
    requests."""
    from repro.parallel.cache import PagePool

    cfg = _config("qwen3-moe-30b-a3b")
    pcfg = ParallelConfig(blk=8, impl="pallas")
    params, _ = split_tree(lm.init_params(jax.random.PRNGKey(0), cfg))
    reqs = _requests(cfg, N_REQ, seed=11)
    maxp = MAX_SEQ // 4

    def run_server(kv_quant):
        server = serve.PagedServer(
            cfg, pcfg, None, num_slots=NUM_SLOTS, page_size=4,
            num_pages=1 + NUM_SLOTS * maxp, max_pages_per_slot=maxp,
            params=params, prefill_chunk=5, kv_quant=kv_quant,
        )
        for r in reqs:
            server.submit(dataclasses.replace(r, out=[]))
        done = server.run()
        server.pool.assert_consistent()
        assert len(done) == N_REQ
        return server, {r.rid: r.out for r in done}

    srv_fp, out_fp = run_server(None)
    srv_q, out_q = run_server("int8")
    assert out_q == out_fp, "int8 KV diverged from its own fp run"
    # the int8 cache really is int8 + scales
    attn_pos = next(i for i in range(cfg.period)
                    if cfg.layer_kind(i) == "attn")
    entry = srv_q.cache["layers"][attn_pos]
    assert entry["k"].dtype == jnp.int8 and "k_scale" in entry

    # equal-HBM admission capacity: same byte budget -> more int8 pages ->
    # more concurrently admissible requests
    pb_fp = lm.paged_kv_page_bytes(cfg, 4, None)
    pb_q = lm.paged_kv_page_bytes(cfg, 4, "int8")
    assert srv_fp.page_bytes == pb_fp and srv_q.page_bytes == pb_q
    budget = 24 * pb_fp
    pool_fp = PagePool(1 + budget // pb_fp, page_bytes=pb_fp)
    pool_q = PagePool(1 + budget // pb_q, page_bytes=pb_q)
    need = 4  # worst-case pages of a representative request

    def capacity(pool):
        n = 0
        while pool.try_reserve(need):
            n += 1
        return n

    cap_fp, cap_q = capacity(pool_fp), capacity(pool_q)
    assert cap_q > cap_fp, (cap_q, cap_fp)
    assert cap_q * pb_q * need <= budget + need * pb_q  # still within HBM


# --- prefix-sharing rows (ISSUE 6) ---------------------------------------

def _shared_prefix_requests(cfg, n, seed, *, shared_len=12):
    """High-duplicate chat-style workload: every request opens with the
    same ``shared_len``-token system prompt and appends a short unique
    tail — the later admissions' prefixes are fully cached."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, cfg.vocab_size, size=shared_len).astype(np.int32)
    reqs = []
    for i in range(n):
        tail = rng.integers(
            0, cfg.vocab_size, size=int(rng.integers(1, 4))).astype(np.int32)
        reqs.append(serve.Request(
            rid=i, prompt=np.concatenate([shared, tail]),
            max_new=int(rng.integers(2, 6))))
    return reqs


def _run_paged(cfg, pcfg, params, reqs, *, num_slots=NUM_SLOTS,
               num_pages=None, spec=None, spec_k=3, **kw):
    maxp = MAX_SEQ // 4
    server = serve.PagedServer(
        cfg, pcfg, None, num_slots=num_slots, page_size=4,
        num_pages=num_pages or (1 + num_slots * maxp),
        max_pages_per_slot=maxp, params=params, prefill_chunk=5, **kw)
    if spec is not None:
        spec_lib.SpecDecoder(server, spec, k=spec_k)
    for r in reqs:
        server.submit(dataclasses.replace(r, out=[]))
    done = server.run()
    assert len(done) == len(reqs)
    return server, {r.rid: r.out for r in done}


def _assert_drained(server):
    """The pool returns to its full budget once the index is dropped."""
    server.drop_prefix_cache()
    server.pool.assert_consistent()
    assert server.pool.free_pages == sum(server.pool.shares)
    assert (server.table == 0).all()


@pytest.mark.parametrize("arch", ARCHS)
def test_prefix_cache_parity(arch):
    """Prefix-cache ON is token-identical to OFF and to the batch-1 dense
    reference on every config in the matrix, while actually sharing pages
    (hits > 0, strictly fewer physical allocations)."""
    cfg = _config(arch)
    pcfg = ParallelConfig(blk=8, impl="pallas")
    params, _ = split_tree(lm.init_params(jax.random.PRNGKey(0), cfg))
    reqs = _shared_prefix_requests(cfg, N_REQ, seed=29)
    refs = _reference_streams(cfg, pcfg, params, reqs, MAX_SEQ)

    srv_on, out_on = _run_paged(cfg, pcfg, params, reqs, prefix_cache=True)
    srv_off, out_off = _run_paged(cfg, pcfg, params, reqs)
    assert out_on == out_off == refs, f"{arch}: prefix-cache changed tokens"
    pf = srv_on.stats()["prefix"]
    assert pf["hit_tokens"] > 0, f"{arch}: no prefix was ever shared"
    assert srv_on.pool.total_allocs < srv_off.pool.total_allocs
    assert srv_on.pool.total_forks > 0
    _assert_drained(srv_on)


def test_prefix_cache_int8_parity():
    """Shared int8 pages share their scale rows through the same physical
    index: int8 + prefix-cache stays token-identical to int8 alone."""
    cfg = _config("qwen3-moe-30b-a3b")
    pcfg = ParallelConfig(blk=8, impl="pallas")
    params, _ = split_tree(lm.init_params(jax.random.PRNGKey(0), cfg))
    reqs = _shared_prefix_requests(cfg, N_REQ, seed=31)

    srv_on, out_on = _run_paged(cfg, pcfg, params, reqs,
                                kv_quant="int8", prefix_cache=True)
    srv_off, out_off = _run_paged(cfg, pcfg, params, reqs, kv_quant="int8")
    assert out_on == out_off, "int8 prefix-cache diverged from int8 alone"
    assert srv_on.stats()["prefix"]["hit_tokens"] > 0
    entry = srv_on.cache["layers"][0]
    assert entry["k"].dtype == jnp.int8 and "k_scale" in entry
    _assert_drained(srv_on)


def test_prefix_cache_parity_under_eviction_pressure():
    """A pool too small to keep every family cached forces mid-run LRU
    evictions of trie pages during admission — the streams must not move
    and the drained pool must still balance."""
    cfg = _config("gemma-2b")
    pcfg = ParallelConfig(blk=8, impl="pallas")
    params, _ = split_tree(lm.init_params(jax.random.PRNGKey(0), cfg))
    # three distinct 8-token prefix families, revisited out of order
    rng = np.random.default_rng(37)
    fams = [rng.integers(0, cfg.vocab_size, size=8).astype(np.int32)
            for _ in range(3)]
    reqs = []
    for i, f in enumerate([0, 1, 2, 0, 1, 2, 2, 0]):
        tail = rng.integers(0, cfg.vocab_size, size=2).astype(np.int32)
        reqs.append(serve.Request(
            rid=i, prompt=np.concatenate([fams[f], tail]), max_new=3))
    refs = _reference_streams(cfg, pcfg, params, reqs, MAX_SEQ)

    # worst case per request: ceil((10 + 3 - 1) / 4) = 3 pages; 2 slots
    # need 6 of the 7 usable pages, but the three families want 6 cached
    # pages between them -> admission must evict LRU trie pages
    srv, out = _run_paged(cfg, pcfg, params, reqs, num_slots=2,
                          num_pages=8, prefix_cache=True)
    assert out == refs, "eviction pressure changed tokens"
    pf = srv.stats()["prefix"]
    assert pf["evictions"] > 0, "pool was never actually under pressure"
    _assert_drained(srv)


def test_prefix_cache_rejects_recurrent_stack():
    """Recurrent layers keep per-slot state outside the pages, so a
    skipped prefix would decode from zeros — the server refuses."""
    cfg = dataclasses.replace(
        cfglib.get_smoke_config("jamba-1.5-large-398b"), dtype="float32")
    pcfg = ParallelConfig(blk=8, impl="pallas")
    params, _ = split_tree(lm.init_params(jax.random.PRNGKey(0), cfg))
    with pytest.raises(ValueError, match="all-attention"):
        serve.PagedServer(
            cfg, pcfg, None, num_slots=2, page_size=4, num_pages=17,
            max_pages_per_slot=8, params=params, prefix_cache=True)


def test_sampled_stream_parity_across_engines():
    """RNG plumbing (ISSUE 6): a sampled request's stream is a pure
    function of (seed, step, logits) — dense server, paged server, and the
    batch-1 reference all draw identical tokens, and the temperature
    actually moves the stream off greedy."""
    cfg = _config("gemma-2b")
    pcfg = ParallelConfig(blk=8, impl="pallas")
    params, _ = split_tree(lm.init_params(jax.random.PRNGKey(0), cfg))
    reqs = _requests(cfg, N_REQ, seed=41)
    for r in reqs:
        r.temperature, r.seed = 0.8, 1000 + r.rid
    step = jax.jit(steps_lib.make_serve_step(
        cfg, pcfg, None, (1, 1, cfg.d_model)))
    refs = {r.rid: serve.reference_stream(
        cfg, pcfg, None, params, r, max_seq=MAX_SEQ, step=step)
        for r in reqs}
    greedy = {r.rid: serve.greedy_reference(
        cfg, pcfg, None, params, r.prompt, r.max_new,
        max_seq=MAX_SEQ, step=step) for r in reqs}
    assert any(refs[r.rid] != greedy[r.rid] for r in reqs), (
        "temperature 0.8 never moved any token off argmax — the sampled "
        "path is not exercised")

    srv_p, out_paged = _run_paged(cfg, pcfg, params, reqs)
    dense = serve.BatchedServer(
        cfg, pcfg, None, num_slots=NUM_SLOTS, max_seq=MAX_SEQ,
        params=params)
    for r in reqs:
        dense.submit(dataclasses.replace(r, out=[]))
    out_dense = {r.rid: r.out for r in dense.run()}
    assert out_paged == refs, "paged sampled stream diverged"
    assert out_dense == refs, "dense sampled stream diverged"


# --- hierarchical topology row (DESIGN.md §10) ---------------------------

@pytest.mark.multihost
def test_hier_topology_serving_token_identical():
    """Paged serving on a two-level mesh (2 nodes x 2 devices of a 4-wide TP
    group, node-local combine before the cross-node exchange) must stream
    token-for-token what the flat 4-wide mesh streams, both for the paged
    engine (slot refill, chunked prefill) and for the batch-1 greedy
    reference on the same meshes — the topology reshapes the collectives,
    never the tokens. The comparisons are mesh-to-mesh (same batch layout,
    same sharded reductions, only the schedule differs); a sharded run is
    not token-comparable to the single-device oracle, greedy argmax sits on
    reassociated f32 sums there. Subprocess: needs 8 fake devices."""
    import json as _json
    import os as _os
    import subprocess as _sp
    import sys as _sys

    code = r"""
import dataclasses, json
import jax
import numpy as np
from repro import configs as cfglib
from repro.launch import serve
from repro.launch.mesh import make_mesh, split_model_axis
from repro.models import lm
from repro.parallel.autotune import Topology
from repro.parallel.sharding import ParallelConfig, split_tree, tree_shardings

cfg = dataclasses.replace(
    cfglib.get_smoke_config("qwen3-moe-30b-a3b"), dtype="float32")
rng = np.random.default_rng(11)
reqs = []
for i in range(6):
    plen = int(rng.integers(2, 14))
    reqs.append(serve.Request(
        rid=i,
        prompt=rng.integers(0, cfg.vocab_size, size=plen).astype(np.int32),
        max_new=int(rng.integers(1, 6))))

def run(mesh, pcfg):
    params, specs = split_tree(lm.init_params(jax.random.PRNGKey(0), cfg))
    params = jax.tree.map(jax.device_put, params,
                          tree_shardings(params, specs, pcfg, mesh))
    maxp = 32 // 4
    server = serve.PagedServer(
        cfg, pcfg, mesh, num_slots=4, page_size=4,
        num_pages=1 + 4 * maxp, max_pages_per_slot=maxp,
        params=params, prefill_chunk=5)
    for r in reqs:
        server.submit(dataclasses.replace(r, out=[]))
    done = server.run()
    server.pool.assert_consistent()
    refs = {str(r.rid): serve.greedy_reference(
        cfg, pcfg, mesh, params, r.prompt, r.max_new, max_seq=32)
        for r in reqs}
    return {str(r.rid): r.out for r in done}, refs

flat, flat_ref = run(make_mesh((2, 4), ("data", "model")),
                     ParallelConfig(blk=8))
topo = Topology(intra_bw=50e9, inter_bw=12.5e9, node_size=2)
dims, axes = split_model_axis((2, 4), ("data", "model"), topo.node_size)
hier, hier_ref = run(make_mesh(dims, axes),
                     ParallelConfig(blk=8, topology=topo))
print("RESULT" + json.dumps({"flat": flat, "hier": hier,
                             "flat_ref": flat_ref, "hier_ref": hier_ref}))
"""
    root = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
    env = dict(_os.environ)
    env["PYTHONPATH"] = _os.path.join(root, "src")
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    res = _sp.run([_sys.executable, "-c", code], capture_output=True,
                  text=True, env=env, timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    line = [l for l in res.stdout.splitlines() if l.startswith("RESULT")]
    assert line, res.stdout[-2000:]
    out = _json.loads(line[-1][len("RESULT"):])
    assert out["hier"] == out["flat"], (
        "hierarchical paged serving changed the token stream")
    assert out["hier_ref"] == out["flat_ref"], (
        "hierarchical batch-1 greedy reference changed the token stream")


def test_prefill_chunk_size_is_invisible():
    """Chunked prefill is a scheduling choice, not a numerical one: chunk
    sizes 1/3/16 produce identical streams."""
    cfg = _config("mixtral-8x7b")
    pcfg = ParallelConfig(blk=8, impl="pallas")
    params, _ = split_tree(lm.init_params(jax.random.PRNGKey(0), cfg))
    reqs = _requests(cfg, 4, seed=19)
    outs = []
    maxp = MAX_SEQ // 4
    for chunk in (1, 3, 16):
        server = serve.PagedServer(
            cfg, pcfg, None, num_slots=2, page_size=4,
            num_pages=1 + 2 * maxp, max_pages_per_slot=maxp,
            params=params, prefill_chunk=chunk,
        )
        for r in reqs:
            server.submit(dataclasses.replace(r, out=[]))
        done = server.run()
        outs.append({r.rid: r.out for r in done})
    assert outs[0] == outs[1] == outs[2]


# --- speculative decoding rows (ISSUE 9, DESIGN.md §11) ------------------

class _WrongDrafter:
    """Adversarial drafter proposing provably wrong tokens — every verify
    round hits a mid-verify rejection and the rollback path, yet the
    committed stream must be byte-identical (the sampled correction token
    IS the non-speculative token).

    It knows each request's non-speculative stream (prompt + output), so
    its first draft is the true next token plus one (mod vocab): never
    accepted, hence no later draft of the round is either."""

    def __init__(self, streams: dict, vocab: int):
        self.streams = streams
        self.vocab = vocab

    def draft(self, history, k, rid=-1):
        stream = self.streams[rid]
        pos = len(history)
        nxt = int(stream[pos]) if pos < len(stream) else 0
        return [(nxt + 1 + j) % self.vocab for j in range(k)]


def _spec_requests(cfg, n, seed):
    """Greedy + seeded-temperature mix (odd rids sample at 0.8)."""
    reqs = _requests(cfg, n, seed)
    for r in reqs:
        if r.rid % 2:
            r.temperature, r.seed = 0.8, 1000 + r.rid
        r.max_new = max(r.max_new, 3)   # give speculation room to verify
    return reqs


@pytest.mark.parametrize("arch", ARCHS)
def test_spec_stream_parity(arch):
    """Speculative ON == speculative OFF == batch-1 dense reference on
    every all-attention config in the matrix, greedy AND seeded
    temperature, with the page pool drained and rollback exercised under
    --audit (the structural oracle runs every scheduler step)."""
    cfg = _config(arch)
    pcfg = ParallelConfig(blk=8, impl="pallas")
    params, _ = split_tree(lm.init_params(jax.random.PRNGKey(0), cfg))
    reqs = _spec_requests(cfg, N_REQ, seed=47)
    step = jax.jit(steps_lib.make_serve_step(
        cfg, pcfg, None, (1, 1, cfg.d_model)))
    refs = {r.rid: serve.reference_stream(
        cfg, pcfg, None, params, dataclasses.replace(r, out=[]),
        max_seq=MAX_SEQ, step=step) for r in reqs}

    srv_off, out_off = _run_paged(cfg, pcfg, params, reqs)
    srv_on, out_on = _run_paged(cfg, pcfg, params, reqs,
                                spec=spec_lib.NGramDrafter(), audit=True)
    assert out_on == out_off == refs, (
        f"{arch}: speculative stream diverged")
    assert srv_on.spec.rounds > 0
    _assert_drained(srv_on)


def test_spec_forced_midverify_rejection_parity():
    """An adversarial always-wrong drafter forces a rejection + rollback
    every round; tokens stay identical, rollback trace events fire, and
    the audit oracle holds through every truncation."""
    cfg = _config("mixtral-8x7b")   # windowed: rollback meets reclamation
    pcfg = ParallelConfig(blk=8, impl="pallas")
    params, _ = split_tree(lm.init_params(jax.random.PRNGKey(0), cfg))
    reqs = _spec_requests(cfg, N_REQ, seed=53)

    _, out_off = _run_paged(cfg, pcfg, params, reqs)
    streams = {r.rid: list(r.prompt) + out_off[r.rid] for r in reqs}
    srv_on, out_on = _run_paged(cfg, pcfg, params, reqs,
                                spec=_WrongDrafter(streams, cfg.vocab_size),
                                audit=True)
    assert out_on == out_off
    sp = srv_on.spec.stats()
    assert sp["drafted"] > 0 and sp["accepted_drafts"] == 0
    assert sp["rollback_tokens"] == sp["drafted"]
    assert any(ev[0] == "rollback" for ev in srv_on.trace), (
        "forced rejection never exercised _rollback")
    _assert_drained(srv_on)


def test_spec_parity_int8_kv():
    """Speculative verify writes/reads int8-quantized pages row-wise like
    prefill; streams must match the non-speculative int8 engine."""
    cfg = _config("qwen3-moe-30b-a3b")
    pcfg = ParallelConfig(blk=8, impl="pallas")
    params, _ = split_tree(lm.init_params(jax.random.PRNGKey(0), cfg))
    reqs = _spec_requests(cfg, N_REQ, seed=59)

    _, out_off = _run_paged(cfg, pcfg, params, reqs, kv_quant="int8")
    srv_on, out_on = _run_paged(cfg, pcfg, params, reqs, kv_quant="int8",
                                spec=spec_lib.NGramDrafter(), audit=True)
    assert out_on == out_off
    _assert_drained(srv_on)


def test_spec_parity_under_prefix_cache_hits():
    """Speculation on top of prefix-cache hits: rollback must only ever
    pop decode-region pages, never a refcount>1 shared prompt page (the
    pool raises on any violation), and streams stay identical."""
    cfg = _config("gemma-2b")
    pcfg = ParallelConfig(blk=8, impl="pallas")
    params, _ = split_tree(lm.init_params(jax.random.PRNGKey(0), cfg))
    reqs = _shared_prefix_requests(cfg, N_REQ, seed=61)
    for r in reqs:
        r.max_new = max(r.max_new, 3)

    srv_off, out_off = _run_paged(cfg, pcfg, params, reqs,
                                  prefix_cache=True)
    srv_on, out_on = _run_paged(cfg, pcfg, params, reqs, prefix_cache=True,
                                spec=spec_lib.NGramDrafter(), audit=True)
    assert out_on == out_off
    assert srv_on.index.stats()["hit_tokens"] > 0, "no prefix hits"
    _assert_drained(srv_on)


def test_spec_model_drafter_self_draft_full_acceptance():
    """A ModelDrafter running the TARGET's own config+params drafts
    exactly what greedy verification will sample: every draft of every
    greedy request is accepted (acceptance == 1.0) and the stream still
    equals the non-speculative engine — the strongest equivalence check
    on the multi-token score step."""
    cfg = _config("gemma-2b")
    pcfg = ParallelConfig(blk=8, impl="pallas")
    params, _ = split_tree(lm.init_params(jax.random.PRNGKey(0), cfg))
    reqs = _requests(cfg, N_REQ, seed=67)
    for r in reqs:
        r.max_new = max(r.max_new, 4)   # greedy only

    drafter = spec_lib.ModelDrafter(cfg, pcfg, None, params,
                                    max_seq=MAX_SEQ)
    _, out_off = _run_paged(cfg, pcfg, params, reqs)
    srv_on, out_on = _run_paged(cfg, pcfg, params, reqs, spec=drafter,
                                audit=True)
    assert out_on == out_off
    sp = srv_on.spec.stats()
    assert sp["drafted"] > 0
    assert sp["acceptance_rate"] == 1.0, (
        f"self-drafting must be fully accepted under greedy: {sp}")
    assert not drafter._state, "finished requests leaked draft caches"
    _assert_drained(srv_on)


def test_spec_rejects_recurrent_stack():
    """Hybrid (recurrent) stacks cannot rewind token-wise state by page
    truncation: SpecDecoder must refuse loudly at construction."""
    cfg = dataclasses.replace(
        cfglib.get_smoke_config("jamba-1.5-large-398b"), dtype="float32")
    pcfg = ParallelConfig(blk=8, impl="pallas")
    params, _ = split_tree(lm.init_params(jax.random.PRNGKey(0), cfg))
    maxp = MAX_SEQ // 4
    server = serve.PagedServer(
        cfg, pcfg, None, num_slots=2, page_size=4, num_pages=1 + 2 * maxp,
        max_pages_per_slot=maxp, params=params)
    with pytest.raises(ValueError, match="all-attention"):
        spec_lib.SpecDecoder(server, spec_lib.NGramDrafter(), k=3)
    assert server.spec is None, "failed construction must not attach"
