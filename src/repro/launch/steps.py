"""Step builders: train_step (loss+grads+optimizer), prefill_step,
serve_step — the functions the drivers execute — and the Swin-MoE
classification train step.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch.inputs import activation_spec
from repro.models import lm, swin
from repro.obs import device as obs_device
from repro.optim import adamw
from repro.parallel.sharding import (
    ParallelConfig,
    divisible_spec,
    resolve_spec,
    tree_shardings,
)


def _fwd5(out):
    """Normalise ``lm.forward``'s flag-dependent arity to a 5-tuple
    ``(x, cache, aux, z, stats)`` — ``stats`` is None when router
    telemetry (``pcfg.collect_router_stats``) is off."""
    return out if len(out) == 5 else (*out, None)


def xent_loss(logits, labels, mask):
    """Vocab-parallel-safe cross entropy. logits (B,S,[C,]V) f32."""
    logits = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    shifted = logits - m
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + m[..., 0]
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    per_tok = lse - ll
    if per_tok.ndim == 3:  # (B, S, num_codebooks)
        per_tok = per_tok.mean(-1)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.sum(per_tok * mask) / denom


def chunked_xent(x, params, cfg: ModelConfig, labels, mask,
                 n_chunks: int = 16, pcfg=None, mesh=None):
    """Cross entropy over sequence chunks: the (B, S_c, V) logits block is
    materialised (vocab-sharded) one chunk at a time and rematerialised in
    backward — peak memory drops by n_chunks vs. full-sequence logits,
    which otherwise dominate activation memory for 150k-260k vocabularies.
    x: (B, S, D) final hidden states."""
    from repro.parallel.sharding import constrain

    b, s, _ = x.shape
    while s % n_chunks:
        n_chunks //= 2
    cs = s // n_chunks

    # Gather the (small, bf16) hidden states over the seq-parallel axis so
    # chunk slicing is local and every rank computes every chunk with its
    # vocab shard (balanced vocab-parallel loss).
    if pcfg is not None and mesh is not None:
        x = constrain(x, (("dp",), None, None), pcfg, mesh)

    # Localise the D contraction: the embedding's fsdp (D) shard would
    # otherwise make every logits chunk a full (B,S_c,V) all-reduce over
    # "data". Gathering the table's D once (a few 10s of MB) instead keeps
    # logits purely vocab-sharded.
    if pcfg is not None and mesh is not None:
        params = dict(params)
        if cfg.num_codebooks > 1 and "cb_heads" in params:
            params["cb_heads"] = constrain(
                params["cb_heads"], (None, None, "tp"), pcfg, mesh)
        elif cfg.tie_embeddings:
            params["embed"] = constrain(
                params["embed"], ("tp", None), pcfg, mesh)
        elif "head" in params:
            params["head"] = constrain(
                params["head"], (None, "tp"), pcfg, mesh)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def chunk_loss(x_c, lbl_c, m_c):
        logits = lm._logits_out(params, x_c, cfg)
        lg = logits.astype(jnp.float32)
        mx = jax.lax.stop_gradient(jnp.max(lg, axis=-1, keepdims=True))
        lse = jnp.log(jnp.sum(jnp.exp(lg - mx), axis=-1)) + mx[..., 0]
        ll = jnp.take_along_axis(lg, lbl_c[..., None], axis=-1)[..., 0]
        per_tok = lse - ll
        if per_tok.ndim == 3:
            per_tok = per_tok.mean(-1)
        return jnp.sum(per_tok * m_c)

    # A scan, not an unrolled loop: its backward accumulates the output
    # head's gradient in one carry. Unrolled, every chunk kept a float32
    # head-sized gradient of its own alive (13 GB per chip for a replicated
    # 151936-row head under data_centric).
    def body(total, c):
        def sl(a):
            return jax.lax.dynamic_slice_in_dim(a, c * cs, cs, axis=1)
        return total + chunk_loss(sl(x), sl(labels), sl(mask)), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                            jnp.arange(n_chunks))
    return total / jnp.maximum(jnp.sum(mask), 1.0)


def make_loss_fn(cfg: ModelConfig, pcfg: ParallelConfig, mesh: Optional[Mesh],
                 batch_shape3):
    """Build the training loss closure: LM forward (hidden-state output) +
    chunked cross-entropy + MoE aux/z losses, weighted per ``cfg.moe``."""
    x_spec = activation_spec(batch_shape3, pcfg, mesh)
    aw = cfg.moe.aux_weight if cfg.moe else 0.0
    zw = cfg.moe.z_weight if cfg.moe else 0.0

    def loss_fn(params, batch):
        hidden, _, aux, z, stats = _fwd5(lm.forward(
            params, batch, cfg, pcfg, mesh, mode="train", x_spec=x_spec,
            return_hidden=True,
        ))
        labels = batch["labels"]
        mask = batch["loss_mask"]
        if cfg.frontend == "siglip":
            # no loss on the image prefix
            n_img = hidden.shape[1] - (labels.shape[1])
            if n_img > 0:
                hidden = hidden[:, n_img:]
        loss = chunked_xent(hidden, params, cfg, labels, mask,
                            pcfg=pcfg, mesh=mesh)
        total = loss + aw * aux + zw * z
        metrics = {"loss": loss, "aux_loss": aux, "z_loss": z}
        if stats is not None:
            # Device telemetry rides the has_aux channel (not
            # differentiated); train drivers pop this non-scalar entry
            # before float()-ing the metrics dict.
            metrics["router_stats"] = stats
        return total, metrics

    return loss_fn


def make_train_step(
    cfg: ModelConfig,
    pcfg: ParallelConfig,
    mesh: Optional[Mesh],
    opt_cfg: adamw.OptimizerConfig,
    batch_shape3,
):
    """Build the jittable train step: value_and_grad of ``make_loss_fn``
    followed by the AdamW update, returning (params, opt_state, metrics)."""
    loss_fn = make_loss_fn(cfg, pcfg, mesh, batch_shape3)

    def train_step(params, opt_state, batch):
        (total, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch
        )
        params, opt_state, om = adamw.apply_updates(
            params, grads, opt_state, opt_cfg
        )
        return params, opt_state, {**metrics, **om, "total_loss": total}

    return train_step


def make_swin_loss_fn(cfg: swin.SwinConfig, pcfg: ParallelConfig):
    """Swin-MoE classification loss ``(params, images, labels) -> loss``:
    cross-entropy against one-hot labels plus ``cfg.moe.aux_weight`` times
    the load-balancing loss, through ``swin.swin_forward``'s hexa path."""

    def loss_fn(params, images, labels):
        logits, aux, _ = swin.swin_forward(params, images, cfg, pcfg, None)
        onehot = jax.nn.one_hot(labels, cfg.num_classes)
        ce = -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * onehot, -1))
        return ce + cfg.moe.aux_weight * aux

    return loss_fn


def make_swin_train_step(cfg: swin.SwinConfig, pcfg: ParallelConfig,
                         opt_cfg: adamw.OptimizerConfig):
    """Build the jittable Swin-MoE train step: value_and_grad of
    ``make_swin_loss_fn`` followed by the AdamW update,
    ``(params, opt_state, images, labels) -> (params, opt_state, loss)``."""
    loss_fn = make_swin_loss_fn(cfg, pcfg)

    def train_step(params, opt_state, images, labels):
        loss, grads = jax.value_and_grad(loss_fn)(params, images, labels)
        params, opt_state, _ = adamw.apply_updates(
            params, grads, opt_state, opt_cfg
        )
        return params, opt_state, loss

    return train_step


def make_prefill_step(cfg: ModelConfig, pcfg: ParallelConfig,
                      mesh: Optional[Mesh], batch_shape3):
    """Build the dense-cache prefill step: one forward over the whole
    prompt batch, writing K/V rows into the ``(slots, max_seq)`` cache."""
    x_spec = activation_spec(batch_shape3, pcfg, mesh)

    def prefill_step(params, inputs, cache):
        logits, new_cache, _, _, stats = _fwd5(lm.forward(
            params, inputs, cfg, pcfg, mesh, mode="prefill",
            cache=cache, x_spec=x_spec,
        ))
        if pcfg.collect_router_stats:
            return logits, new_cache, stats
        return logits, new_cache

    return prefill_step


def make_serve_step(cfg: ModelConfig, pcfg: ParallelConfig,
                    mesh: Optional[Mesh], batch_shape3):
    """Build the dense-cache decode macro-step: one token per occupied
    slot (``active`` masks the rest), returning last-position logits.
    With ``pcfg.collect_router_stats`` the return grows a third element,
    the obs.device stats pytree (DESIGN.md §12)."""
    # decode tokens are replicated over TP (S=1 can't shard).
    x_spec = activation_spec(batch_shape3, pcfg, mesh)

    def serve_step(params, inputs, cache):
        logits, new_cache, _, _, stats = _fwd5(lm.forward(
            params, inputs, cfg, pcfg, mesh, mode="decode",
            cache=cache, x_spec=x_spec,
            active=inputs.get("active"),
        ))
        if pcfg.collect_router_stats:
            return logits, new_cache, stats
        return logits, new_cache

    return serve_step


def make_paged_serve_step(cfg: ModelConfig, pcfg: ParallelConfig,
                          mesh: Optional[Mesh], batch_shape3,
                          page_size: int):
    """Continuous-batching decode macro-step over the paged KV cache
    (DESIGN.md §7). ``inputs`` carries the scheduler's per-step view:
    tokens (B, 1), page_table (B, maxp) int32, active (B,) bool. With
    ``pcfg.collect_router_stats`` the return grows a third element, the
    obs.device stats pytree (DESIGN.md §12)."""
    x_spec = activation_spec(batch_shape3, pcfg, mesh)

    def serve_step(params, inputs, cache):
        logits, new_cache, _, _, stats = _fwd5(lm.forward(
            params, {"tokens": inputs["tokens"]}, cfg, pcfg, mesh,
            mode="decode", cache=cache, x_spec=x_spec,
            paged={"table": inputs["page_table"], "page_size": page_size},
            active=inputs["active"],
        ))
        if pcfg.collect_router_stats:
            return logits, new_cache, stats
        return logits, new_cache

    return serve_step


def make_paged_prefill_step(cfg: ModelConfig, pcfg: ParallelConfig,
                            mesh: Optional[Mesh], page_size: int):
    """Chunked prefill into the paged cache (DESIGN.md §7): one request's
    next ``chunk`` prompt tokens advance between decode macro-steps.
    Returns the logits at the last valid token — after the final chunk
    these are the request's first-generated-token logits, exactly what a
    batch-1 dense prefill would have produced.

    Two implementations behind one signature
    ``(params, tokens (chunk,), n_valid (), slot (), table_row (maxp,),
    cache) -> (last_logits (V,), cache)``; short final chunks pad and mask:

      * all-attention stacks: ONE batch-1 forward over the whole chunk
        (``mode="prefill"`` + ``paged`` — the chunk-extension attention in
        ``models.transformer``), the production chunked-prefill shape;
      * stacks with recurrent mixers (mamba/xlstm): a ``lax.scan`` of
        single-token decode forwards — those states only advance
        token-wise mid-stream, so the chunk is a scheduling unit, not a
        compute one.
    """
    if all(cfg.layer_kind(p) == "attn" for p in range(cfg.period)):
        return _make_paged_prefill_chunk(cfg, pcfg, mesh, page_size)
    return _make_paged_prefill_scan(cfg, pcfg, mesh, page_size)


def _paged_chunk_forward(cfg: ModelConfig, pcfg: ParallelConfig,
                         mesh: Optional[Mesh], page_size: int):
    """Shared body of the chunk-extension paged forward: one batch-1
    ``mode="prefill"`` forward over ``chunk`` tokens continuing at the
    slot's resident length, against the shared page pools through
    ``table_row``. Returns the final-norm hidden states at EVERY chunk
    position, the cache with the slot's length advanced by ``n_valid``,
    and the obs.device stats pytree (None when telemetry is off) — the
    prefill step projects only the last valid row to logits, the
    speculative score step projects them all (DESIGN.md §11).
    All-attention stacks only: recurrent mixers advance per-slot state
    token-wise and take the scan path instead."""
    if any(cfg.layer_kind(p) != "attn" for p in range(cfg.period)):
        raise ValueError(
            "chunk-extension paged forward requires an all-attention "
            "stack (recurrent mixers advance token-wise)")
    x_spec = activation_spec((1, 1, cfg.d_model), pcfg, mesh)

    def fwd(params, tokens, n_valid, slot, table_row, cache):
        chunk = tokens.shape[0]
        # every layer is attention, so the whole layer cache is the shared
        # (batch-free) page pools — only the length is per-slot
        sub = {
            "layers": cache["layers"],
            "len": jax.lax.dynamic_slice(cache["len"], (slot,), (1,)),
        }
        active = (jnp.arange(chunk) < n_valid)[None]       # (1, chunk)
        hidden, sub, _, _, stats = _fwd5(lm.forward(
            params, {"tokens": tokens[None]}, cfg, pcfg, mesh,
            mode="prefill", cache=sub, x_spec=x_spec,
            paged={"table": table_row[None], "page_size": page_size},
            active=active, return_hidden=True,
        ))
        new_len = jax.lax.dynamic_update_slice(
            cache["len"], sub["len"], (slot,))
        return hidden, {"layers": sub["layers"], "len": new_len}, stats

    return fwd


def _make_paged_prefill_chunk(cfg: ModelConfig, pcfg: ParallelConfig,
                              mesh: Optional[Mesh], page_size: int):
    fwd = _paged_chunk_forward(cfg, pcfg, mesh, page_size)

    def prefill_step(params, tokens, n_valid, slot, table_row, cache):
        hidden, new_cache, stats = fwd(params, tokens, n_valid, slot,
                                       table_row, cache)
        # last valid row only: prefill wants the first-generated-token
        # logits, and projecting one row keeps the vocab matmul off the
        # chunk's other positions
        last_h = jax.lax.dynamic_slice_in_dim(hidden, n_valid - 1, 1, axis=1)
        logits = lm._logits_out(params, last_h, cfg)
        out = logits.reshape(-1).astype(jnp.float32)
        if pcfg.collect_router_stats:
            return out, new_cache, stats
        return out, new_cache

    return prefill_step


def make_paged_score_step(cfg: ModelConfig, pcfg: ParallelConfig,
                          mesh: Optional[Mesh], page_size: int):
    """Multi-token scoring step for speculative verification (DESIGN.md
    §11): the chunk-extension paged forward of ``make_paged_prefill_step``
    with logits at **every** chunk position instead of only the last.

    Signature ``(params, tokens (k,), n_valid (), slot (), table_row
    (maxp,), cache) -> (logits (k, V) f32, cache)``: row ``i`` is the
    next-token distribution AFTER ``tokens[:i+1]``, i.e. exactly what a
    sequential decode would have produced having fed ``tokens[i]`` — so
    one forward verifies a whole drafted continuation against the same
    paged pools. The slot's cache length advances by ``n_valid``; rows at
    and past ``n_valid`` are sink-written padding and must be ignored (the
    caller rolls back rejected rows by page-table truncation,
    ``PagedServer._rollback``). All-attention stacks only — raises
    ``ValueError`` otherwise (see ``launch.spec.SpecDecoder``)."""
    if cfg.num_codebooks > 1:
        raise ValueError("score step does not support codebook heads")
    fwd = _paged_chunk_forward(cfg, pcfg, mesh, page_size)

    def score_step(params, tokens, n_valid, slot, table_row, cache):
        hidden, new_cache, stats = fwd(params, tokens, n_valid, slot,
                                       table_row, cache)
        logits = lm.score_logits(params, hidden, cfg)   # (1, chunk, V)
        out = logits[0].astype(jnp.float32)
        if pcfg.collect_router_stats:
            return out, new_cache, stats
        return out, new_cache

    return score_step


def make_paged_handoff_step(cfg: ModelConfig):
    """Prefill→decode role handoff (DESIGN.md §7 disaggregation): move one
    finished-prefill sequence from slot ``src`` to slot ``dst`` as a PURE
    page-table/metadata transfer — the KV pages live in the shared pool and
    are reached through the (host-side) table row the scheduler copies, so
    NO K/V bytes move. On-device state that IS per-slot moves here: the
    ``len`` entry and, for hybrid stacks, the recurrent mixer state rows
    (mamba conv/ssm, xlstm c/n/h/m) — constant-size, orders of magnitude
    below the KV it avoids copying. ``src``/``dst`` are traced int32
    scalars so slot refill never retraces."""
    is_attn = [cfg.layer_kind(p) == "attn" for p in range(cfg.period)]

    def handoff(cache, src, dst):
        def move_rows(v):
            row = jax.lax.dynamic_index_in_dim(v, src, axis=1, keepdims=True)
            v = jax.lax.dynamic_update_slice_in_dim(v, row, dst, axis=1)
            return jax.lax.dynamic_update_slice_in_dim(
                v, jnp.zeros_like(row), src, axis=1)

        layers = [
            cache["layers"][p] if is_attn[p]
            else jax.tree.map(move_rows, cache["layers"][p])
            for p in range(cfg.period)
        ]
        ln = cache["len"]
        val = jax.lax.dynamic_index_in_dim(ln, src, axis=0, keepdims=True)
        ln = jax.lax.dynamic_update_slice(ln, val, (dst,))
        ln = jax.lax.dynamic_update_slice(
            ln, jnp.zeros((1,), ln.dtype), (src,))
        return {"layers": layers, "len": ln}

    return handoff


def make_page_copy_step(cfg: ModelConfig):
    """Copy one physical page's K/V rows (and int8 scale rows) from ``src``
    to ``dst`` across every attention pool — the device half of
    ``parallel.cache.PagePool.cow``: the scheduler allocates ``dst`` from
    the writer's reservation, copies the shared payload here, and repoints
    the writer's table entry so the refcount>1 original is never written
    (DESIGN.md §7)."""
    is_attn = [cfg.layer_kind(p) == "attn" for p in range(cfg.period)]

    def copy_page(cache, src, dst):
        def cp(v):
            row = jax.lax.dynamic_index_in_dim(v, src, axis=1, keepdims=True)
            return jax.lax.dynamic_update_slice_in_dim(v, row, dst, axis=1)

        layers = [
            jax.tree.map(cp, cache["layers"][p]) if is_attn[p]
            else cache["layers"][p]
            for p in range(cfg.period)
        ]
        return {"layers": layers, "len": cache["len"]}

    return copy_page


def _make_paged_prefill_scan(cfg: ModelConfig, pcfg: ParallelConfig,
                             mesh: Optional[Mesh], page_size: int):
    x_spec = activation_spec((1, 1, cfg.d_model), pcfg, mesh)
    period = cfg.period
    is_attn = [cfg.layer_kind(p) == "attn" for p in range(period)]
    collect = pcfg.collect_router_stats
    n_experts = cfg.moe.num_experts if cfg.moe is not None else 1

    def prefill_step(params, tokens, n_valid, slot, table_row, cache):
        def take_slot(tree):
            return jax.tree.map(
                lambda v: jax.lax.dynamic_slice_in_dim(v, slot, 1, axis=1),
                tree,
            )

        sub_layers = [
            cache["layers"][p] if is_attn[p]
            else take_slot(cache["layers"][p])
            for p in range(period)
        ]
        sub = {
            "layers": sub_layers,
            "len": jax.lax.dynamic_slice(cache["len"], (slot,), (1,)),
        }

        def body(carry, xs):
            if collect:
                sc, last, stacc = carry
            else:
                sc, last = carry
            tok, t = xs
            act = (t < n_valid)[None]
            logits, sc, _, _, st = _fwd5(lm.forward(
                params, {"tokens": tok.reshape(1, 1)}, cfg, pcfg, mesh,
                mode="decode", cache=sc, x_spec=x_spec,
                paged={"table": table_row[None], "page_size": page_size},
                active=act,
            ))
            last = jnp.where(act[0], logits.reshape(-1), last)
            if collect:
                return (sc, last, obs_device.add_stats(stacc, st)), None
            return (sc, last), None

        chunk = tokens.shape[0]
        last0 = jnp.zeros((cfg.vocab_size,), jnp.float32)
        if collect:
            init = (sub, last0, obs_device.zero_stats(n_experts))
            (sub, last, stats), _ = jax.lax.scan(
                body, init, (tokens, jnp.arange(chunk))
            )
        else:
            (sub, last), _ = jax.lax.scan(
                body, (sub, last0), (tokens, jnp.arange(chunk))
            )
            stats = None

        new_layers = []
        for p in range(period):
            if is_attn[p]:
                new_layers.append(sub["layers"][p])
            else:
                new_layers.append(jax.tree.map(
                    lambda full, part: jax.lax.dynamic_update_slice_in_dim(
                        full, part, slot, axis=1
                    ),
                    cache["layers"][p], sub["layers"][p],
                ))
        new_len = jax.lax.dynamic_update_slice(
            cache["len"], sub["len"], (slot,)
        )
        new_cache = {"layers": new_layers, "len": new_len}
        if collect:
            return last, new_cache, stats
        return last, new_cache

    return prefill_step


def sharded_params(cfg: ModelConfig, pcfg: ParallelConfig, mesh: Mesh):
    """(abstract_params_with_shardings, shardings_tree, logical_specs)."""
    values, specs = lm.abstract_params(cfg, plan=pcfg.hetero_plan)
    sh = tree_shardings(values, specs, pcfg, mesh)
    abstract = jax.tree.map(
        lambda v, s: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=s),
        values, sh,
    )
    return abstract, sh, specs


def sharded_opt_state(abstract_params, opt_cfg: adamw.OptimizerConfig,
                      mesh: Mesh):
    """Abstract optimizer state whose moments inherit param shardings."""
    def like(p, dtype):
        return jax.ShapeDtypeStruct(p.shape, dtype, sharding=p.sharding)

    sd = jnp.dtype(opt_cfg.state_dtype)
    state = {
        "m": jax.tree.map(lambda p: like(p, sd), abstract_params),
        "v": jax.tree.map(lambda p: like(p, sd), abstract_params),
        "step": jax.ShapeDtypeStruct(
            (), jnp.int32, sharding=NamedSharding(mesh, P())
        ),
    }
    if opt_cfg.master_fp32:
        state["master"] = jax.tree.map(
            lambda p: (
                like(p, jnp.float32)
                if jnp.issubdtype(p.dtype, jnp.floating) and p.dtype != jnp.float32
                else None
            ),
            abstract_params,
        )
    return state


def wrap_step_with_faults(step_fn, site: str):
    """Host-level chaos wrapper for a jitted step callable (DESIGN.md §9).

    Fault injection cannot live *inside* a jitted function — the hook
    would fire once at trace time and never again — so the drivers wrap
    their compiled steps here: ``inject(site)`` runs before every call
    (raising for ``error``/``device_drop`` kinds, sleeping for ``delay``)
    and the wrapped fn is only entered if no fault fires. With no
    installed plan the wrapper adds one attribute read per step."""
    from repro.runtime import faults as faults_lib

    @functools.wraps(step_fn)
    def wrapped(*args, **kwargs):
        faults_lib.inject(site)
        return step_fn(*args, **kwargs)

    return wrapped
