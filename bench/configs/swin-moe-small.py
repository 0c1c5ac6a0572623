"""Swin-MoE-Small: the program's training step, and its plain reference.

``Program`` is the system under test: the benchmark's weights in the
layout of ``repro.models.swin``, and the step that the window drives, a
copy of ``benchmarks.memory_table.make_train_fn`` (``swin.swin_forward``
with ``moe_impl="hexa"`` on the Pallas kernels, then
``repro.optim.adamw.apply_updates``).

``Reference`` is the plain reference: the forward pass, loss,
gradients and AdamW written here in ``jax.numpy`` from the configuration,
importing nothing of the program. It runs in float32 at the ``highest``
matmul precision. As the control it runs one precision below what the
configuration states (float32 weights and activations, matmuls at the
default precision: bfloat16 operands): every matmul operand rounded to
float8 e4m3 in the forward pass, and the gradient of every such operand in
the backward pass, with the weights, the optimizer and everything else in
float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts

# -- the weights, made by the benchmark from the seed ------------------------

def _ln(d):
    return {"scale": ("ones", (d,)), "bias": ("zeros", (d,))}


def param_shapes(c: dict) -> dict:
    """The parameter tree of ``repro.models.swin`` as (init, shape) leaves."""
    p, e = c["patch_size"], c["num_experts"]
    tree = {
        "patch_w": ("normal", (p, p, c["in_chans"], c["dims"][0])),
        "patch_b": ("normal", (c["dims"][0],)),
        "patch_ln": _ln(c["dims"][0]),
        "stages": [],
        "final_ln": _ln(c["dims"][-1]),
        "head_w": ("normal", (c["dims"][-1], c["num_classes"])),
        "head_b": ("normal", (c["num_classes"],)),
    }
    for s, depth in enumerate(c["depths"]):
        d, h = c["dims"][s], int(c["mlp_ratio"] * c["dims"][s])
        blocks = []
        for b in range(depth):
            blk = {
                "ln1": _ln(d),
                "attn": {
                    "qkv_w": ("normal", (d, 3 * d)),
                    "qkv_b": ("normal", (3 * d,)),
                    "proj_w": ("normal", (d, d)),
                    "proj_b": ("normal", (d,)),
                    "rel_bias": ("normal", ((2 * c["window"] - 1) ** 2,
                                            c["heads"][s])),
                },
                "ln2": _ln(d),
            }
            if is_moe(c, s, b):
                blk["moe"] = {"router": ("normal", (d, e)),
                              "w1": ("normal", (e, d, h)),
                              "b1": ("normal", (e, h)),
                              "w2": ("normal", (e, h, d)),
                              "b2": ("normal", (e, d))}
            else:
                blk["mlp"] = {"w1": ("normal", (d, h)), "b1": ("normal", (h,)),
                              "w2": ("normal", (h, d)), "b2": ("normal", (d,))}
            blocks.append(blk)
        stage = {"blocks": blocks}
        if s < len(c["depths"]) - 1:
            stage["merge_w"] = ("normal", (4 * d, 2 * d))
            stage["merge_ln"] = _ln(4 * d)
        tree["stages"].append(stage)
    return tree


def is_moe(c: dict, stage: int, block: int) -> bool:
    return stage in c["moe_stages"] and block % 2 == 1


def make_params(c: dict, key):
    """The weights in float32, on the default device, in one jitted call."""
    shapes = param_shapes(c)
    leaves, treedef = jax.tree.flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], str))

    def make(key):
        out = []
        for i, (init, shape) in enumerate(leaves):
            if init == "normal":
                out.append(0.02 * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32))
            else:
                out.append((jnp.ones if init == "ones" else jnp.zeros)(
                    shape, jnp.float32))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(make)(key)


# -- the program ---------------------------------------------------------------

class Program:
    """The timed path: the program's Swin-MoE train step over the
    benchmark's weights and batches."""

    def __init__(self, c: dict, traffic: dict):
        from repro.configs.base import MoEConfig
        from repro.models.swin import SwinConfig
        from repro.optim import adamw
        from repro.parallel.sharding import ParallelConfig

        self.c, self.traffic = c, traffic
        self.scfg = SwinConfig(
            name=c["name"], img_size=c["img_size"],
            patch_size=c["patch_size"], in_chans=c["in_chans"],
            depths=tuple(c["depths"]), dims=tuple(c["dims"]),
            heads=tuple(c["heads"]), window=c["window"],
            mlp_ratio=c["mlp_ratio"], num_classes=c["num_classes"],
            moe_stages=tuple(c["moe_stages"]),
            moe=MoEConfig(num_experts=c["num_experts"], top_k=c["top_k"],
                          d_ff=0, norm_topk=c["norm_topk"]),
            norm_eps=c["norm_eps"], dtype=c["dtype"])
        self.pcfg = ParallelConfig(impl=c["kernel_impl"])
        o = c["optimizer"]
        self.opt_cfg = adamw.OptimizerConfig(
            peak_lr=o["peak_lr"], min_lr=o["min_lr"],
            warmup_steps=o["warmup_steps"], decay_steps=o["decay_steps"],
            b1=o["b1"], b2=o["b2"], eps=o["eps"],
            weight_decay=o["weight_decay"], grad_clip=o["grad_clip"],
            master_fp32=False)

    def init_state(self, key):
        from repro.optim import adamw

        params = make_params(self.c, key)
        return params, adamw.init_opt_state(params, self.opt_cfg)

    def loss_fn(self, params, images, labels):
        """Copy of ``benchmarks.memory_table.make_loss_fn``."""
        from repro.models import swin

        logits, aux, _ = swin.swin_forward(
            params, images, self.scfg, self.pcfg, None,
            moe_impl=self.c["moe_impl"])
        onehot = jax.nn.one_hot(labels, self.scfg.num_classes)
        ce = -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * onehot, -1))
        return ce + self.c["aux_weight"] * aux

    def step_fn(self):
        """Copy of ``benchmarks.memory_table.make_train_fn``, over a state
        tuple and a batch dict: ``(state, batch) -> (state, loss)``."""
        from repro.optim import adamw

        def train(state, batch):
            params, opt = state
            loss, grads = jax.value_and_grad(self.loss_fn)(
                params, batch["images"], batch["labels"])
            params, opt, _ = adamw.apply_updates(params, grads, opt,
                                                 self.opt_cfg)
            return (params, opt), loss

        return train

    @staticmethod
    def params_of(state):
        return state[0]

    def first_grads_of(self, state):
        """The clipped gradient the optimizer took in its first step,
        worked out from its state after that step: m / (1 - b1)."""
        return jax.tree.map(lambda m: m / (1 - self.opt_cfg.b1),
                            state[1]["m"])

    def model_flops_per_step(self) -> float:
        return 3.0 * self.traffic["batch"] * counts.swin_forward_flops(self.c)

    def expert_least_time_s(self, device_kind: str) -> float:
        """Least time of one step's expert FFN work, forward and backward."""
        from bench.peaks import least_time_s

        c, total = self.c, 0.0
        size = c["img_size"] // c["patch_size"]
        for s, depth in enumerate(c["depths"]):
            d, h = c["dims"][s], int(c["mlp_ratio"] * c["dims"][s])
            rows = self.traffic["batch"] * size * size * c["top_k"]
            kw = dict(experts=c["num_experts"], glu=False, w_bytes=4,
                      a_bytes=4, bias=True)
            for b in range(depth):
                if is_moe(c, s, b):
                    for f in (counts.expert_ffn, counts.expert_ffn_backward):
                        n = f(rows, d, h, **kw)
                        total += least_time_s(n["flops"], n["bytes"],
                                              device_kind)
            size //= 2 if s < len(c["depths"]) - 1 else 1
        return total


# -- the plain reference -----------------------------------------------------------

def _layernorm(p, x, eps):
    x32 = x.astype(jnp.float32)
    mean = x32.mean(-1, keepdims=True)
    var = ((x32 - mean) ** 2).mean(-1, keepdims=True)
    y = (x32 - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]
    return y.astype(x.dtype)


def _gelu_tanh(x):
    return 0.5 * x * (1 + jnp.tanh(np.sqrt(2 / np.pi)
                                   * (x + 0.044715 * x ** 3)))


def _bias_table_index(w: int) -> np.ndarray:
    """(w*w, w*w) index into the (2w-1)^2 relative-position table."""
    pos = [(i, j) for i in range(w) for j in range(w)]
    return np.array([[(qi - ki + w - 1) * (2 * w - 1) + (qj - kj + w - 1)
                      for ki, kj in pos] for qi, qj in pos], np.int32)


def _fp8(x):
    """``x`` rounded to float8 e4m3 with one scale for the whole tensor
    (its largest magnitude maps to 448), back in its own type."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale


@jax.custom_vjp
def _fp8_operand(x):
    """A matmul operand in float8: rounded on the way forward, and its
    gradient rounded on the way back."""
    return _fp8(x)


_fp8_operand.defvjp(lambda x: (_fp8(x), None), lambda _, g: (_fp8(g),))


def _exact(x):
    return x


def _window_attention(p, x, heads, w, r):
    b, hh, ww, c = x.shape
    hd = c // heads
    win = x.reshape(b, hh // w, w, ww // w, w, c).transpose(0, 1, 3, 2, 4, 5)
    win = win.reshape(-1, w * w, c)
    qkv = r(win) @ r(p["qkv_w"]) + p["qkv_b"]
    q, k, v = (qkv[..., i * c:(i + 1) * c].reshape(-1, w * w, heads, hd)
               for i in range(3))
    scores = jnp.einsum("nqhd,nkhd->nhqk", r(q), r(k)) / np.sqrt(hd)
    bias = p["rel_bias"][_bias_table_index(w)]           # (q, k, heads)
    scores = scores + jnp.moveaxis(bias, -1, 0)[None]
    attn = jax.nn.softmax(scores.astype(jnp.float32), -1).astype(x.dtype)
    out = jnp.einsum("nhqk,nkhd->nqhd", r(attn), r(v)).reshape(-1, w * w, c)
    out = r(out) @ r(p["proj_w"]) + p["proj_b"]
    out = out.reshape(b, hh // w, ww // w, w, w, c).transpose(0, 1, 3, 2, 4, 5)
    return out.reshape(b, hh, ww, c)


def _moe_mlp(p, x, c, r):
    """Top-k routed expert MLP, every expert computed densely and weighted
    by its gate (zero where not chosen). Returns (y, count of rows routed
    to each expert, sum over rows of the router's probabilities). The
    router stays in float32 whatever ``r`` does to the experts' operands."""
    e, k = c["num_experts"], c["top_k"]
    logits = x.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, -1)
    top, idx = jax.lax.top_k(probs, k)
    if c["norm_topk"]:
        top = top / jnp.maximum(top.sum(-1, keepdims=True), 1e-9)
    chosen = jax.nn.one_hot(idx, e, dtype=jnp.float32)          # (n, k, e)
    gates = jnp.einsum("nk,nke->ne", top, chosen).astype(x.dtype)
    h = _gelu_tanh(jnp.einsum("nd,edf->nef", r(x), r(p["w1"])) + p["b1"])
    y = jnp.einsum("nef,efd->ned", r(h), r(p["w2"])) + p["b2"]
    y = jnp.einsum("ne,ned->nd", gates, y)
    return y, chosen.sum((0, 1)), probs.sum(0)


def _forward(params, images, c, r=_exact):
    """Logits and, for each MoE block, (expert counts, probability sums).
    ``r`` is applied to every matmul operand but the router's."""
    p = c["patch_size"]
    x = jax.lax.conv_general_dilated(
        r(images), r(params["patch_w"]), (p, p), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC")) + params["patch_b"]
    x = _layernorm(params["patch_ln"], x, c["norm_eps"])
    stats = []
    for s, stage in enumerate(params["stages"]):
        for b, blk in enumerate(stage["blocks"]):
            w = min(c["window"], x.shape[1], x.shape[2])
            shift = w // 2 if b % 2 == 1 and w < x.shape[1] else 0
            h = _layernorm(blk["ln1"], x, c["norm_eps"])
            h = jnp.roll(h, (-shift, -shift), (1, 2))
            h = _window_attention(blk["attn"], h, c["heads"][s], w, r)
            x = x + jnp.roll(h, (shift, shift), (1, 2))
            h = _layernorm(blk["ln2"], x, c["norm_eps"])
            bb, hh, ww, d = h.shape
            rows = h.reshape(-1, d)
            if "moe" in blk:
                y, cnt, psum = _moe_mlp(blk["moe"], rows, c, r)
                stats.append((cnt, psum))
            else:
                m = blk["mlp"]
                y = r(_gelu_tanh(r(rows) @ r(m["w1"]) + m["b1"])) @ r(
                    m["w2"]) + m["b2"]
            x = x + y.reshape(bb, hh, ww, d)
        if "merge_w" in stage:
            bb, hh, ww, d = x.shape
            x = x.reshape(bb, hh // 2, 2, ww // 2, 2, d)
            x = x.transpose(0, 1, 3, 2, 4, 5).reshape(bb, hh // 2, ww // 2,
                                                      4 * d)
            x = _layernorm(stage["merge_ln"], x, c["norm_eps"])
            x = r(x) @ r(stage["merge_w"])
    x = _layernorm(params["final_ln"], x, c["norm_eps"])
    pooled = x.astype(jnp.float32).mean((1, 2))
    logits = r(pooled) @ r(params["head_w"]) + params["head_b"]
    return logits, stats


def _tokens_per_image(c: dict) -> list[int]:
    """Tokens per image at each MoE block, in order."""
    out, size = [], c["img_size"] // c["patch_size"]
    for s, depth in enumerate(c["depths"]):
        out += [size * size for b in range(depth) if is_moe(c, s, b)]
        size //= 2 if s < len(c["depths"]) - 1 else 1
    return out


class Reference:
    """Loss and gradients of a whole batch, computed in blocks of rows, and
    the optimizer step.

    The load-balancing loss couples every row of a batch (E * sum_e f_e *
    P_e, with f_e the share of rows routed to expert e and P_e the mean
    probability), so a first pass counts f_e over the batch, and the
    second adds each block's share of P_e at those fixed f_e. The sum of
    the blocks' losses and gradients is the whole batch's. With
    ``control``, every matmul operand is in float8 (``_fp8_operand``)."""

    def __init__(self, c: dict, control: bool = False,
                 rows_per_block: int = 16):
        self.c, self.rows = c, rows_per_block
        r = _fp8_operand if control else _exact

        def count(params, images):
            with jax.default_matmul_precision("highest"):
                _, stats = _forward(params, images, c, r)
            return [cnt for cnt, _ in stats]

        def block_loss(params, images, labels, f, n_images):
            with jax.default_matmul_precision("highest"):
                logits, stats = _forward(params, images, c, r)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            ce = -jnp.take_along_axis(logp, labels[:, None], 1).sum()
            aux = sum(c["num_experts"] * jnp.sum(fl * psum.astype(jnp.float32))
                      / (n_images * t)
                      for fl, (_, psum), t in zip(f, stats,
                                                  _tokens_per_image(c)))
            aux = aux / max(len(stats), 1)
            return ce / n_images + c["aux_weight"] * aux

        self._count = jax.jit(count)
        self._grad = jax.jit(jax.value_and_grad(block_loss),
                             static_argnums=4)
        self.update = jax.jit(
            lambda p, g, m, v, step: adamw(p, g, m, v, step, c["optimizer"]),
            static_argnums=4)

    def loss_and_grads(self, params, batch: dict):
        images, labels = batch["images"], batch["labels"]
        n = images.shape[0]
        blocks = [(images[i:i + self.rows], labels[i:i + self.rows])
                  for i in range(0, n, self.rows)]
        counts_ = None
        for x, _ in blocks:
            cnt = self._count(params, x)
            counts_ = cnt if counts_ is None else [
                a + b for a, b in zip(counts_, cnt)]
        f = [cnt / (n * t * self.c["top_k"])
             for cnt, t in zip(counts_, _tokens_per_image(self.c))]
        loss, grads = 0.0, None
        for x, y in blocks:
            l, g = self._grad(params, x, y, f, n)
            loss = loss + l
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        return loss, grads

    def steps(self, key, batches: list):
        """The first ``len(batches)`` steps from the benchmark's weights:
        (losses, first clipped gradient, params before, params after), all
        in float32."""
        params = p0 = make_params(self.c, key)
        m = v = jax.tree.map(jnp.zeros_like, params)
        losses, first = [], None
        for i, batch in enumerate(batches):
            loss, grads = self.loss_and_grads(params, batch)
            params, m, v, g = self.update(params, grads, m, v, i + 1)
            losses.append(float(loss))
            if first is None:
                first = g
        return losses, first, p0, params


def adamw(params, grads, m, v, step: int, o: dict):
    """One AdamW step as the configuration states it: warmup then cosine
    learning rate, clipping by the global gradient norm, bias-corrected
    moments, decoupled weight decay on matrices only. Returns (params, m,
    v, the clipped gradient)."""
    if step < o["warmup_steps"]:
        lr = o["peak_lr"] * step / max(o["warmup_steps"], 1)
    else:
        t = min(max((step - o["warmup_steps"])
                    / max(o["decay_steps"] - o["warmup_steps"], 1), 0.0), 1.0)
        lr = o["min_lr"] + 0.5 * (o["peak_lr"] - o["min_lr"]) * (
            1 + np.cos(np.pi * t))
    gnorm = jnp.sqrt(sum(jnp.sum(g ** 2)
                         for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, o["grad_clip"] / jnp.maximum(gnorm, 1e-9))
    g = jax.tree.map(lambda a: a * scale, grads)
    m = jax.tree.map(lambda a, b: o["b1"] * a + (1 - o["b1"]) * b, m, g)
    v = jax.tree.map(lambda a, b: o["b2"] * a + (1 - o["b2"]) * b * b, v, g)
    c1, c2 = 1 - o["b1"] ** step, 1 - o["b2"] ** step

    def upd(p, mm, vv):
        u = (mm / c1) / (jnp.sqrt(vv / c2) + o["eps"])
        decay = o["weight_decay"] if p.ndim >= 2 else 0.0
        return p - lr * (u + decay * p)

    return jax.tree.map(upd, params, m, v), m, v, g
