"""qwen3-moe-30b-a3b cut to 4 layers: the program's paged server, and its
plain reference.

``Program`` is the system under test: the benchmark's weights in the
layout of ``repro.models.lm``, served by ``repro.launch.serve.PagedServer``
as ``launch/serve.main`` builds it for a paged run.

``Reference`` is the plain reference: the decoder written here in
``jax.numpy`` from the configuration (RMSNorm, attention with per-head
RMSNorm of queries and keys and rotary embeddings, top-k routing with the
chosen probabilities renormalised, SiLU-gated experts, the head), importing
nothing of the program. It runs in float32 at the ``highest`` matmul
precision, or with every matmul operand in float8 (e4m3) as the control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts
from bench.peaks import least_time_s

#: Rows of tokens the reference pushes through attention, the experts and
#: the head at a time.
REF_ROWS = 256


# -- the weights, made by the benchmark from the seed ------------------------

def param_shapes(c: dict) -> dict:
    """The parameter tree of ``repro.models.lm`` for this decoder as
    (dtype, init, shape) leaves; the layers are stacked on a leading axis."""
    d, hd, v = c["hidden_size"], c["head_dim"], c["vocab_size"]
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    e, f, n = c["num_experts"], c["moe_intermediate_size"], \
        c["num_hidden_layers"]
    w = c["torch_dtype"]
    return {
        "embed": (w, "normal", (v, d)),
        "head": (w, "normal", (d, v)),
        "final_norm": {"scale": ("float32", "ones", (d,))},
        "layers": [{
            "ln1": {"scale": ("float32", "ones", (n, d))},
            "ln2": {"scale": ("float32", "ones", (n, d))},
            "mixer": {
                "wq": (w, "normal", (n, d, hq * hd)),
                "wk": (w, "normal", (n, d, hkv * hd)),
                "wv": (w, "normal", (n, d, hkv * hd)),
                "wo": (w, "normal", (n, hq * hd, d)),
                "q_norm": ("float32", "ones", (n, hd)),
                "k_norm": ("float32", "ones", (n, hd)),
            },
            "ffn": {
                "router": ("float32", "normal", (n, d, e)),
                "w_gate": (w, "normal", (n, e, d, f)),
                "w_up": (w, "normal", (n, e, d, f)),
                "w_down": (w, "normal", (n, e, f, d)),
            },
        }],
    }


def make_params(c: dict, key):
    """The weights on the default device, in one jitted call."""
    leaves, treedef = jax.tree.flatten(
        param_shapes(c),
        is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], str))

    def make(key):
        out = []
        for i, (dtype, init, shape) in enumerate(leaves):
            if init == "normal":
                x = 0.02 * jax.random.normal(jax.random.fold_in(key, i), shape,
                                             jnp.float32)
            else:
                x = jnp.ones(shape, jnp.float32)
            out.append(x.astype(dtype))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(make)(key)


# -- the program ---------------------------------------------------------------

class Program:
    """The timed path: ``PagedServer`` over the benchmark's weights."""

    def __init__(self, c: dict, traffic: dict):
        from repro.configs.base import ModelConfig, MoEConfig
        from repro.parallel.sharding import ParallelConfig

        self.c, self.traffic = c, traffic
        self.mcfg = ModelConfig(
            name=c["name"], family="moe",
            num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
            num_heads=c["num_attention_heads"],
            num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            d_ff=0, vocab_size=c["vocab_size"], act=c["hidden_act"],
            glu=True, norm_eps=c["rms_norm_eps"], rope_theta=c["rope_theta"],
            qk_norm=True, tie_embeddings=c["tie_word_embeddings"],
            moe=MoEConfig(num_experts=c["num_experts"],
                          top_k=c["num_experts_per_tok"],
                          d_ff=c["moe_intermediate_size"],
                          norm_topk=c["norm_topk_prob"]),
            dtype=c["torch_dtype"])
        self.pcfg = ParallelConfig(**c["serving"]["parallel"])

    def make_server(self, key, *, slots: int, max_tokens: int):
        """The server as ``launch/serve.main`` builds it for a paged run:
        each slot holds up to ``max_tokens``, and the shared pool half of
        the dense (slots, max_tokens) rectangle, the default of its
        ``--pages``."""
        from repro.launch import serve

        s = self.c["serving"]
        page = s["page_size"]
        pages = -(-max_tokens // page)
        return serve.PagedServer(
            self.mcfg, self.pcfg, None, num_slots=slots, page_size=page,
            num_pages=slots * pages // 2 + 1, max_pages_per_slot=pages,
            params=make_params(self.c, key), prefill_chunk=s["prefill_chunk"],
            kv_quant=None if s["kv_dtype"] == self.c["torch_dtype"]
            else s["kv_dtype"])

    # Shape facts for the per-layer metrics.

    def token_flops(self, context: int, head: bool) -> int:
        return counts.lm_token_flops(self.c, context, head=head)

    def expert_least_time_s(self, rows: int, device_kind: str) -> float:
        """Least time of one MoE layer's expert FFN over ``rows`` tokens."""
        c = self.c
        n = counts.expert_ffn(rows * c["num_experts_per_tok"],
                              c["hidden_size"], c["moe_intermediate_size"],
                              experts=c["num_experts"], glu=True, w_bytes=2,
                              a_bytes=2)
        return c["num_hidden_layers"] * least_time_s(n["flops"], n["bytes"],
                                                     device_kind)

    def decode_attention_least_time_s(self, contexts: list[int],
                                      device_kind: str) -> float:
        """Least time of one decode step's paged attention, each layer one
        call over every slot's ``contexts`` cached tokens."""
        c, flops, nbytes = self.c, 0, 0
        for ctx in contexts:
            n = counts.paged_decode_attention(
                ctx, heads=c["num_attention_heads"],
                kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                kv_bytes=2)
            flops, nbytes = flops + n["flops"], nbytes + n["bytes"]
        return c["num_hidden_layers"] * least_time_s(flops, nbytes,
                                                     device_kind)


# -- the plain reference -----------------------------------------------------------

def _fp8(x, axis):
    """``x`` rounded to float8 e4m3 with one scale per slice along
    ``axis`` (the largest magnitude maps to 448), back in float32."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                        1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, low: bool):
    """x @ w in float32; with ``low`` both operands first rounded to fp8
    (x per row, w per output column)."""
    if low:
        x, w = _fp8(x, -1), _fp8(w, -2)
    return x @ w


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = theta ** (-np.arange(half, dtype=np.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv[None]        # (T, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]  # (T, 1, half)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _layer(x, w, c: dict, low: bool):
    """One decoder layer over a whole sequence x (T, D), float32."""
    t = x.shape[0]
    hq, hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    eps = c["rms_norm_eps"]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    h = _rms(x, w["ln1"]["scale"], eps)
    m = w["mixer"]
    q = _mm(h, f32(m["wq"]), low).reshape(t, hq, hd)
    k = _mm(h, f32(m["wk"]), low).reshape(t, hkv, hd)
    v = _mm(h, f32(m["wv"]), low).reshape(t, hkv, hd)
    q = _rms(q, m["q_norm"], eps)
    k = _rms(k, m["k_norm"], eps)
    pos = jnp.arange(t)
    q, k = _rope(q, pos, c["rope_theta"]), _rope(k, pos, c["rope_theta"])
    rep = hq // hkv
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    blocks = t // REF_ROWS

    def attend(rows):
        qr, pr = rows
        scores = jnp.einsum("qhd,khd->hqk", qr, k) / np.sqrt(hd)
        scores = jnp.where(pr[None, :, None] >= pos[None, None, :], scores,
                           -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)

    attn = jax.lax.map(attend, (q.reshape(blocks, REF_ROWS, hq, hd),
                                pos.reshape(blocks, REF_ROWS)))
    x = x + _mm(attn.reshape(t, hq * hd), f32(m["wo"]), low)

    h = _rms(x, w["ln2"]["scale"], eps)
    ffn = w["ffn"]
    probs = jax.nn.softmax(h @ ffn["router"], -1)
    top, idx = jax.lax.top_k(probs, c["num_experts_per_tok"])
    if c["norm_topk_prob"]:
        top = top / top.sum(-1, keepdims=True)
    gates = jnp.einsum("tk,tke->te", top,
                       jax.nn.one_hot(idx, c["num_experts"]))
    wg, wu, wd = f32(ffn["w_gate"]), f32(ffn["w_up"]), f32(ffn["w_down"])
    if low:
        wg, wu, wd = _fp8(wg, -2), _fp8(wu, -2), _fp8(wd, -2)

    def experts(rows):
        hr, g = rows
        if low:
            hr = _fp8(hr, -1)
        a = jnp.einsum("td,edf->tef", hr, wg)
        u = jnp.einsum("td,edf->tef", hr, wu)
        a = jax.nn.silu(a) * u * g[:, :, None]
        if low:
            a = _fp8(a, -1)
        return jnp.einsum("tef,efd->td", a, wd)

    y = jax.lax.map(experts, (h.reshape(blocks, REF_ROWS, -1),
                              gates.reshape(blocks, REF_ROWS, -1)))
    return x + y.reshape(t, -1)


def _by_rows(fn, *rows):
    """``fn`` over blocks of ``REF_ROWS`` rows of each of ``rows``, joined."""
    t = rows[0].shape[0]
    out = jax.lax.map(lambda r: fn(*r), tuple(
        a.reshape(t // REF_ROWS, REF_ROWS, *a.shape[1:]) for a in rows))
    return out.reshape(t, *out.shape[2:])


def _gap(hidden, head, served):
    """Per position: the reference's best logit less its logit of the
    token ``served`` there."""
    head = head.astype(jnp.float32)

    def gap(h, s):
        ref = h @ head
        return ref.max(-1) - jnp.take_along_axis(ref, s[:, None], -1)[:, 0]

    return _by_rows(gap, hidden, served)


def _low_first(hidden_low, head):
    """Per position: the token that the fp8 control puts first."""
    head = _fp8(head.astype(jnp.float32), -2)
    return _by_rows(lambda h: (_fp8(h, -1) @ head).argmax(-1), hidden_low)


class Reference:
    """The plain reference over whole sequences, padded to ``pad_to``
    tokens (a multiple of ``REF_ROWS``), one layer at a time with each
    layer's weights upcast from the served type."""

    def __init__(self, c: dict, pad_to: int):
        self.c = c
        self.pad_to = -(-pad_to // REF_ROWS) * REF_ROWS
        self._layer = {low: jax.jit(lambda x, w, low=low: _layer(x, w, c, low))
                       for low in (False, True)}
        self._gap = jax.jit(_gap)
        self._low_first = jax.jit(_low_first)

    def hidden(self, params, tokens, *, low: bool = False):
        """Final normed hidden states (T, D) of one sequence (T,)."""
        c = self.c
        with jax.default_matmul_precision("highest"):
            x = params["embed"][tokens].astype(jnp.float32)
            stacked = params["layers"][0]
            for i in range(c["num_hidden_layers"]):
                x = self._layer[low](x, jax.tree.map(lambda a: a[i], stacked))
            return _rms(x, params["final_norm"]["scale"], c["rms_norm_eps"])

    def served_gaps(self, params, prompt: np.ndarray, out: list[int], *,
                    control: bool = False) -> np.ndarray:
        """At every served position of one request, the gap by which the
        served token's reference logit lies below the reference's best.
        With ``control``, the same for the tokens that the fp8 control puts
        first at those positions instead of ``out``."""
        seq = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
        tokens = np.zeros((self.pad_to,), np.int32)
        tokens[:len(seq)] = seq
        first = len(prompt) - 1                  # predicts out[0]
        served = np.zeros((self.pad_to,), np.int32)
        served[first:first + len(out)] = out
        with jax.default_matmul_precision("highest"):
            hid = self.hidden(params, jnp.asarray(tokens))
            if control:
                low = self.hidden(params, jnp.asarray(tokens), low=True)
                served = self._low_first(low, params["head"])
            gap = self._gap(hid, params["head"], jnp.asarray(served))
        return np.asarray(gap, np.float64)[first:first + len(out)]
