"""Device time by layer scope: the scopes in the compiled Swin-MoE train
step, the scopes the metrics declare, the split of a hand-built trace, a
trace that holds an op the step's module lacks, a recorded v5e HLO snippet,
and a trace with no scope map."""
import json
import os
import re
import sys
import types

import pytest

from bench import generator, harness, scopes, tracing

sys.path.insert(0, os.path.join(harness.ROOT, "src"))

DATA = os.path.join(os.path.dirname(__file__), "data")
TPU0, TPU1 = "/device:TPU:0", "/device:TPU:1"

CELL = "swin_train"
METRICS = ("attention_ms.train", "layernorm_ms.train", "dense_mlp_ms.train",
           "optimizer_ms.train", "moe_dispatch_ms.train",
           "expert_ffn_ms.train", "unattributed_ms.train")
#: The layer scopes the seven metrics read, in ``per_layer``'s order.
SCOPES = ("swin.attention", "swin.norm", "swin.mlp", "optim.adamw",
          "moe.route", "moe.combine", "moe.experts")
#: The tokens of ``moe_kernel_roofline.train``'s kernel pattern.
KERNEL_TOKENS = ("esffn", "esmm", "esfk", "ess", "estmm")


def ev(plane, name, start, end):
    return (plane, tracing.OPS_LINE, name, start, end - start)


def host(name, start, end):
    return ("/host:CPU", tracing.HOST_LINE, name, start, end - start)


def rehearsal_ctx(trace=None, traced_steps=1):
    bench = harness.load_benchmark()
    cell = harness.find(bench["workloads"], CELL, "workload")
    cfg, mod = harness.load_config(
        harness.find(bench["configs"], cell["config"], "config"),
        rehearsal=True)
    traffic = generator.load(cell["traffic"], rehearsal=True)
    return harness.Context(
        cfg=cfg, traffic=traffic, program=mod.Program(cfg, traffic),
        kind="train", device_kind="cpu", chips=1,
        records={"traced_steps": traced_steps}, trace=trace)


@pytest.fixture(scope="module")
def step_text():
    """The optimized HLO of the cell's step at its rehearsal sizes."""
    return scopes.step_hlo(rehearsal_ctx())


@pytest.fixture(scope="module")
def step_paths(step_text):
    return scopes.hlo_op_names(step_text)


def test_layer_scopes_are_the_metrics_scopes():
    assert scopes.layer_scopes() == SCOPES


def test_a_new_metric_file_adds_its_scope(monkeypatch):
    """A scope comes with its metric: a new per-layer entry whose reader
    declares ``SCOPES`` takes its ops from the enclosing scope, and no list
    in ``bench/scopes.py`` changes."""
    bench = harness.load_benchmark()
    bench["per_layer"].append({"name": "head_ms.train"})
    load = harness.load_module
    monkeypatch.setattr(harness, "load_benchmark", lambda: bench)
    monkeypatch.setattr(
        harness, "load_module",
        lambda path, name: (types.SimpleNamespace(SCOPES=("swin.head",))
                            if name == "head_ms.train" else load(path, name)))
    scopes.layer_scopes.cache_clear()
    try:
        assert scopes.layer_scopes() == SCOPES + ("swin.head",)
        assert scopes.layer_of("jit(train)/jvp(swin.attention)/swin.head/mul",
                               scopes.layer_scopes()) == "swin.head"
    finally:
        scopes.layer_scopes.cache_clear()


@pytest.mark.parametrize("scope", SCOPES)
def test_compiled_step_carries_each_scope(step_paths, scope):
    """Each scope names ops of the compiled step; the model's scopes in
    the forward (``jvp(``) and the backward (``transpose(``) pass, the
    optimizer's once, outside both."""
    paths = [p for p in step_paths.values()
             if scopes.layer_of(p, SCOPES) == scope]
    assert paths
    if scope == "optim.adamw":
        assert not any("jvp(" in p or "transpose(" in p for p in paths)
    else:
        assert any("jvp(" in p and "transpose(" not in p for p in paths)
        assert any("transpose(" in p for p in paths)


def test_scope_names_avoid_the_kernel_pattern():
    """No scope adds a kernel token to an op's name, so the roofline's
    pattern matches the same ops with the scopes as without."""
    for scope in scopes.layer_scopes():
        assert not any(t in scope for t in KERNEL_TOKENS), scope


def test_every_metric_reads_the_split(monkeypatch, step_text, step_paths):
    """On a trace of the compiled step's own op names, the seven metrics
    split the device time of its ops, and each scoped one reads some."""
    names = sorted(step_paths)
    events = [host(tracing.WINDOW_SPAN, 0, 10 * len(names))]
    events += [ev(TPU0, n, 10 * i, 10 * i + 10) for i, n in
               enumerate(names)]
    monkeypatch.setattr(scopes, "step_hlo", lambda ctx: step_text)
    ctx = rehearsal_ctx(tracing.Trace(events), traced_steps=2)
    out = harness.read_metrics(list(METRICS), ctx)
    assert set(out) == set(METRICS)
    assert all(m["unit"] == "ms" and m["value"] > 0 for m in out.values())
    total = sum(m["value"] for m in out.values())
    assert total == pytest.approx(10 * len(names) / 2 / 1e6)


@pytest.mark.parametrize("path,layer", [
    ("jit(train)/transpose(jvp(swin.attention))/mul", "swin.attention"),
    ("jit(train)/jvp(moe.experts)/jit(esffn_mlp_pallas)", "moe.experts"),
    ("jit(train)/jvp(swin.attention)/swin.norm/add", "swin.norm"),
    ("jit(train)/transpose(jvp(swin.attention/swin.norm))/mul", "swin.norm"),
    ("jit(train)/jvp(swin.attentions)/mul", None),
    ("jit(train)/jvp()/conv_general_dilated", None),
    ("", None),
])
def test_layer_of_takes_the_innermost_whole_segment(path, layer):
    assert scopes.layer_of(path, SCOPES) == layer


@pytest.fixture
def scoped_trace():
    # Device 0 in a window [0, 100): an attention fusion [0, 10); a
    # fusion [10, 30) whose root is a LayerNorm's op inside the attention
    # (the innermost scope wins); an optimizer fusion [30, 50); an expert
    # kernel [50, 70); an unscoped convolution [70, 80); a copy with no
    # op_name metadata [80, 90); a fusion [95, 110) that runs past the
    # window. A fusion is one op and names one path, its root's, so it
    # counts once. Device 1 runs one attention op throughout.
    names = {
        "fusion.1": "jit(train)/jvp(swin.attention)/add",
        "fusion.2": "jit(train)/transpose(jvp(swin.attention/swin.norm))/mul",
        "fusion.3": "jit(train)/optim.adamw/sub",
        "transpose_jvp_moe_experts_jit_esfk_pallas___.4":
            "jit(train)/transpose(jvp(moe.experts))/jit(esfk_pallas)",
        "convolution.5": "jit(train)/jvp()/conv_general_dilated",
        "copy.6": "",
        "fusion.9": "jit(train)/jvp(moe.route)/reduce_max",
    }
    events = [
        host(tracing.WINDOW_SPAN, 0, 100),
        ev(TPU0, "fusion.1", 0, 10),
        ev(TPU0, "fusion.2", 10, 30),
        ev(TPU0, "fusion.3", 30, 50),
        ev(TPU0, "transpose_jvp_moe_experts_jit_esfk_pallas___.4", 50, 70),
        ev(TPU0, "convolution.5", 70, 80),
        ev(TPU0, "copy.6", 80, 90),
        ev(TPU0, "fusion.9", 95, 110),
        ev(TPU1, "fusion.1", 0, 100),
    ]
    return tracing.Trace(events), names


def test_split_of_a_hand_built_trace(scoped_trace):
    trace, names = scoped_trace
    assert scopes.layer_ns(trace.ops[0], names, trace.window, SCOPES) == {
        "swin.attention": 10, "swin.norm": 20, "optim.adamw": 20,
        "moe.experts": 20, None: 20, "moe.route": 5}
    split = scopes.split_s(trace, names, SCOPES)
    assert split["swin.attention"] == pytest.approx((10 + 100) / 2 * 1e-9)
    assert split[None] == pytest.approx(20 / 2 * 1e-9)
    # The split's parts sum to the busy time: no op counts twice.
    assert sum(split.values()) == pytest.approx(trace.busy_s())


def test_layer_ms_per_traced_step(monkeypatch, scoped_trace):
    trace, names = scoped_trace
    text = "\n".join(f'  %{n} = f32[8]{{0}} fusion(%p), kind=kLoop'
                     + (f', metadata={{op_name="{p}"}}' if p else "")
                     for n, p in names.items())
    monkeypatch.setattr(scopes, "step_hlo", lambda ctx: text)
    ctx = harness.Context(cfg={}, traffic={}, program=None, kind="train",
                          device_kind="TPU v5 lite", chips=2,
                          records={"traced_steps": 5}, trace=trace)
    assert scopes.layer_ms(ctx, "moe.route", "moe.experts") == \
        pytest.approx(25 / 2 * 1e-6 / 5)
    assert scopes.layer_ms(ctx, None) == pytest.approx(10e-6 / 5)
    for kind, tr in (("serve", trace), ("train", None)):
        other = harness.Context(cfg={}, traffic={}, program=None, kind=kind,
                                device_kind="TPU v5 lite", chips=1,
                                records={"traced_steps": 5}, trace=tr)
        assert scopes.layer_ms(other, "swin.norm") is None


def test_an_op_the_module_lacks_leaves_no_split(monkeypatch, scoped_trace):
    """A traced op that is no instruction of the re-lowered module (here
    the expert kernel under another name) means the module is not the one
    that ran: no split, and every metric is left out of the line."""
    trace, names = scoped_trace
    planted = {("fusion.10" if n == "fusion.1" else n): p
               for n, p in names.items()}
    assert scopes.split_s(trace, planted, SCOPES) is None
    text = "\n".join(f'  %{n} = f32[8]{{0}} fusion(%p), kind=kLoop'
                     + (f', metadata={{op_name="{p}"}}' if p else "")
                     for n, p in planted.items())
    monkeypatch.setattr(scopes, "step_hlo", lambda ctx: text)
    ctx = harness.Context(cfg={}, traffic={}, program=None, kind="train",
                          device_kind="TPU v5 lite", chips=2,
                          records={"traced_steps": 5}, trace=trace)
    assert harness.read_metrics(list(METRICS), ctx) == {}
    # An op outside the window does not count.
    late = trace.ops[0] + [("fusion.11", 200, 210)]
    outside = types.SimpleNamespace(ops={0: late}, window=trace.window)
    assert scopes.split_s(outside, names, SCOPES) is not None


def test_recorded_v5e_hlo_snippet():
    """Entry instructions of the Swin-MoE-Small step's optimized HLO as
    the TPU compiler wrote it for a v5e: a fusion of each scope forward
    and backward, the three Pallas kernels, an async copy and a parameter.
    Every instruction is in the map, with its ``op_name`` path or, where it
    has none, an empty one; the kernels are the expert FFN's, and the roofline's pattern
    finds them and nothing else."""
    with open(os.path.join(DATA, "swin_step_hlo_v5e.txt")) as f:
        text = f.read()
    names = scopes.hlo_op_names(text)
    lines = {m.group(1): line for line in text.splitlines()
             for m in [re.match(r"^\s*(?:ROOT )?%([^\s=]+) = ", line)] if m}
    assert len(lines) == 20
    assert set(names) == set(lines)
    assert {n for n, p in names.items() if p} == {
        n for n, line in lines.items() if "metadata={op_name=" in line}
    layers = {scopes.layer_of(p, SCOPES) for p in names.values()}
    assert layers == set(SCOPES) | {None}
    roofline = harness.load_module(
        os.path.join(harness.HERE, "metrics", "moe_kernel_roofline.train.py"),
        "moe_kernel_roofline.train")
    kernels = sorted(n for n in lines if roofline.PATTERN.search(n))
    assert kernels == ["esffn_mlp_pallas.10", "esfk_pallas.3",
                       "esmm_pallas.58"]
    assert all(scopes.layer_of(names[n], SCOPES) == "moe.experts"
               for n in kernels)
    assert names["copy-start.1285"] == ""


def test_recorded_decode_tick_without_a_scope_map():
    """The recorded decode tick reads as before, and with no scope map,
    one that names no scope, or one that lacks the tick's ops, there is no
    split."""
    with open(os.path.join(DATA, "decode_tick_trace.json")) as f:
        events = [tuple(e) for e in json.load(f)]
    tr = tracing.Trace(events)
    ops = tr.ops[0]
    assert tracing.busy_ns(ops, tr.window) == 84_597_134
    expert = re.compile(r"(?:^|_)esffn\w*_pallas")
    assert tracing.kernel_ns(ops, expert, tr.window) == sum(
        e - s for n, s, e in ops if n.startswith("esffn_glu_pallas"))
    gaps = tracing.idle_gaps(ops, tr.spans, tr.window)
    assert gaps[0][0] == "bench.tick"
    assert gaps[0][1] == pytest.approx(tr.window_s - 84_597_134 / 1e9)
    assert scopes.split_s(tr, {}, SCOPES) is None
    assert scopes.split_s(tr, {n: "jit(step)/jvp()/add"
                               for n, *_ in ops}, SCOPES) is None
    assert scopes.split_s(tr, {"fusion.1": "jit(step)/jvp(swin.norm)/add"},
                          SCOPES) is None
    lo, hi = tr.window
    assert scopes.layer_ns(ops, {}, tr.window, SCOPES) == {
        None: sum(min(e, hi) - max(s, lo) for _, s, e in ops
                  if min(e, hi) > max(s, lo))}
