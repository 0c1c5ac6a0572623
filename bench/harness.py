"""What every cell shares: finding its files by name, the JAX set-up, the
device check, the per-layer metric readers and the result line."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from typing import Any

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: JAX's persistent compilation cache: a fixed path inside the checkout.
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json has no {what} named {name!r}")


def load_module(path: str, name: str):
    """Import the Python file ``path`` (names may hold ``-`` and ``.``)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + name.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_driver(kind: str):
    """The module ``drivers/<kind>.py`` that generates a traffic mix of this
    ``kind`` from the seed and runs its cell: ``run(...)``, and
    ``readings(...)`` for ``calibrate.py``."""
    if not kind.isidentifier():
        raise ValueError(f"traffic kind {kind!r} is not a module name")
    if not os.path.exists(os.path.join(HERE, "drivers", f"{kind}.py")):
        raise ValueError(f"no driver for traffic kind {kind!r} in "
                         f"bench/drivers/")
    return importlib.import_module(f"bench.drivers.{kind}")


def load_config(entry: dict, *, rehearsal: bool = False):
    """(sizes, module) of a configuration: its JSON file under ``paths``
    and the Python file of the same name beside it, which holds the
    program's entry and the plain reference."""
    from bench.generator import merge

    path = os.path.join(ROOT, entry["file"])
    with open(path) as f:
        cfg = json.load(f)
    over = cfg.pop("rehearsal", {})
    if rehearsal:
        cfg = merge(cfg, over)
    mod = load_module(os.path.splitext(path)[0] + ".py", entry["name"])
    return cfg, mod


def load_limits(cell: str, *, rehearsal: bool = False) -> dict:
    """The limit of each number that decides a cell's ``correct``:
    ``limits/<cell>.json``, with its ``rehearsal`` keys for a rehearsal."""
    from bench.generator import merge

    with open(os.path.join(HERE, "limits", f"{cell}.json")) as f:
        limits = json.load(f)
    over = limits.pop("rehearsal", {})
    return merge(limits, over) if rehearsal else limits


def setup_jax():
    """Point JAX's persistent compilation cache at its fixed directory inside
    the checkout, whatever the environment names, and keep every program
    there with no size cap (an evicted step would compile again in the
    next run), so that only a cell's first run compiles."""
    import jax

    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def devices_for(chips: int, *, rehearsal: bool):
    """The first ``chips`` TPU devices. Exits when JAX finds no TPU or too
    few: a CPU number is never reported as a device result. A rehearsal
    takes the CPU devices instead."""
    import jax

    devs = jax.devices()
    if rehearsal:
        if devs[0].platform != "cpu":
            sys.exit("bench: a rehearsal runs on the CPU only")
        if len(devs) < chips:
            sys.exit(f"bench: rehearsal needs {chips} CPU devices; set "
                     f"XLA_FLAGS=--xla_force_host_platform_device_count="
                     f"{chips}")
        return devs[:chips]
    if devs[0].platform != "tpu" or len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} TPU chip(s); JAX found "
                 f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def device_record(devs) -> dict:
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader may read."""
    cfg: dict                     # the configuration's sizes
    traffic: dict                 # the traffic mix
    program: Any                  # the configuration module's program
    kind: str                     # "train" or "serve"
    device_kind: str
    chips: int
    records: dict                 # the run's own counts and times
    trace: Any = None             # tracing.Trace of the traced window


def read_metrics(names: list[str], ctx: Context) -> dict:
    """Run the reader ``bench/metrics/<name>.py`` of each metric; a reader
    that finds nothing to read returns None, and its metric is left out."""
    out = {}
    for name in names:
        mod = load_module(os.path.join(HERE, "metrics", f"{name}.py"), name)
        value = mod.read(ctx)
        if value is not None:
            out[name] = {"value": float(value), "unit": mod.UNIT}
    return out


def per_layer_names(bench: dict, cell: str, moves: set) -> list[str]:
    """The per-layer metrics a cell reports: those that list it, or that
    list no cells and move an end-to-end metric the cell reports."""
    return [m["name"] for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in moves)]


def end_to_end_names(bench: dict, cell: str) -> list[str]:
    return [m["name"] for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])]


@dataclasses.dataclass
class Check:
    """One number of the correctness comparison and its limit; it passes
    when ``value <= limit``."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


def emit(result: dict, checks: list[Check]) -> None:
    """Print the checks as the last lines on stderr, then the result line
    (with the checks as its last key) as the last line on stdout."""
    result = dict(result)
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    for c in checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)
