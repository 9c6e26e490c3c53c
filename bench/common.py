"""What every cell shares: finding its files by name, the chip check, the
compile cache, the compile counter and the result line."""
from __future__ import annotations

import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
#: Fixed, inside the checkout: the persistent compile cache and traces.
CACHE = os.path.join(ROOT, ".bench_cache")


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def resolve(workload: str) -> tuple[dict, dict, dict, dict, dict]:
    """(benchmark, cell, config, traffic, limits) of a cell, by name."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(ROOT, conf["file"])
    traffic = load_json(BENCH, "traffic", cell["traffic"] + ".json")
    limits = load_json(BENCH, "limits", workload + ".json")
    return bench, cell, config, traffic, limits


def require_chips(n: int):
    """The accelerator devices, or exit non-zero without a result."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n:
        print(f"bench: the cell needs {n} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        raise SystemExit(1)
    return devices


def setup_compile_cache() -> str:
    import jax
    path = os.path.join(CACHE, "jax")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts traces and backend compiles while ``armed``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if self.armed and event in self.EVENTS:
            self.count += 1


def load_reader(name: str):
    """The per-layer metric reader ``bench/metrics/<name>.py``."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def applies(metric: dict, cell: str, bench: dict) -> bool:
    """Whether ``cell`` reports ``metric``: its ``workloads`` list, or for a
    per-layer metric without one, every cell that reports what it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    moves = metric.get("moves")
    if moves is None:
        return True
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    return applies(e2e[moves], cell, bench)
