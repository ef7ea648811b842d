"""What the benchmark's parts share: locating files by name, the run's
context, seeds, the program's configuration, and host annotations."""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
from typing import Any, Callable, Dict, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE = os.path.join(BENCH, ".cache")


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def load_module(path: str, name: str):
    """Import a file by path (metric files carry dots in their names)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """A workload of BENCHMARK.json with its configuration, traffic and
    driver resolved from the files they name."""
    name: str
    chips: int
    config: dict
    traffic: dict
    driver: Any
    end_to_end: List[dict]
    per_layer: List[dict]
    root: str = ROOT


def _applies(metric: dict, name: str, reported: List[str]) -> bool:
    """A metric with ``workloads`` belongs to the cells it lists; one
    without, to every cell, or for a per-layer metric to every cell that
    reports the end-to-end metric it ``moves``."""
    if "workloads" in metric:
        return name in metric["workloads"]
    return metric.get("moves") in reported if "moves" in metric else True


def resolve(workload: str, root: str = ROOT) -> Cell:
    bm = benchmark(root)
    wl = {w["name"]: w for w in bm["workloads"]}[workload]
    conf = {c["name"]: c for c in bm["configs"]}[wl["config"]]
    bench = os.path.join(root, "bench")
    config = load_json(os.path.join(root, conf["file"]))
    traffic = load_json(os.path.join(bench, "traffic",
                                     wl["traffic"] + ".json"))
    driver = load_module(os.path.join(bench, "drivers",
                                      traffic["driver"] + ".py"),
                         "driver_" + traffic["driver"])
    e2e = [m for m in bm["end_to_end"] if _applies(m, workload, [])]
    names = [m["name"] for m in e2e]
    layer = [m for m in bm["per_layer"] if _applies(m, workload, names)]
    return Cell(workload, int(wl["chips"]), config, traffic, driver, e2e,
                layer, root)


def metric_reader(name: str, root: str = ROOT) -> Callable:
    return load_module(os.path.join(root, "bench", "metrics", name + ".py"),
                       "metric_" + name.replace(".", "_")).read


@dataclasses.dataclass
class Context:
    """What a driver's session sees: the cell's files and the seed, the
    host annotation to wrap phases in, and the window's step count once
    known. ``control`` names a precision the configuration states
    (a key of its ``precision``) to run one step below: ``"matmul"`` runs
    the program's own lower matmul path (``highest`` -> ``high``);
    ``"control"`` puts the float32 reference of the control plane in the
    program's place for ``check``, where float64 is stated."""
    config: dict
    traffic: dict
    seed: int
    annotate: Callable = lambda name: contextlib.nullcontext()
    control: Optional[str] = None
    n_steps: int = 0
    marks: List[tuple] = dataclasses.field(default_factory=list)

    def mark(self, name: str) -> None:
        """Note the end of a set-up phase (printed to standard error)."""
        import time
        self.marks.append((name, time.time()))


def seeds(seed: int, n: int) -> List[int]:
    """``n`` independent 32-bit seeds from any non-negative ``--seed``."""
    ss = np.random.SeedSequence(int(seed))
    return [int(s) for s in ss.generate_state(n)]


def feel_config(cfg: dict):
    from repro.configs.base import FeelConfig
    kw = dict(cfg["feel"])
    kw["gamma"] = tuple(kw["gamma"])
    return FeelConfig(**kw)


def init_mlp(seed: int, layers: List[int]) -> Dict[str, Any]:
    """The initial model on the device, from the seed, in one jitted call:
    each weight truncated-normal in [-3, 3] over sqrt(fan-in), biases 0,
    float32 (the program's MLP initialiser's distribution)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        out = {}
        keys = jax.random.split(key, len(layers) - 1)
        for i, (a, b) in enumerate(zip(layers[:-1], layers[1:])):
            w = jax.random.truncated_normal(keys[i], -3.0, 3.0, (a, b),
                                            jnp.float32)
            out[f"w{i + 1}"] = w / np.float32(np.sqrt(a))
            out[f"b{i + 1}"] = jnp.zeros((b,), jnp.float32)
        return out

    return make(jax.random.key(seed))


def trace_annotation(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)
