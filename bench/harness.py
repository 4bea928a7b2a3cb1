"""Shared plumbing of the benchmark: finding a cell's files by name, the
device check, the compile cache, and the result line.

Layout under ``bench/`` (everything is found by the names in
``BENCHMARK.json``, so a new cell, configuration, traffic mix or metric is a
new file and no edit):

  configs/<config>.json   sizes as run; ``kind`` names the driver
  traffic/<mix>.json      parameters of the traffic mix
  drivers/<kind>.py       set-up, measured window and correctness check
  metrics/<metric>.py     ``read(ctx)`` -> number or None, from the reduced
                          trace and the run's counts
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def find(items: list, name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json")


def load_module(path: pathlib.Path):
    """Import a file by path; metric files carry dots in their names, so
    they are loaded this way rather than by package import."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def resolve(spec: dict, workload: str) -> dict:
    """Everything one cell needs, by name: its entry, configuration, traffic,
    driver module path and the metric reader paths of its trace run."""
    cell = find(spec["workloads"], workload, "workload")
    cfg_entry = find(spec["configs"], cell["config"], "config")
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    driver = BENCH / "drivers" / f"{config['kind']}.py"

    def mine(m):
        return workload in m.get("workloads", [workload])

    e2e = [m for m in spec["end_to_end"] if mine(m)]
    per_layer = [m for m in spec["per_layer"] if mine(m)]
    return {"cell": cell, "config": config, "traffic": traffic,
            "driver": driver, "end_to_end": e2e, "per_layer": per_layer,
            "readers": {m["name"]: BENCH / "metrics" / f"{m['name']}.py"
                        for m in per_layer}}


def sub_seed(seed: int, *path: int) -> int:
    """A program seed below 2**31 derived from the run seed (any size)."""
    import numpy as np
    ss = np.random.SeedSequence([seed % (1 << 63), *path])
    return int(ss.generate_state(1, np.uint32)[0] >> 1)


def use_src() -> None:
    """Make the program (``src/``) importable."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def require_tpu(chips: int):
    """The chips this cell runs on; exits non-zero without them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.stderr.write(f"no TPU: JAX found {devs[0].platform} devices only\n")
        raise SystemExit(3)
    if len(devs) < chips:
        sys.stderr.write(f"cell needs {chips} chips, JAX found {len(devs)}\n")
        raise SystemExit(3)
    return devs[:chips]


def enable_cache() -> str:
    """The program's persistent compile cache (``.jax_cache/`` in the
    checkout unless ``JAX_COMPILATION_CACHE_DIR`` is set), with every
    compile written to it, the sub-second eager ones included."""
    import jax
    use_src()
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def device_info(devs) -> dict:
    stats = [d.memory_stats() or {} for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0))
                                     for s in stats)}


def print_result(correct: bool, attempted: int, failed: int, metrics: dict,
                 device: dict, checks: list, breakdown=None) -> None:
    """Checks go last on stderr and last in the result line; the result is
    the last line of stdout."""
    for name, value, limit in checks:
        sys.stderr.write(f"check {name} = {value!r} limit {limit!r}\n")
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {n: {"value": v, "limit": l} for n, v, l in checks}
    sys.stderr.flush()
    sys.stdout.write(json.dumps(out) + "\n")
    sys.stdout.flush()
