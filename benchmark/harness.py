"""What every driver shares: the benchmark's files, the look for the chip,
the compile cache, the per-layer readers and the result line."""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import re
import sys
from typing import Any, Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NotMeasurable(SystemExit):
    """The run cannot be a measurement (no chip, too few chips, a device
    without published peaks).  Exits non-zero and prints no result."""

    def __init__(self, why: str):
        print(f"benchmark: not measurable: {why}", file=sys.stderr)
        super().__init__(3)


def require_program() -> None:
    """The system under test is the checkout's own ``tfmesos_tpu``: a
    directory that holds only the benchmark measures nothing, and a copy
    installed elsewhere is not the commit being measured."""
    found = importlib.util.find_spec("tfmesos_tpu")
    origin = getattr(found, "origin", None)
    if not origin or not os.path.realpath(origin).startswith(
            os.path.realpath(ROOT) + os.sep):
        raise NotMeasurable(f"the program (tfmesos_tpu) is not in this "
                            f"checkout ({ROOT}); found {origin or 'none'}")


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_spec() -> Dict[str, Any]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(spec: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json "
                     f"(known: {[w['name'] for w in spec['workloads']]})")


def load_model(config: Dict[str, Any]):
    """The configuration's model adapter, ``models/<model>.py``: the module
    that knows the block (README.md lists its functions).  A configuration
    names it with ``"model"``; there is no default."""
    name = config.get("model")
    if not name:
        raise SystemExit("benchmark: the configuration names no \"model\" "
                         "(the adapter under benchmark/models/)")
    if not os.path.exists(os.path.join(HERE, "models", name + ".py")):
        raise SystemExit(f"benchmark: no model adapter {name!r} under "
                         f"{os.path.join(HERE, 'models')}")
    return importlib.import_module("benchmark.models." + name)


def load_cell(workload: str):
    """Everything one cell is made of, found by name: ``(spec, cell,
    config, traffic, driver module)``.  The driver finds the
    configuration's model adapter the same way (``load_model``), before it
    looks for the chip."""
    from benchmark import traffic_gen
    require_program()
    spec = load_spec()
    cell = find_cell(spec, workload)
    entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    traffic = traffic_gen.load_traffic(cell["traffic"])
    driver = importlib.import_module("benchmark.drivers." + config["driver"])
    return spec, cell, config, traffic, driver


def cell_metrics(spec: Dict[str, Any], cell: str, group: str) -> List[dict]:
    """The metrics of ``group`` (``end_to_end`` / ``per_layer``) that
    ``cell`` reports.  An end-to-end metric without ``workloads`` is for
    every cell; a per-layer metric without it is for every cell that
    reports the end-to-end metric it moves."""
    e2e = [m for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if group == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def enable_compile_cache() -> str:
    """JAX's persistent cache: where ``JAX_COMPILATION_CACHE_DIR`` says, or
    at a fixed path inside the checkout (the path is part of the key).
    The program's own ``enable_compile_cache`` makes the same choice, so a
    task the benchmark launches lands in the same directory."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def accelerator(chips: int, require_chip: bool = True) -> Dict[str, Any]:
    """The device as JAX reports it, with its published peaks.  Without a
    TPU, with fewer chips than the cell asks for, or on a device kind that
    ``peaks.json`` does not list, the run is not a measurement."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    peaks = load_json("peaks.json")["peaks"]
    if require_chip:
        if dev.platform != "tpu":
            raise NotMeasurable(f"JAX came up on {dev.platform!r}, not a TPU")
        if len(devs) < chips:
            raise NotMeasurable(f"the cell asks for {chips} chip(s), JAX "
                                f"sees {len(devs)}")
        if dev.device_kind not in peaks:
            raise NotMeasurable(f"no published peaks for device kind "
                                f"{dev.device_kind!r} in peaks.json")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs), "peaks": peaks.get(dev.device_kind)}


def memory_peak_bytes() -> int:
    """Peak on the fullest chip, where the backend reports it."""
    import jax
    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def load_reader(metric: str) -> Callable[[Dict[str, Any]], Optional[float]]:
    """``layer_metrics/<metric>.py`` defines ``read(run) -> number | None``.
    A metric split by cell with a suffix (``prefill_p50_ms.docqa``) is its
    base quantity, as for the end-to-end metrics: where it has no file of
    its own, the base name's reader reads it.  A reader that finds nothing
    to read returns None and the metric is left out of the line."""
    for name in (metric, metric.split(".")[0]):
        path = os.path.join(HERE, "layer_metrics", name + ".py")
        if os.path.exists(path):
            break
    else:
        raise SystemExit(f"benchmark: no reader for {metric!r} under "
                         f"{os.path.join(HERE, 'layer_metrics')}")
    spec = importlib.util.spec_from_file_location(
        "benchmark_layer_metric_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def per_layer(spec: Dict[str, Any], cell: str, run: Dict[str, Any]
              ) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in cell_metrics(spec, cell, "per_layer"):
        value = load_reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: Dict[str, Dict[str, Any]], device: Dict[str, Any],
                breakdown: Optional[Dict[str, Any]] = None,
                compared: Optional[Dict[str, Dict[str, float]]] = None
                ) -> str:
    """The result: the keys the driver reads, then ``compared``, the
    numbers ``correct`` was decided from, each with its limit, last."""
    line: Dict[str, Any] = {
        "correct": bool(correct), "attempted": int(attempted),
        "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    if compared is not None:
        line["compared"] = compared
    return json.dumps(line)


def compared_lines(compared: Dict[str, Dict[str, float]]) -> List[str]:
    return [f"correct: {k} = {v['value']:.6g} (limit {v['limit']})"
            for k, v in compared.items()]
