"""BENCHMARK.json and the files it names.  A cell is found by its name; its
configuration by the cell's ``config`` in ``configs``; its mix as
``traffic/<traffic>.json`` beside this file's directory entry in ``paths``;
a per-layer metric's reader as ``metrics/<name>.py``."""

from __future__ import annotations

import json
import os


def load(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        bench = json.load(f)
    bench["root"] = os.path.dirname(os.path.abspath(path))
    bench["home"] = os.path.join(bench["root"], bench["paths"][0])
    return bench


def cell(bench: dict, name: str):
    for c in bench["workloads"]:
        if c["name"] == name:
            break
    else:
        raise SystemExit(f"perfbench: no cell {name!r} in BENCHMARK.json")
    for cfg in bench["configs"]:
        if cfg["name"] == c["config"]:
            break
    else:
        raise SystemExit(f"perfbench: cell {name!r} names no configuration")
    with open(os.path.join(bench["root"], cfg["file"]), encoding="utf-8") as f:
        config = json.load(f)
    from . import traffic

    mix = traffic.load(os.path.join(bench["home"], "traffic", c["traffic"] + ".json"))
    return c, config, mix


def load_file_module(path: str, label: str):
    """A Python file found by name (a metric's reader, a configuration's
    reference), as a module of its own."""
    import importlib.util

    if not os.path.exists(path):
        raise SystemExit(f"perfbench: no file {path}")
    sp = importlib.util.spec_from_file_location(label, path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod


def metrics_for(bench: dict, group: str, cell_name: str) -> list[dict]:
    """The cell's metrics of ``end_to_end`` or ``per_layer``: those that
    list it, and those that list no cells at all."""
    return [m for m in bench[group] if "workloads" not in m or cell_name in m["workloads"]]


def shrink(config: dict, mix: dict) -> None:
    """Toy sizes for a CPU rehearsal (tests).  The shapes stay."""
    config["cluster"]["nodes"] = 600
    config["serve"]["batch_size"] = 64
    config["serve"]["chunk_size"] = 8
    config["initial_pods"] = 40
    config["measure_pods"] = 150
    mix["warmup"]["short_pods"] = 20
    if mix["loop"] == "open":
        mix["warmup"]["arrivals_s"] = 1.0
        for seg in mix.get("segments", ()):
            seg["rate_pods_per_s"] = seg["rate_pods_per_s"] * 60.0 / mix["rate_pods_per_s"]
        mix["rate_pods_per_s"] = 60.0
    else:
        mix["prebuild_pods_per_s"] = 4000
        mix["prebuild_seconds_margin"] = 1.0
    mix["trace"] = {"seconds": 1.0}
    if config.get("trace"):
        config["trace"] = {"seconds": 0.5}
