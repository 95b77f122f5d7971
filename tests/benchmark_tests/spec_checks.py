#!/usr/bin/env python3
"""Hold every structural check of the yardstick's tests against the
BENCHMARK.json of the checkout this process's ``benchmark`` package lies in.
A structural check is a test of a ``test_benchmark_*.py`` beside this file
whose one argument is ``spec``: it reads nothing of BENCHMARK.json but
through that argument.  Prints one JSON line: the checks run, and each
failure with its traceback.  Started by test_benchmark_second_adapter.py from
a copy of ``benchmark/`` under a BENCHMARK.json to which a later PR's cell
and entries were added; never a measurement."""

import glob
import importlib
import inspect
import json
import os
import sys
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def checks():
    for path in sorted(glob.glob(os.path.join(HERE, "test_benchmark_*.py"))):
        mod = importlib.import_module(
            os.path.splitext(os.path.basename(path))[0])
        for name, fn in sorted(vars(mod).items()):
            if name.startswith("test_") and inspect.isfunction(fn) \
                    and list(inspect.signature(fn).parameters) == ["spec"]:
                yield f"{mod.__name__}::{name}", fn


def main() -> int:
    from benchmark import harness
    spec = harness.load_spec()
    ran, failed = [], {}
    for name, fn in checks():
        ran.append(name)
        try:
            fn(spec)
        except (Exception, SystemExit):     # harness refuses by exiting
            failed[name] = traceback.format_exc()
    print(json.dumps({
        "benchmark": harness.HERE, "ran": ran, "failed": failed,
        "cells": [w["name"] for w in spec["workloads"]],
        "per_layer": [m["name"] for m in spec["per_layer"]]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
