"""Files found by the name a data file gives them.

A configuration file names its plain reference and its FLOP count:
``"reference": "<name>"`` is ``references/<name>.py`` (``logits(params,
tokens) -> [B, T, V]`` float32 in plain ``jax.numpy``), ``"costs": "<name>"``
is ``costs/<name>.py`` (``param_count(model)`` and
``train_flops_per_token(model, seq_len)``). Each is looked for under the
benchmark directory of the root ``run.py`` was given, then under this
benchmark's own: the rehearsal's toy brings the files of its second family
and shares the rest. A key or a file that is missing is an error that names
the key; nothing has a default.
"""
from __future__ import annotations

import importlib.util
import os
import re

from benchmarks.lib.cluster import BENCH_DIR

# key of a configuration file -> the directory its files live in
KINDS = {"reference": "references", "costs": "costs"}


def need(block: dict, key: str, where: str):
    """``block[key]``, or an error that names the key and where it should be."""
    if key not in block:
        raise KeyError(f"{where} has no {key!r}")
    return block[key]


def find(key: str, name: str, bench_dir: str, where: str) -> str:
    """Path of the file that ``key: name`` of the file ``where`` names."""
    tried = [os.path.join(d, KINDS[key], f"{name}.py")
             for d in dict.fromkeys((bench_dir, BENCH_DIR))]
    for path in tried:
        if os.path.isfile(path):
            return path
    raise FileNotFoundError(
        f"{where}: {key!r} names {name!r}, and there is no "
        + " or ".join(tried))


def load(path: str):
    """The module at ``path``, under a name of its own (two directories may
    hold a file of one name)."""
    name = "benchmark_file_" + re.sub(r"\W", "_", os.path.splitext(path)[0])
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
