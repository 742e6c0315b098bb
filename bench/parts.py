"""The benchmark's parts that are found by name, one file each:

    parts.load("placements", config["placement"])  # bench/placements/<name>.py
    parts.load("graphs", config["graph"]["kind"])   # bench/graphs/<name>.py
    parts.load("metrics", metric["name"])           # bench/metrics/<name>.py

Nothing here names a part: a later change adds one by adding its file.
"""
from __future__ import annotations

import importlib.util
import re
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
#: a name as ``BENCHMARK.json`` allows it, so no name leads out of its folder
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


def path(folder: str, name: str) -> Path:
    """The file that holds part ``name`` of ``folder``."""
    if not isinstance(name, str) or not NAME.fullmatch(name):
        raise ValueError(f"{name!r} is not a part name (letters, digits, '_', '.', '-')")
    return BENCH / folder / f"{name}.py"


def load(folder: str, name: str):
    """The module in ``bench/<folder>/<name>.py``, imported once a process."""
    file = path(folder, name)
    # '_' -> '__', '.' -> '_d', '-' -> '_h': distinct names stay distinct
    mangled = name.replace("_", "__").replace(".", "_d").replace("-", "_h")
    module_name = f"bench_{folder}_{mangled}"
    if module_name in sys.modules:
        return sys.modules[module_name]
    if not file.is_file():
        raise FileNotFoundError(f"no {folder} file for {name!r}: {file} does not exist")
    spec = importlib.util.spec_from_file_location(module_name, file)
    module = importlib.util.module_from_spec(spec)
    sys.modules[module_name] = module
    try:
        spec.loader.exec_module(module)
    except BaseException:
        del sys.modules[module_name]
        raise
    return module
