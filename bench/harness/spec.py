"""Find a cell's configuration, traffic mix, limits and per-layer readers
by the names in ``BENCHMARK.json``: each sits in a file of its own, so a
later cell, configuration, mix or metric is new files and new entries."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / 'BENCHMARK.json').read_text())


def cell(name: str, root: Path = ROOT) -> tuple:
    """(workload entry, configuration dict, traffic dict) of a cell."""
    spec = benchmark(root)
    work = next((w for w in spec['workloads'] if w['name'] == name), None)
    if work is None:
        raise KeyError(f'no workload {name!r} in BENCHMARK.json')
    entry = next(c for c in spec['configs'] if c['name'] == work['config'])
    cfg = json.loads((root / entry['file']).read_text())
    traffic = json.loads(
        (BENCH / 'traffic' / f'{work["traffic"]}.json').read_text())
    return work, cfg, traffic


def metrics(kind: str, workload: str, root: Path = ROOT) -> list:
    """The ``end_to_end`` or ``per_layer`` entries a cell reports."""
    return [m for m in benchmark(root)[kind]
            if workload in m.get('workloads', [workload])]


def _load(kind: str, name: str):
    """Import ``bench/<kind>/<name>.py`` as a module of its own."""
    import sys
    mod_name = f'bench_{kind}_{name.replace(".", "_").replace("-", "_")}'
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    mod_spec = importlib.util.spec_from_file_location(
        mod_name, BENCH / kind / f'{name}.py')
    mod = importlib.util.module_from_spec(mod_spec)
    sys.modules[mod_name] = mod
    mod_spec.loader.exec_module(mod)
    return mod


def reader(name: str):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    return _load('metrics', name).read


def reference(name: str):
    """The plain reference module ``bench/references/<name>.py``."""
    return _load('references', name)


def system(name: str):
    """The system-under-test adapter ``bench/systems/<name>.py``."""
    return _load('systems', name)
