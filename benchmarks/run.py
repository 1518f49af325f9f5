"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]

Suites:
  breakdown    Fig. 3/4   execution breakdown + sparsity characterization
  sparsity     Fig. 11/12 significance CDF + tag fidelity vs k
  quality      Fig. 20    PSNR/SSIM of S2/RC/Lumina/DS-2 vs exact baseline
  speedup      Fig. 22/25 variant speedup + energy (incl. GSCore)
  sensitivity  Fig. 23/24 margin x window, alpha-record length
  finetune     Fig. 21/13 scale-constrained loss
  kernel       --         Pallas chunk-early-exit savings
  serve        --         multi-viewer throughput, batched vs sequential
"""
from __future__ import annotations

import argparse
import json
import time
import traceback
from pathlib import Path

SUITES = ('breakdown', 'sparsity', 'quality', 'speedup', 'sensitivity',
          'finetune', 'kernel', 'serve')

# Suites whose rows are additionally written as machine-readable
# BENCH_<name>.json at the repo root — the perf trajectory other sessions
# diff against (experiments/bench/ keeps the full per-run archive).
TRACKED = ('serve', 'kernel')
REPO_ROOT = Path(__file__).resolve().parent.parent


def _render(mod, rows) -> str:
    from benchmarks import common
    title = mod.__doc__.strip().splitlines()[0]
    return common.fmt_rows(rows, title)


def main() -> None:
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument('--quick', action='store_true')
    ap.add_argument('--only', default='')
    ap.add_argument('--out', default='experiments/bench')
    args = ap.parse_args()

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    failures = []
    for name in SUITES:
        if args.only and name != args.only:
            continue
        mod = __import__(f'benchmarks.bench_{name}', fromlist=['run', 'main'])
        t0 = time.time()
        try:
            rows = mod.run(quick=args.quick)
            print(_render(mod, rows))
            print(f'[{name}: {time.time() - t0:.1f}s]\n')
            with open(out_dir / f'{name}.json', 'w') as f:
                json.dump(rows, f, indent=1, default=str)
            if name in TRACKED:
                payload = {'suite': name, 'quick': bool(args.quick),
                           'wall_s': round(time.time() - t0, 2),
                           'rows': rows}
                with open(REPO_ROOT / f'BENCH_{name}.json', 'w') as f:
                    json.dump(payload, f, indent=1, default=str)
        except Exception:
            failures.append(name)
            print(f'== {name} FAILED ==')
            traceback.print_exc()
    if failures:
        raise SystemExit(f'benchmark suites failed: {failures}')


if __name__ == '__main__':
    main()
