import os
# A host-only compile tool: it and every child it starts stay on the CPU
# (children inherit this environment), and the forced device count is
# added to whatever XLA_FLAGS already holds.  MUST precede any jax import:
# jax locks the platform and device count on first init.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512"
                           ).strip()
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the real step function (train_step for train
shapes, prefill/decode serve steps otherwise), attaches the recipe's
in/out shardings, lowers it against ``input_specs`` ShapeDtypeStructs (no
allocation), compiles for the production mesh, and records:

  * ``compiled.memory_analysis()``  — proves the cell fits 16 GB/chip HBM;
  * ``compiled.cost_analysis()``    — XLA's own FLOPs/bytes counters;
  * parsed optimized-HLO aggregates — per-chip FLOPs / HBM bytes /
    collective bytes with while-loop trip counts applied (the roofline
    inputs; see repro.analysis.hlo_parse for why cost_analysis alone
    under-counts scanned layers);
  * the three-term roofline (repro.analysis.roofline).

Run one cell:     python -m repro.launch.dryrun --arch yi-34b --shape train_4k --mesh single
Run everything:   python -m repro.launch.dryrun --all   (subprocess per cell)
Results land in   experiments/dryrun/<arch>__<shape>__<mesh>.json
"""
import argparse
import dataclasses
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import roofline as rl
from repro.analysis.flops import model_flops
from repro.configs import ALL_LM_ARCHS, get_config
from repro.configs.base import SHAPES, shape_applicable
from repro.launch.mesh import make_production_mesh
from repro.models import registry
from repro.optim import adam
from repro.runtime.sharding import spec_to_sharding

OUT_DIR = Path(__file__).resolve().parents[3] / 'experiments' / 'dryrun'

RENDER_SHAPES = ('render_1080p',)   # the paper-native lumina-3dgs cell


def _opt_overrides(cfg, opt: str):
    """Apply comma-separated perf-iteration overrides (§Perf knobs)."""
    if not opt:
        return cfg
    for item in opt.split(','):
        k, _, v = item.partition('=')
        k = k.strip()
        if not k:
            continue
        field_types = {f.name: f.type for f in dataclasses.fields(cfg)}
        if k not in field_types:
            raise ValueError(f'unknown override {k!r} for {cfg.name}')
        cur = getattr(cfg, k)
        if isinstance(cur, bool):
            val = v.lower() in ('1', 'true', 'yes')
        elif isinstance(cur, int):
            val = int(v)
        elif isinstance(cur, float):
            val = float(v)
        else:
            val = v
        cfg = dataclasses.replace(cfg, **{k: val})
    return cfg


# ---------------------------------------------------------------------------
# Cell builders: (fn, abstract args, in_shardings, out_shardings)
# ---------------------------------------------------------------------------

def build_lm_cell(arch: str, shape_name: str, mesh, opt: str = ''):
    cfg = _opt_overrides(get_config(arch), opt)
    shape = SHAPES[shape_name]
    long_context = shape.name == 'long_500k'
    ctx = registry.make_ctx(mesh, cfg, long_context=long_context)
    tp = registry.tp_of(mesh, cfg)

    params_abs = registry.abstract_params(cfg, tp)
    p_spec = registry.param_specs(cfg, params_abs, mesh)
    p_sh = spec_to_sharding(mesh, p_spec)
    batch_abs = registry.input_specs(cfg, shape)
    b_sh = spec_to_sharding(mesh, registry.batch_shardings(cfg, mesh, batch_abs))
    repl = NamedSharding(mesh, P())

    if shape.kind == 'train':
        step, acfg = registry.make_train_step(cfg, ctx)
        opt_abs = jax.eval_shape(lambda p: adam.init(p, acfg), params_abs)
        o_sh = adam.AdamState(step=repl,
                              mu=jax.tree.map(lambda s: s, p_sh),
                              nu=jax.tree.map(lambda s: s, p_sh))
        metrics_sh = {'loss': repl, 'grad_norm': repl}
        fn = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                     out_shardings=(p_sh, o_sh, metrics_sh))
        args = (params_abs, opt_abs, batch_abs)
    elif shape.kind == 'prefill':
        prefill = registry.make_prefill(cfg, ctx)
        fn = jax.jit(prefill, in_shardings=(p_sh, b_sh), out_shardings=repl)
        args = (params_abs, batch_abs)
    else:  # decode
        dstep = registry.make_decode_step(cfg, ctx)
        state_abs = registry.abstract_decode_state(
            cfg, shape.global_batch, shape.seq_len, tp)
        if cfg.family == 'encdec':
            # cross caches are precomputed at request admission; the decode
            # dry-run carries them as state (same shapes as init)
            pass
        s_spec = registry.decode_state_specs(cfg, state_abs, mesh,
                                             long_context=long_context)
        s_sh = spec_to_sharding(mesh, s_spec)
        tok_abs = batch_abs['token']
        tok_sh = spec_to_sharding(
            mesh, registry.batch_shardings(cfg, mesh, tok_abs))
        pos_abs = jax.ShapeDtypeStruct((), jnp.int32)

        fn = jax.jit(dstep, in_shardings=(p_sh, tok_sh, s_sh, repl),
                     out_shardings=(repl, s_sh))
        args = (params_abs, tok_abs, state_abs, pos_abs)

    mf = model_flops(cfg, shape)
    return fn, args, mf


def build_render_cell(shape_name: str, mesh, opt: str = ''):
    """The paper-native workload: one LuminSys serve frame, distributed.

    Gaussians shard over 'data' (projection is embarrassingly parallel),
    tiles shard over 'model' for rasterization — the cluster-scale analogue
    of the paper's GPU(sort) / NRU(raster) split.
    """
    from repro.core import render_dist
    cfg = get_config('lumina-3dgs')
    if opt:
        cfg = _opt_overrides(cfg, opt)
    return render_dist.build_dryrun_cell(cfg, mesh, shape_name)


# ---------------------------------------------------------------------------
# One cell: lower -> compile -> analyze -> save
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, mesh_kind: str, *,
             opt: str = '', save_hlo: bool = False,
             out_dir: Path = OUT_DIR) -> dict:
    multi = mesh_kind == 'multi'
    mesh = make_production_mesh(multi_pod=multi)
    chips = mesh.devices.size
    pod_size = 256

    t0 = time.time()
    if arch == 'lumina-3dgs':
        fn, args, mf = build_render_cell(shape_name, mesh, opt)
    else:
        fn, args, mf = build_lm_cell(arch, shape_name, mesh, opt)

    with mesh:
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()

    roof = rl.from_compiled(
        arch, shape_name, mesh_kind, chips, hlo,
        model_flops=mf, pod_size=pod_size, memory_analysis=mem,
        note=opt)
    rec = {
        'arch': arch, 'shape': shape_name, 'mesh': mesh_kind,
        'chips': chips, 'opt': opt,
        'lower_s': round(t_lower, 2), 'compile_s': round(t_compile, 2),
        'memory_analysis': {
            k: int(getattr(mem, k, 0) or 0)
            for k in ('argument_size_in_bytes', 'output_size_in_bytes',
                      'temp_size_in_bytes', 'alias_size_in_bytes',
                      'generated_code_size_in_bytes')
        },
        'cost_analysis': {k: float(v) for k, v in cost.items()
                          if isinstance(v, (int, float))
                          and k in ('flops', 'bytes accessed',
                                    'transcendentals', 'optimal_seconds')},
        'roofline': roof.row(),
        'hlo_chars': len(hlo),
    }
    out_dir.mkdir(parents=True, exist_ok=True)
    stem = f'{arch}__{shape_name}__{mesh_kind}' + (f'__{_slug(opt)}' if opt else '')
    with open(out_dir / f'{stem}.json', 'w') as f:
        json.dump(rec, f, indent=1, default=str)
    if save_hlo:
        import gzip
        with gzip.open(out_dir / f'{stem}.hlo.txt.gz', 'wt') as f:
            f.write(hlo)
    return rec


def _slug(s: str) -> str:
    return ''.join(c if c.isalnum() else '-' for c in s)[:48]


def all_cells(include_render: bool = True):
    cells = []
    for arch in ALL_LM_ARCHS:
        cfg = get_config(arch)
        for sname, shape in SHAPES.items():
            if not shape_applicable(cfg, shape):
                continue
            cells.append((arch, sname))
    if include_render:
        for sname in RENDER_SHAPES:
            cells.append(('lumina-3dgs', sname))
    return cells


def run_all(mesh_kinds=('single', 'multi'), *, opt: str = '',
            jobs: int = 1, timeout: int = 7200, force: bool = False,
            include_render: bool = True) -> None:
    """Drive every cell in a subprocess (fresh jax per cell; crash isolation)."""
    work = []
    for arch, sname in all_cells(include_render):
        for mk in mesh_kinds:
            stem = f'{arch}__{sname}__{mk}' + (f'__{_slug(opt)}' if opt else '')
            if not force and (OUT_DIR / f'{stem}.json').exists():
                continue
            work.append((arch, sname, mk))
    print(f'{len(work)} cells to run')
    procs: list = []
    results = {'ok': 0, 'fail': 0}
    log_dir = OUT_DIR / 'logs'
    log_dir.mkdir(parents=True, exist_ok=True)

    def launch(arch, sname, mk):
        stem = f'{arch}__{sname}__{mk}' + (f'__{_slug(opt)}' if opt else '')
        log = open(log_dir / f'{stem}.log', 'w')
        cmd = [sys.executable, '-m', 'repro.launch.dryrun', '--arch', arch,
               '--shape', sname, '--mesh', mk]
        if opt:
            cmd += ['--opt', opt]
        p = subprocess.Popen(cmd, stdout=log, stderr=subprocess.STDOUT)
        return (p, log, time.time(), (arch, sname, mk))

    queue = list(work)
    while queue or procs:
        while queue and len(procs) < jobs:
            procs.append(launch(*queue.pop(0)))
        time.sleep(5)
        still = []
        for p, log, t0, cell in procs:
            if p.poll() is None:
                if time.time() - t0 > timeout:
                    p.kill()
                    print(f'TIMEOUT {cell}')
                    results['fail'] += 1
                    log.close()
                else:
                    still.append((p, log, t0, cell))
            else:
                ok = p.returncode == 0
                results['ok' if ok else 'fail'] += 1
                dt = time.time() - t0
                print(f'{"OK  " if ok else "FAIL"} {cell} ({dt:.0f}s)')
                log.close()
        procs = still
    print(f"done: {results['ok']} ok, {results['fail']} failed")


def collect_table() -> list[dict]:
    rows = []
    for f in sorted(OUT_DIR.glob('*.json')):
        with open(f) as fh:
            rec = json.load(fh)
        rows.append(rec['roofline'] | {
            'compile_s': rec['compile_s'],
            'temp_bytes': rec['memory_analysis'].get('temp_size_in_bytes', 0),
        })
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument('--arch')
    ap.add_argument('--shape')
    ap.add_argument('--mesh', choices=('single', 'multi'), default='single')
    ap.add_argument('--opt', default='', help='cfg overrides, k=v,k=v')
    ap.add_argument('--all', action='store_true')
    ap.add_argument('--force', action='store_true')
    ap.add_argument('--jobs', type=int, default=1)
    ap.add_argument('--timeout', type=int, default=7200)
    ap.add_argument('--save-hlo', action='store_true')
    ap.add_argument('--table', action='store_true',
                    help='print the collected roofline table and exit')
    args = ap.parse_args()

    if args.table:
        print(rl.fmt_table(collect_table()))
        return
    if args.all:
        run_all(opt=args.opt, jobs=args.jobs, timeout=args.timeout,
                force=args.force)
        return
    assert args.arch and args.shape, '--arch/--shape or --all required'
    rec = run_cell(args.arch, args.shape, args.mesh, opt=args.opt,
                   save_hlo=args.save_hlo)
    print(json.dumps({k: rec[k] for k in
                      ('arch', 'shape', 'mesh', 'lower_s', 'compile_s')},
                     indent=1))
    print('memory_analysis:', rec['memory_analysis'])
    print('cost_analysis:', rec['cost_analysis'])
    r = rec['roofline']
    print(f"roofline: compute={rl.fmt_seconds(r['t_compute_s'])} "
          f"memory={rl.fmt_seconds(r['t_memory_s'])} "
          f"collective={rl.fmt_seconds(r['t_collective_s'])} "
          f"bound={r['bottleneck']} useful={r['useful_ratio']:.2f} "
          f"roofline%={100 * r['roofline_fraction']:.1f}")


if __name__ == '__main__':
    main()
