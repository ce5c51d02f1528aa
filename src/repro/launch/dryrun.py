import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ.setdefault("JAX_PLATFORMS", "cpu")   # a CPU-only tool: never take a chip

# Multi-pod dry-run: AOT lower + compile every (arch × shape) cell on the
# production meshes, record memory/cost/collective analysis for §Roofline.
#
# The XLA_FLAGS line above MUST run before any jax import — jax locks the
# device count on first init. Do not import this module from tests.
#
# Usage:
#   PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k
#   PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--force]
# Artifacts: experiments/dryrun/<mesh>/<arch>__<shape>[__tag].json

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.analysis.roofline import (collective_bytes, count_params,
                                     model_flops, roofline_terms)
from repro.configs.base import DEFAULT_TUNABLES, SHAPES, Tunables, supports
from repro.configs.registry import ARCHS, get_config, get_shape
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.optim.adamw import OptConfig
from repro.sharding import rules
from repro.train.step import (init_train_state, make_prefill_step,
                              make_serve_step, make_train_step)

OUT_ROOT = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def _shardings(axes_tree):
    return rules.tree_shardings(axes_tree)


def _lower(cfg, shape, tun, oc):
    """Build + AOT-lower the right step for this cell. Returns (lowered,
    n_total, n_active)."""
    if shape.kind == "train":
        state_sds = jax.eval_shape(
            lambda: init_train_state(jax.random.PRNGKey(0), cfg, oc, tun))
        batch_sds = M.input_specs(cfg, shape)
        state_sh = _shardings(rules.state_axes_tree(state_sds, tun.zero3))
        batch_sh = _shardings(rules.batch_axes_tree(batch_sds))
        fn = make_train_step(cfg, oc, tun)
        jitted = jax.jit(fn, in_shardings=(state_sh, batch_sh),
                         donate_argnums=(0,) if tun.donate else ())
        lowered = jitted.lower(state_sds, batch_sds)
        n_total, n_active = count_params(state_sds["params"], cfg)
    elif shape.kind == "prefill":
        params_sds = jax.eval_shape(lambda: M.init(jax.random.PRNGKey(0), cfg))
        batch_sds = M.input_specs(cfg, shape)
        params_sh = _shardings(rules.param_axes_tree(params_sds, tun.zero3))
        batch_sh = _shardings(rules.batch_axes_tree(batch_sds))
        fn = make_prefill_step(cfg, tun)
        jitted = jax.jit(fn, in_shardings=(params_sh, batch_sh))
        lowered = jitted.lower(params_sds, batch_sds)
        n_total, n_active = count_params(params_sds, cfg)
    else:  # decode
        params_sds = jax.eval_shape(lambda: M.init(jax.random.PRNGKey(0), cfg))
        cache_sds = M.cache_specs(cfg, shape)
        batch_sds = M.input_specs(cfg, shape)
        params_sh = _shardings(rules.param_axes_tree(params_sds, tun.zero3))
        cache_sh = _shardings(rules.cache_axes_tree(
            cache_sds, M.cache_seq_axis(cfg)))
        batch_sh = _shardings(rules.batch_axes_tree(batch_sds))
        fn = make_serve_step(cfg, tun)
        jitted = jax.jit(fn, in_shardings=(params_sh, cache_sh, batch_sh),
                         donate_argnums=(1,) if tun.donate else ())
        lowered = jitted.lower(params_sds, cache_sds, batch_sds)
        n_total, n_active = count_params(params_sds, cfg)
    return lowered, n_total, n_active


# ---------------------------------------------------------------------------
# Cost probes: XLA's cost_analysis counts scan bodies ONCE, so per-layer cost
# is measured from two shallow probes (1 and 2 layer-units, inner loops
# unrolled) and extrapolated linearly to the full depth. Exact for homogeneous
# stacks; zamba2's 3 remainder layers are approximated as half a group (<2%).
# ---------------------------------------------------------------------------


def scale_units(cfg, k: int):
    if cfg.family == "encdec":
        return cfg.replace(n_layers=k, enc_layers=k)
    if cfg.family == "hybrid":
        return cfg.replace(n_layers=k * cfg.hybrid_period)
    if cfg.moe is not None and cfg.moe.first_layer_dense:
        return cfg.replace(n_layers=k + 1)
    return cfg.replace(n_layers=k)


def units_full(cfg) -> float:
    if cfg.family == "encdec":
        return float(cfg.n_layers)
    if cfg.family == "hybrid":
        return cfg.n_layers / cfg.hybrid_period
    if cfg.moe is not None and cfg.moe.first_layer_dense:
        return float(cfg.n_layers - 1)
    return float(cfg.n_layers)


def probe_cost(cfg, shape, tun, oc, mesh):
    """(cost_dict, coll_dict) extrapolated to full depth, per device."""
    import dataclasses as dc
    dp = mesh.devices.size // mesh.shape["model"]
    mb = tun.microbatches if shape.kind == "train" else 1
    probe_b = max(shape.global_batch // mb, min(dp, shape.global_batch))
    mb_scale = shape.global_batch / probe_b
    pshape = dc.replace(shape, global_batch=probe_b)
    ptun = tun.replace(attn_unroll=True, layer_unroll=True, microbatches=1)

    results = []
    for k in (1, 2):
        pcfg = scale_units(cfg, k)
        lowered, _, _ = _lower(pcfg, pshape, ptun, oc)
        compiled = lowered.compile()
        cost = {k2: float(v) for k2, v in (compiled.cost_analysis() or {}).items()
                if isinstance(v, (int, float))}
        coll = collective_bytes(compiled.as_text())
        results.append((cost, coll))
    (c1, l1), (c2, l2) = results
    uf = units_full(cfg)

    def extrap(d1, d2):
        out = {}
        for key in set(d1) | set(d2):
            a, b = d1.get(key, 0.0), d2.get(key, 0.0)
            marg = max(b - a, 0.0)     # physical per-layer cost is >= 0
            out[key] = (a + (uf - 1.0) * marg) * mb_scale
        return out

    return extrap(c1, c2), extrap(l1, l2)


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               tun: Tunables = DEFAULT_TUNABLES, oc: OptConfig = OptConfig(),
               verbose: bool = True):
    """Lower + compile one cell; returns the result record dict."""
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    if not supports(cfg, shape):
        raise ValueError(f"unsupported cell {arch}/{shape_name}")
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    rules.set_mesh(mesh)
    t0 = time.time()

    lowered, n_total, n_active = _lower(cfg, shape, tun, oc)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = {}
    try:
        ma = compiled.memory_analysis()
        for k in ("temp_size_in_bytes", "argument_size_in_bytes",
                  "output_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            mem[k] = getattr(ma, k, None)
        if verbose:
            print("memory_analysis:", ma)
    except Exception as e:  # CPU backend may not implement it
        mem["error"] = repr(e)
    raw_cost = {k: float(v) for k, v in (compiled.cost_analysis() or {}).items()
                if isinstance(v, (int, float))}

    # depth-extrapolated cost (scan bodies are counted once by XLA)
    cost, coll = probe_cost(cfg, shape, tun, oc, mesh)
    t_probe = time.time() - t0 - t_lower - t_compile
    if verbose:
        print("cost_analysis (extrapolated) flops:", cost.get("flops"),
              "bytes:", cost.get("bytes accessed"))
    mf = model_flops(cfg, shape, n_active)
    rl = roofline_terms(cost, coll, chips=chips, model_flops=mf)

    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
        "tunables": tun.as_dict(),
        "n_params_total": n_total, "n_params_active": n_active,
        "memory": mem,
        "cost": cost, "cost_raw_scan_once": raw_cost,
        "collectives": coll,
        "roofline": rl.as_dict(),
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "probe_s": round(t_probe, 2),
    }
    return rec


def run_cell(arch, shape_name, *, multi_pod, tun=DEFAULT_TUNABLES, force=False,
             tag="", out_root=OUT_ROOT):
    mesh_name = "2x16x16" if multi_pod else "16x16"
    out_dir = out_root / mesh_name
    out_dir.mkdir(parents=True, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    out = out_dir / f"{arch}__{shape_name}{suffix}.json"
    if out.exists() and not force:
        print(f"[skip] {mesh_name} {arch} {shape_name} (cached)")
        return json.loads(out.read_text())
    print(f"[dryrun] {mesh_name} {arch} {shape_name} ...", flush=True)
    try:
        rec = lower_cell(arch, shape_name, multi_pod=multi_pod, tun=tun)
    except Exception:
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "error": traceback.format_exc()}
        out.write_text(json.dumps(rec, indent=1))
        print(f"[FAIL] {arch} {shape_name}\n{rec['error']}", flush=True)
        return rec
    out.write_text(json.dumps(rec, indent=1))
    r = rec["roofline"]
    print(f"[ok] {arch} {shape_name}: compute={r['compute_s']:.4f}s "
          f"memory={r['memory_s']:.4f}s coll={r['collective_s']:.4f}s "
          f"bottleneck={r['bottleneck']} useful={r['useful_ratio']:.3f} "
          f"(compile {rec['compile_s']}s)", flush=True)
    return rec


def parse_tun(kvs) -> Tunables:
    tun = DEFAULT_TUNABLES
    for kv in kvs or []:
        k, v = kv.split("=", 1)
        cur = getattr(tun, k)
        if isinstance(cur, bool):
            v = v.lower() in ("1", "true", "yes")
        elif isinstance(cur, int):
            v = int(v)
        elif isinstance(cur, float):
            v = float(v)
        tun = tun.replace(**{k: v})
    return tun


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--tun", nargs="*", help="tunable overrides k=v")
    args = ap.parse_args(argv)
    tun = parse_tun(args.tun)

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]
    cells = []
    if args.all:
        from repro.configs.registry import all_cells
        cells = list(all_cells())
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]
    failures = 0
    for mp in meshes:
        for arch, shape_name in cells:
            rec = run_cell(arch, shape_name, multi_pod=mp, tun=tun,
                           force=args.force, tag=args.tag)
            failures += 1 if "error" in rec else 0
    print(f"done; {failures} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
