"""Training launcher.

CPU-friendly default: reduced config + small shape. On a real TPU mesh the
same entry point takes --full and the production mesh (the step builder,
sharding rules, checkpointing and the autonomic loop are identical).

The KERMIT loop is driven through ``repro.kermit.KermitSession``; pass
``--kermit-config spec.json`` to load a full declarative ``KermitConfig``
tree (``KermitConfig.from_dict`` round-trips ``to_dict`` output), and the
launcher subscribes to the typed event stream to report per-kind counts.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --steps 30
  PYTHONPATH=src python -m repro.launch.train --arch mamba2-1.3b --autonomic \
      --steps 200 --ckpt-dir /tmp/ckpt --kermit-config kermit.json
"""
from __future__ import annotations

import argparse
import json
from collections import Counter

from repro.configs.base import DEFAULT_TUNABLES, ShapeSpec, reduced
from repro.configs.registry import ARCHS, get_config
from repro.kermit import (KermitConfig, KermitSession, KnowledgeConfig,
                          MonitorConfig)
from repro.optim.adamw import OptConfig
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.fault import FailureInjector
from repro.runtime.loop import Trainer


def _build_session(args) -> KermitSession:
    if args.kermit_config:
        with open(args.kermit_config) as f:
            cfg = KermitConfig.from_dict(json.load(f))
        if args.kermit_root:            # CLI root overrides the spec's
            cfg = cfg.replace(
                knowledge=KnowledgeConfig(root=args.kermit_root,
                                          drift_eps=cfg.knowledge.drift_eps))
    else:
        # preserve the historical CLI cadence (the old AutonomicManager
        # defaults: window 16 vs MonitorConfig's 32) so short --autonomic
        # runs keep reaching the analysis threshold where they used to
        cfg = KermitConfig(
            monitor=MonitorConfig(window_size=16),
            knowledge=KnowledgeConfig(root=args.kermit_root))
    return KermitSession(cfg)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="qwen2-1.5b")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--full", action="store_true",
                    help="full config (needs a real accelerator mesh)")
    ap.add_argument("--autonomic", action="store_true",
                    help="enable the KERMIT MAPE-K loop")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--kermit-root", default=None)
    ap.add_argument("--kermit-config", default=None,
                    help="JSON KermitConfig tree (see KermitConfig.to_dict)")
    ap.add_argument("--fail-at", type=int, nargs="*", default=[],
                    help="inject node failures at these steps")
    ap.add_argument("--tun", nargs="*", default=[], help="tunable k=v")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if not args.full:
        cfg = reduced(cfg)
    shape = ShapeSpec("cli", args.seq, args.batch, "train")
    tun = DEFAULT_TUNABLES
    for kv in args.tun:
        k, v = kv.split("=", 1)
        cur = getattr(tun, k)
        v = (v.lower() in ("1", "true")) if isinstance(cur, bool) else \
            type(cur)(v)
        tun = tun.replace(**{k: v})

    session = _build_session(args) if args.autonomic else None
    event_counts: Counter = Counter()
    if session is not None:
        session.subscribe(None, lambda ev: event_counts.update([ev.kind]))
    injector = FailureInjector(fail_steps=tuple(args.fail_at)) \
        if args.fail_at else None
    tr = Trainer(cfg, shape, OptConfig(lr=args.lr, warmup=10), tun,
                 ckpt_dir=args.ckpt_dir, autonomic=session,
                 injector=injector)
    rep = tr.run(args.steps)
    out = {
        "arch": args.arch, "steps": rep.steps_done,
        "loss_first": rep.losses[0], "loss_last": rep.losses[-1],
        "mean_step_s": sum(rep.step_times) / len(rep.step_times),
        "failures_recovered": rep.failures_recovered,
        "straggler_events": rep.straggler_events,
        "retunes": rep.retunes,
    }
    if session is not None:
        out["kermit"] = session.summary()
        out["kermit_events"] = dict(event_counts)
        session.close()
    print(json.dumps(out, indent=1, default=str))


if __name__ == "__main__":
    main()
