"""Serving driver: continuous-batching engine fed by a synthetic request
stream, optionally scheduled across a cluster by the paper's placement
engine.

Engine mode (one replica, real forward passes):
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --reduced \
      --requests 16 --slots 4

Cluster mode (placement-integrated, paper use cases live):
  PYTHONPATH=src python -m repro.launch.serve --cluster --nodes 4 \
      --policy heuristic
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, reduced as reduce_cfg
from repro.launch.compile_cache import enable_compile_cache
from repro.models import bundle
from repro.serving import Engine, EngineConfig, Request
from repro.serving.cluster import ClusterServer


def run_engine(args) -> int:
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg, capacity_factor=8.0)
    mb = bundle(cfg)
    params = mb.init(jax.random.key(0))
    eng = Engine(mb, params, EngineConfig(max_slots=args.slots, max_len=args.max_len))
    rng = np.random.default_rng(args.seed)
    for i in range(args.requests):
        plen = int(rng.integers(4, args.max_len // 4))
        prompt = list(map(int, rng.integers(1, cfg.vocab_size, size=plen)))
        eng.submit(Request(rid=f"req{i}", prompt=prompt,
                           max_new_tokens=int(rng.integers(4, args.max_new))))
    t0 = time.time()
    done = eng.run()
    dt = time.time() - t0
    toks = sum(len(c.tokens) for c in done)
    print(f"{len(done)} completions, {toks} tokens in {dt:.2f}s "
          f"({toks / dt:,.1f} tok/s), {eng.stats['decode_steps']} decode steps, "
          f"{eng.stats['prefills']} prefills")
    assert len(done) == args.requests
    return 0


def run_cluster(args) -> int:
    srv = ClusterServer(n_nodes=args.nodes, policy=args.policy)
    print(f"cluster: {args.nodes} pods, policy={args.policy}")
    # Scale-up wave (paper: initial deployment)
    for model, arch, n in (
        ("chat", "smollm-135m", 5),
        ("code", "chatglm3-6b", 3),
        ("draft", "xlstm-125m", 2),
    ):
        rep = srv.deploy(model, arch, n, max_batch=8, max_len=4096)
        print(f"  deploy {model} ({arch}) x{n}: placed={len(rep.placed)} "
              f"pending={len(rep.pending)} nodes_used={rep.metrics.n_gpus}")
    print(f"  utilization: {srv.utilization()}")
    # Scale-down + compaction (paper Sec 2.3.2)
    srv.retire("chat", 3)
    srv.retire("code", 1)
    rep = srv.compact()
    print(f"  compaction: {rep.before.n_gpus} -> {rep.after.n_gpus} nodes, "
          f"{rep.plan.n_moves} moves ({rep.plan.n_sequential} sequential)")
    # Maintenance reconfiguration (paper Sec 2.3.3)
    rep = srv.reconfigure()
    print(f"  reconfiguration: {rep.before.n_gpus} -> {rep.after.n_gpus} nodes, "
          f"wastage {rep.before.compute_wastage} -> {rep.after.compute_wastage}")
    print(f"  final: {srv.utilization()}")
    srv.state.validate()
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cluster", action="store_true")
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--policy", default="heuristic",
                    choices=["heuristic", "mip", "first_fit", "load_balanced"])
    args = ap.parse_args()
    return run_cluster(args) if args.cluster else run_engine(args)


if __name__ == "__main__":
    enable_compile_cache()
    raise SystemExit(main())
