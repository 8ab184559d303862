"""Bring-up smoke run of the placement fabric and the served path on a TPU.

    python chip_smoke.py             # one chip: every phase below
    python chip_smoke.py --chips 4   # four chips: one replica per chip, only

Phases (one chip):
  device         the platform JAX found; anything but a TPU exits non-zero
  control plane  Sec-5.1 deploy on a 1024-GPU fleet through the jitted
                 fabric, placement-identical to the scalar path; compact
  served         smollm-135m: 2 replicas behind ClusterServer, jnp and
                 Pallas kernels, prefill logits against a float32 reference
  served         chatglm3-6b: 1 replica, peak HBM in use
  kernels        each Pallas kernel against its kernels/ref.py oracle

Every phase prints one line: its name, its wall time (cold set-up: the
compiles are inside it) and what it checked.  A failed check raises, so the
script exits non-zero and never prints the last line, which is the JSON
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Weights are
random from fixed seeds; the whole run is one process and starts no other.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import obs  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import fabric  # noqa: E402
from repro.core.engine import PlacementEngine  # noqa: E402
from repro.core.simulator import generate_test_case  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.kernels.decode_attention import (  # noqa: E402
    decode_attention_pallas,
    decode_attention_q8_pallas,
)
from repro.kernels.flash_attention import flash_attention_pallas  # noqa: E402
from repro.kernels.ssd_scan import ssd_scan_pallas  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import bundle  # noqa: E402
from repro.models.ssm import mamba_dims  # noqa: E402
from repro.serving import Engine, EngineConfig, Request  # noqa: E402
from repro.serving.cluster import ClusterServer  # noqa: E402

#: prefill logits of the bf16 model against the float32 reference:
#: max |bf16 - f32| over max |f32|.  bf16 keeps 8 mantissa bits (2^-8 =
#: 0.4% per rounding); 30 layers of rounded activations stay within a few
#: percent of the logit range.
LOGIT_TOL = 0.05
#: kernel vs oracle on bf16 inputs (tests/test_kernels.py's bf16 tolerances)
ATTN_TOL = 2e-2
SSD_TOL = 5e-2


def check(ok: bool, what: str) -> None:
    """Raise unless ``ok`` (unlike ``assert``, kept under ``python -O``)."""
    if not ok:
        raise AssertionError(what)


def _line(name: str, seconds: float, detail: str) -> None:
    print(f"[{name}] {seconds:.1f} s cold set-up, compiles included | {detail}",
          flush=True)


def _devices_of(tree) -> set:
    return {d for leaf in jax.tree.leaves(tree) for d in leaf.devices()}


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------
def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# control plane
# ---------------------------------------------------------------------------
def _layout(state) -> list:
    return sorted(
        (p.wid, g.gid, p.index) for g in state.gpus.values() for p in g.placements
    )


def phase_control_plane(n_gpus: int = 1024, seed: int = 0,
                        fabric_mode: str = "auto") -> str:
    """Sec-5.1 deploy through the fabric (``fabric="auto"`` takes it from
    FABRIC_AUTO_MIN_GPUS up) against the scalar path (``fabric="off"``) on
    the same seeded fleet, then compact."""
    platform = jax.devices()[0].platform
    runs = {}
    for mode in (fabric_mode, "off"):
        case = generate_test_case(seed, n_gpus=n_gpus)
        eng = PlacementEngine("rule_based", fabric=mode)
        with obs.enabled() as tel:
            res = eng.deploy(case.initial, case.new_workloads)
        sweeps = tel.metrics.get("fabric_score_seconds", {"kernel": "feasible"})
        runs[mode] = (case, eng, _layout(case.initial), res.pending, sweeps)
    case, eng, layout, pending, sweeps = runs[fabric_mode]
    check(sweeps is not None and sweeps.count > 0, "deploy skipped the fabric")
    check(runs["off"][4] is None, "fabric='off' ran the fabric")
    check(layout == runs["off"][2], "fabric placements differ from the scalar path")
    check([w.wid for w in pending] == [w.wid for w in runs["off"][3]],
          "fabric and scalar paths leave different workloads pending")

    # the jitted sweep runs on the accelerator and agrees with numpy there
    fab = fabric.fleet_fabric(case.initial)
    tab = fab.tables[fab.kinds[0]]
    args = (fab.occ, fab.n_mem, fab.me_used, fab.me_cap,
            tab.mem_sl, tab.me_req, tab.allowed, fab.kind_mask(None))
    swept = fabric._feasible_all_jit(*args)
    on = {d.platform for d in swept.devices()}
    check(on == {platform}, f"fabric sweep ran on {on}, not {platform}")
    check(np.array_equal(np.asarray(swept), fabric._feasible_all_np(*args)),
          "device sweep differs from the numpy reference")

    n_used = len(case.initial.used_gpus())
    rep = eng.compact(case.initial)
    case.initial.validate()
    after = len(case.initial.used_gpus())
    check(after <= n_used, f"compaction grew the fleet: {n_used} -> {after}")
    return (f"{n_gpus} GPUs, {len(case.new_workloads)} new workloads, "
            f"{len(pending)} pending; fabric == scalar placements; sweep on "
            f"{platform}; compact {n_used} -> {after} GPUs "
            f"(committed={rep.committed}), state valid")


# ---------------------------------------------------------------------------
# served path
# ---------------------------------------------------------------------------
def _requests(rng, vocab: int, n: int, prefix: str):
    """Seeded requests: prompts of 16-200 tokens, 8-32 new tokens."""
    out = []
    for i in range(n):
        prompt = rng.integers(1, vocab, int(rng.integers(16, 201)))
        out.append(Request(rid=f"{prefix}{i}", prompt=[int(t) for t in prompt],
                           max_new_tokens=int(rng.integers(8, 33))))
    return out


def _serve(cfg, params, model: str, n_replicas: int, requests, *, max_slots: int,
           max_len: int, after_compact=None):
    """Deploy replicas behind a ClusterServer, route ``requests`` through
    ``submit``/``pump`` and check every one completes; optionally compact
    and serve ``after_compact`` too.  Returns rid -> generated tokens."""
    mb = bundle(cfg)
    srv = ClusterServer(n_nodes=2)
    rep = srv.deploy(model, cfg.name, n_replicas, max_batch=max_slots,
                     max_len=max_len)
    check(len(rep.placed) == n_replicas, f"unplaced replicas {rep.pending}")
    ecfg = EngineConfig(max_slots=max_slots, max_len=max_len)
    for wid in rep.placed:
        srv.attach_engine(wid, Engine(mb, params, ecfg))

    def run(reqs):
        for r in reqs:
            check(srv.submit(model, r) is not None, f"{r.rid} found no replica")
        produced = srv.pump()
        check(produced == sum(r.max_new_tokens for r in reqs),
              f"{produced} tokens produced")

    run(requests)
    if after_compact is not None:
        rep = srv.compact()
        srv.state.validate()
        check(rep.after.n_gpus <= rep.before.n_gpus, "compaction grew the fleet")
        run(after_compact)
    done = {c.rid: c for e in srv.engines.values() for c in e.completed}
    want = list(requests) + list(after_compact or ())
    check(sorted(done) == sorted(r.rid for r in want), f"completed {sorted(done)}")
    for r in want:
        toks = done[r.rid].tokens
        check(len(toks) == r.max_new_tokens
              and all(0 <= t < cfg.vocab_size for t in toks), f"{r.rid}: {toks}")
    return {rid: c.tokens for rid, c in done.items()}


def _prefill_logits(cfg, params, tokens: np.ndarray) -> np.ndarray:
    mb = bundle(cfg)
    fn = jax.jit(lambda p, t: mb.prefill_fn(p, {"tokens": t},
                                            max_len=t.shape[1])[0])
    return np.asarray(fn(params, jnp.asarray(tokens)).astype(jnp.float32))


def phase_served_smollm(cfg=None, *, interpret: bool = False, seed: int = 0,
                        max_len: int = 512) -> str:
    """Two replicas serve 8 routed requests, compact, serve one more; once
    on the jnp kernels and once on the Pallas kernels.  Prefill logits of a
    fixed prompt on both against the ``ref`` kernels in float32."""
    cfg = cfg or get_config("smollm-135m")
    params = bundle(cfg).init(jax.random.key(seed))
    rng = np.random.default_rng(seed)
    requests = _requests(rng, cfg.vocab_size, 8, "req")
    extra = _requests(rng, cfg.vocab_size, 1, "post-compact")
    prompt = rng.integers(1, cfg.vocab_size, (1, 128)).astype(np.int32)

    cfg32 = dataclasses.replace(cfg, dtype="float32")
    params32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    try:
        ops.set_impl("ref")
        with jax.default_matmul_precision("float32"):
            want = _prefill_logits(cfg32, params32, prompt)
        errs, firsts, served = {}, {}, {}
        for impl in ("jnp", "pallas"):
            ops.set_impl(impl, interpret=interpret)
            got = _prefill_logits(cfg, params, prompt)
            errs[impl] = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
            firsts[impl] = int(np.argmax(got[0, -1]))
            served[impl] = _serve(cfg, params, "chat", 2, requests,
                                  max_slots=4, max_len=max_len,
                                  after_compact=extra)
    finally:
        ops.set_impl("jnp")
    for impl, err in errs.items():
        check(err <= LOGIT_TOL, f"{impl} prefill logits off by {err:.4f}")
    check(firsts["jnp"] == firsts["pallas"], f"first tokens differ: {firsts}")
    same = sum(served["jnp"][r] == served["pallas"][r] for r in served["jnp"])
    return (f"{cfg.name} 2 replicas x (jnp, pallas): 8 requests + 1 after "
            f"compact all complete; prefill logits rel err vs f32 ref "
            f"jnp={errs['jnp']:.4f} pallas={errs['pallas']:.4f} "
            f"(tol {LOGIT_TOL}); first token {firsts['jnp']} on both; "
            f"{same}/{len(served['jnp'])} greedy sequences identical")


def phase_served_glm(cfg=None, *, interpret: bool = False, seed: int = 0,
                     max_len: int = 2048) -> str:
    """One full-width replica answers 4 routed requests on the Pallas
    kernels; reports the device's peak bytes in use."""
    cfg = cfg or get_config("chatglm3-6b")
    mb = bundle(cfg)
    params = mb.init(jax.random.key(seed))
    n_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    rng = np.random.default_rng(seed + 1)
    try:
        ops.set_impl("pallas", interpret=interpret)
        _serve(cfg, params, "code", 1, _requests(rng, cfg.vocab_size, 4, "glm"),
               max_slots=4, max_len=max_len)
    finally:
        ops.set_impl("jnp")
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return (f"{cfg.name} 1 replica (4 slots x {max_len}): 4 requests complete; "
            f"weights {n_bytes / 1e9:.3f} GB; peak_bytes_in_use="
            f"{peak if peak is not None else 'not reported'}")


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------
def _max_err(got, oracle, *args, tol: float) -> float:
    """Max |kernel - oracle|, asserted within ``tol``; the oracle's matmuls
    run in full float32 rather than the TPU's one-pass bf16 default."""
    with jax.default_matmul_precision("float32"):
        want = oracle(*args)
    err = 0.0
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        g, w = (np.asarray(jnp.asarray(t, jnp.float32)) for t in (g, w))
        np.testing.assert_allclose(g, w, atol=tol, rtol=tol)
        err = max(err, float(np.max(np.abs(g - w))))
    return err


def phase_kernels(attn_cfgs=None, q8_cfg=None, ssd_cfg=None, *, seq: int = 1024,
                  cache_len: int = 2048, interpret: bool = False) -> str:
    """Each Pallas kernel once against its oracle, at the models' widths."""
    attn_cfgs = attn_cfgs or [get_config("smollm-135m"), get_config("chatglm3-6b")]
    q8_cfg = q8_cfg or get_config("chatglm3-6b")
    ssd_cfg = ssd_cfg or get_config("zamba2-1.2b")
    keys = iter(jax.random.split(jax.random.key(7), 32))

    def normal(shape, scale=1.0):
        return (jax.random.normal(next(keys), shape) * scale).astype(jnp.bfloat16)

    lens = jnp.asarray([1, cache_len // 3, cache_len - 5, cache_len], jnp.int32)
    errs = {}
    for cfg in attn_cfgs:
        hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
        q, k, v = (normal((1, seq, h, d)) for h in (hq, hkv, hkv))
        errs[f"flash/{cfg.name}"] = _max_err(
            flash_attention_pallas(q, k, v, interpret=interpret),
            ref.attention_ref, q, k, v, tol=ATTN_TOL)
        q = normal((4, 1, hq, d))
        k, v = normal((4, cache_len, hkv, d)), normal((4, cache_len, hkv, d))
        errs[f"decode/{cfg.name}"] = _max_err(
            decode_attention_pallas(q, k, v, lens, interpret=interpret),
            ref.decode_attention_ref, q, k, v, lens, tol=ATTN_TOL)
    hq, hkv, d = q8_cfg.n_heads, q8_cfg.n_kv_heads, q8_cfg.head_dim_
    q = normal((4, 1, hq, d))
    kq, ks = ref.quantize_kv(normal((4, cache_len, hkv, d)))
    vq, vs = ref.quantize_kv(normal((4, cache_len, hkv, d)))
    errs[f"decode_q8/{q8_cfg.name}"] = _max_err(
        decode_attention_q8_pallas(q, kq, ks, vq, vs, lens, interpret=interpret),
        ref.decode_attention_q8_ref, q, kq, ks, vq, vs, lens, tol=ATTN_TOL)
    _, h, p, n = mamba_dims(ssd_cfg)
    x = normal((1, seq, h, p), 0.5)
    dt = jax.nn.softplus(jax.random.normal(next(keys), (1, seq, h)))
    a = -jnp.exp(jax.random.normal(next(keys), (h,)) * 0.3)
    b, c = normal((1, seq, n), 0.5), normal((1, seq, n), 0.5)
    errs[f"ssd/{ssd_cfg.name} H={h} P={p} N={n}"] = _max_err(
        ssd_scan_pallas(x, dt, a, b, c, chunk=ssd_cfg.ssm_chunk, interpret=interpret),
        ref.ssd_scan_ref, x, dt, a, b, c, tol=SSD_TOL)
    return "; ".join(f"{k} max err {e:.2e}" for k, e in errs.items())


# ---------------------------------------------------------------------------
# one replica per chip
# ---------------------------------------------------------------------------
def phase_replicas(cfg=None, devices=None, *, seed: int = 0,
                   max_len: int = 512) -> str:
    """A ClusterServer places one replica per device; each serves the same
    seeded prompts and must produce the greedy tokens of the same replica
    run on the first device."""
    cfg = cfg or get_config("smollm-135m")
    devices = list(devices or jax.devices())
    mb = bundle(cfg)
    params = mb.init(jax.random.key(seed))
    ecfg = EngineConfig(max_slots=4, max_len=max_len)
    placed_on = {}

    def factory(model, arch, wid):
        placed_on[wid] = devices[len(placed_on)]
        return Engine(mb, params, ecfg, device=placed_on[wid])

    srv = ClusterServer(n_nodes=len(devices), engine_factory=factory)
    rep = srv.deploy("chat", cfg.name, len(devices), max_batch=4, max_len=max_len)
    check(len(rep.placed) == len(devices), f"unplaced replicas {rep.pending}")
    for wid in rep.placed:
        srv.attach_engine(wid, srv.engine_factory("chat", cfg.name, wid))
    for wid, eng in srv.engines.items():
        dev = placed_on[wid]
        check(_devices_of(eng.params) == {dev}, f"{wid} params off {dev}")
        check(_devices_of(eng.cache) == {dev}, f"{wid} cache off {dev}")

    rng = np.random.default_rng(seed)
    prompts = _requests(rng, cfg.vocab_size, 4, "p")
    # round-robin routing: each prompt submitted once per replica reaches
    # every replica exactly once
    for r in prompts:
        for _ in devices:
            srv.submit("chat", dataclasses.replace(r))
    srv.pump()

    base = Engine(mb, params, ecfg, device=devices[0])
    for r in prompts:
        base.submit(dataclasses.replace(r))
    want = {c.rid: c.tokens for c in base.run()}
    lines = []
    for wid in sorted(srv.engines):
        got = {c.rid: c.tokens for c in srv.engines[wid].completed}
        check(got == want, f"{wid} on {placed_on[wid]} differs from {devices[0]}")
        lines.append(f"{wid}->device {placed_on[wid].id}")
    check(len({placed_on[w].id for w in srv.engines}) == len(devices),
          "replicas share a device")
    n_tok = sum(len(t) for t in want.values())
    return (f"{cfg.name} x{len(devices)} on distinct devices "
            f"({', '.join(lines)}); each served {len(prompts)} prompts, "
            f"{n_tok} greedy tokens equal to the device-{devices[0].id} run")


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the one-replica-per-chip phase, on 4 chips")
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    info = device_info()
    _line("device", time.perf_counter() - t0,
          f"platform={info['platform']} kind={info['kind']} count={info['count']}")
    if info["platform"] != "tpu":
        raise SystemExit(f"no TPU: JAX found platform {info['platform']!r}")
    if info["count"] < args.chips:
        raise SystemExit(f"--chips {args.chips} needs {args.chips} chips, "
                         f"found {info['count']}")
    enable_compile_cache()

    if args.chips == 4:
        phases = [("replicas x4", lambda: phase_replicas(
            devices=jax.devices()[:4]))]
    else:
        phases = [
            ("control plane", phase_control_plane),
            ("served smollm-135m", phase_served_smollm),
            ("served chatglm3-6b", phase_served_glm),
            ("kernels", phase_kernels),
        ]
    for name, fn in phases:
        t0 = time.perf_counter()
        detail = fn()
        _line(name, time.perf_counter() - t0, detail)
        # an Engine's jitted steps close over the engine: only the cycle
        # collector frees a finished phase's weights and caches on the device
        gc.collect()
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
