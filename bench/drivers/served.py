"""Driver for a served model: replicas behind ``ClusterServer``, one per chip,
fed open-loop requests through ``submit`` and ``pump(max_steps=1)``.

Set-up makes the weights on the device from the seed with the
configuration's reference module, hands the same arrays to the system in its
parameter layout, places the replicas and warms every prefill bucket the
mix can use.  The window records, per request, its due time, the round that
admitted it and the time each of its tokens reached the host.  The check
compares a seeded sample of finished requests, the longest among them, with
the reference's logits.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List

import jax

import reference_lm as lm
import traffic_gen
from harness import load_module

from repro.configs import get_config
from repro.models import bundle
from repro.serving import Engine, EngineConfig, Request
from repro.serving.cluster import ClusterServer

MODEL = "served"
#: arrivals keep coming this long after the window, so that the counted
#: requests finish under the same load; none of them is counted.
TAIL_S = 60.0
#: a counted request not finished this long after the window has failed;
#: the longest answer (512 tokens) alone takes about a minute of rounds
#: when every slot decodes.
DRAIN_CAP_S = 120.0
#: finished requests compared with the reference, the longest among them.
N_COMPARED = 4


def program_params(w: dict) -> dict:
    """The reference layout's arrays in the system's parameter tree."""
    ly = w["layers"]
    p = {
        "embedding": w["embed"],
        "ln_f": {"scale": w["final_norm"]},
        "groups": [{
            "ln1": {"scale": ly["attn_norm"]},
            "ln2": {"scale": ly["mlp_norm"]},
            "attn": {k: ly[k] for k in ("wq", "wk", "wv", "wo")},
            "mlp": {"w_gate": ly["w_gate"], "w_up": ly["w_up"], "w_out": ly["w_down"]},
        }],
    }
    if "lm_head" in w:
        p["lm_head"] = w["lm_head"]
    return p


def _same_layout(got, want) -> None:
    g = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), got)
    w = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), want)
    if jax.tree.structure(g) != jax.tree.structure(w) or g != w:
        raise ValueError(f"weights do not fit the system's layout:\n{g}\n!=\n{w}")


@dataclasses.dataclass
class _Track:
    req: traffic_gen.Req
    submit: float = -1.0
    admit_start: float = -1.0
    deliveries: List[tuple] = dataclasses.field(default_factory=list)  # (t, n)
    n: int = 0
    done: bool = False


class Cell:
    def __init__(self, cfg: dict, mix: dict, seed: int, devices: list, root):
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.ref = load_module(root / cfg["reference"])
        self.shape = self.ref.shape(cfg)
        arch = dataclasses.replace(
            get_config(cfg["program_arch"]),
            dtype=cfg["serving"]["dtype"],
            **{prog: cfg[key] for key, prog in cfg["program_keys"].items()})
        mb = bundle(arch)
        self.weights = lm.make_weights(self.shape, seed)
        params = program_params(self.weights)
        _same_layout(params, mb.param_shapes())
        srv_cfg = cfg["serving"]
        self.max_len = srv_cfg["max_len"]
        ecfg = EngineConfig(max_slots=srv_cfg["max_slots"], max_len=self.max_len)
        placed: Dict[str, jax.Device] = {}

        def factory(model, arch_name, wid):
            placed[wid] = devices[len(placed)]
            return Engine(mb, params, ecfg, device=placed[wid])

        self.srv = ClusterServer(n_nodes=1, engine_factory=factory)
        rep = self.srv.deploy(MODEL, cfg["program_arch"], len(devices),
                              max_batch=srv_cfg["max_slots"], max_len=self.max_len)
        if len(rep.placed) != len(devices):
            raise RuntimeError(f"replicas left unplaced: {rep.pending}")
        for wid in rep.placed:
            self.srv.attach_engine(wid, factory(MODEL, arch.name, wid))
        self.engines = list(self.srv.engines.values())
        self._warm()

    def _warm(self) -> None:
        """Every prefill bucket of the mix, its insert and the decode step,
        on every replica; then forget what the warm-up served."""
        for b in traffic_gen.warmup_prompt_lengths(self.mix):
            for e in self.engines:
                e.submit(Request(rid=f"warm{b}", prompt=[1] * (b // 2 + 1),
                                 max_new_tokens=2))
            self.srv.pump()
        for e in self.engines:
            e.completed.clear()

    # ------------------------------------------------------------------ window
    def window(self, seconds: float, span, tracer) -> dict:
        reqs = traffic_gen.requests(self.mix, self.seed, seconds,
                                    self.shape.vocab, TAIL_S)
        tracks = {r.rid: _Track(r) for r in reqs}
        counted = [tracks[r.rid] for r in reqs if r.in_window]
        seen_done = [0] * len(self.engines)
        rounds = []  # (start, end, admitted, prefill FLOPs, decode FLOPs)
        late = []
        i = 0
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            tracer.tick(now)
            if i < len(reqs) and reqs[i].due_s <= now:
                with span("submit"):
                    while i < len(reqs) and reqs[i].due_s <= now:
                        r = reqs[i]
                        self.srv.submit(MODEL, Request(
                            rid=r.rid, prompt=list(r.prompt),
                            max_new_tokens=r.max_new_tokens))
                        tracks[r.rid].submit = now
                        late.append(now - r.due_s)
                        i += 1
            if now >= seconds and all(t.done for t in counted):
                break
            if now > seconds + DRAIN_CAP_S:
                break
            if any(e.has_work for e in self.engines):
                ta = time.perf_counter() - t0
                with span("pump"):
                    self.srv.pump(max_steps=1)
                tb = time.perf_counter() - t0
                flops = {"prefill": 0, "decode": 0}
                admitted = self._observe(tracks, seen_done, ta, tb, flops)
                rounds.append((ta, tb, admitted, flops["prefill"], flops["decode"]))
            elif i < len(reqs):
                with span("wait"):
                    time.sleep(max(0.0, reqs[i].due_s - (time.perf_counter() - t0)))
        self._counted = counted
        return {
            "kind": "served",
            "seconds": seconds,
            "requests": [{"rid": t.req.rid, "due": t.req.due_s, "submit": t.submit,
                          "admit_start": t.admit_start, "deliveries": t.deliveries,
                          "done": t.done} for t in counted],
            "rounds": rounds,
            "late_s": late,
            "attempted": len(counted),
            "failed": sum(not t.done for t in counted),
        }

    def _observe(self, tracks, seen_done, ta, tb, flops) -> int:
        """Tokens that reached the host in the round [ta, tb]."""
        admitted = 0
        for k, e in enumerate(self.engines):
            now_n = {st.req.rid: len(st.generated) for st in e.slots if st is not None}
            for c in e.completed[seen_done[k]:]:
                now_n[c.rid] = len(c.tokens)
                tracks[c.rid].done = True
            seen_done[k] = len(e.completed)
            for rid, n in now_n.items():
                t = tracks[rid]
                if n <= t.n:
                    continue
                plen = len(t.req.prompt)
                if t.n == 0:
                    t.admit_start = ta
                    admitted += 1
                    flops["prefill"] += lm.prefill_flops(self.shape, plen)
                for j in range(max(t.n + 1, 2), n + 1):
                    flops["decode"] += lm.decode_flops(self.shape, plen + j - 1)
                t.deliveries.append((tb, n - t.n))
                t.n = n
        return admitted

    # ------------------------------------------------------------------ check
    def check(self, rec: dict) -> Dict[str, tuple]:
        done = {c.rid: c.tokens for e in self.engines for c in e.completed}
        finished = [t.req for t in self._counted if t.done]
        unfinished = rec["failed"] + sum(
            len(done[r.rid]) != r.max_new_tokens
            or not all(0 <= tok < self.shape.vocab for tok in done[r.rid])
            for r in finished)
        longest = max(finished, key=lambda r: (len(r.prompt) + r.max_new_tokens, r.rid))
        rest = [r for r in finished if r is not longest]
        rng = traffic_gen.rng_for(self.seed, 9)
        pick = [longest] + [rest[j] for j in rng.permutation(len(rest))[:N_COMPARED - 1]]
        seqs = [(r.prompt, done[r.rid]) for r in pick]
        self.compared = seqs
        # free the system's state before the reference runs on the chip
        for e in self.engines:
            e.cache = None
        self.srv.engines.clear()
        self.engines = []
        self.srv = None
        gc.collect()
        gap_fn = lm.make_gap_fn(self.shape, self.ref.rope(self.cfg), quant=False)
        gap = lm.widest_gap(gap_fn, self.weights, seqs, self.max_len,
                            self.mix["output_len"]["max"])
        return {
            "logit_gap": (gap, self.cfg["correct_limits"]["logit_gap"]),
            "unfinished": (unfinished, 0),
        }
