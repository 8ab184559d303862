"""System tests: serving engine, KV-cache surgery, placement-integrated
cluster.  Models are reduced configs executing REAL forward passes on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.tpu_profiles import TPU_V5E_POD
from repro.models import bundle
from repro.serving import Engine, EngineConfig, Request
from repro.serving.cluster import ClusterServer, replica_profile
from repro.serving.kvcache import BlockAllocator, PagedKVCache, paged_decode_attention
from repro.kernels import ref as kref


def _mk(name, **over):
    cfg = reduced(get_config(name), capacity_factor=8.0, **over)
    mb = bundle(cfg)
    params = mb.init(jax.random.key(0))
    return mb, params


def _naive_generate(mb, params, prompt, n_new, extras=None):
    """Oracle: full forward over the growing sequence, greedy argmax."""
    toks = list(prompt)
    out = []
    for _ in range(n_new):
        batch = {"tokens": jnp.asarray([toks], jnp.int32), **(extras or {})}
        logits, _, _ = mb.model.forward(params, batch)
        nxt = int(jnp.argmax(logits[0, -1]))
        toks.append(nxt)
        out.append(nxt)
    return out


# ---------------------------------------------------------------------------
# engine == naive generation, across architecture families
# ---------------------------------------------------------------------------
ENGINE_ARCHS = [
    "smollm-135m",      # dense GQA
    "mixtral-8x7b",     # MoE + sliding-window ring cache
    "deepseek-v3-671b", # MLA latent cache
    "xlstm-125m",       # pure recurrent
    "zamba2-1.2b",      # hybrid mamba2 + shared attention
]


@pytest.mark.parametrize("name", ENGINE_ARCHS)
def test_engine_matches_naive_generation(name):
    mb, params = _mk(name)
    eng = Engine(mb, params, EngineConfig(max_slots=3, max_len=64))
    rng = np.random.default_rng(0)
    prompts = [list(map(int, rng.integers(1, 255, size=n))) for n in (5, 3, 7, 4)]
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=f"r{i}", prompt=p, max_new_tokens=5))
    done = {c.rid: c for c in eng.run()}
    assert len(done) == len(prompts)
    for i, p in enumerate(prompts):
        want = _naive_generate(mb, params, p, 5)
        got = done[f"r{i}"].tokens
        assert got == want, f"{name} r{i}: {got} != {want}"


@pytest.mark.parametrize("name,over", [
    ("smollm-135m", {}),                     # full cache: clamped write at max_len-1
    ("mixtral-8x7b", {"sliding_window": 16}),  # ring cache of 16: wraps twice
])
def test_engine_slot_reaching_max_len(name, over):
    """A request that fills its slot to max_len, next to a shorter one whose
    slot then idles past max_len, and a slot idle throughout: the carried
    decode cache writes every slot's token in place (clamped at max_len - 1,
    or wrapping the ring) and each request matches naive generation."""
    mb, params = _mk(name, **over)
    max_len = 32
    eng = Engine(mb, params, EngineConfig(max_slots=3, max_len=max_len))
    rng = np.random.default_rng(1)
    full = list(map(int, rng.integers(1, 255, size=6)))
    short = list(map(int, rng.integers(1, 255, size=16)))
    eng.submit(Request(rid="full", prompt=full, max_new_tokens=max_len - len(full)))
    eng.submit(Request(rid="short", prompt=short, max_new_tokens=3))
    done = {c.rid: c for c in eng.run()}
    assert eng._slot_indexes()[1] > max_len  # the short slot idled past the end
    assert len(done["full"].prompt) + len(done["full"].tokens) == max_len
    for rid, prompt, n in (("full", full, max_len - len(full)), ("short", short, 3)):
        assert done[rid].tokens == _naive_generate(mb, params, prompt, n), rid


def test_decode_writes_cache_in_place():
    """The ragged decode step reads each layer's K/V in place from the
    stacked cache and writes the new tokens into it in place.  Compiled for
    the CPU at smollm-135m's published widths in float32 with 8 slots of 512,
    it copies no buffer of a layer's or the stack's K/V size, and its
    temporaries hold no second cache: under a tenth of the K/V cache
    (188,743,680 B) beside what the CPU backend copies out of the stacks to
    hand one layer to its dot routines, that layer's weights (14,160,384 B)
    and K and V (6,291,456 B).  When the layer loop scanned the cache as
    xs/ys, temp was 210,165,304 B; now it is 20,876,040 B."""
    import dataclasses
    import re

    from repro.serving.engine import decode_program

    cfg = dataclasses.replace(get_config("smollm-135m"), dtype="float32")
    mb = bundle(cfg)
    slots, max_len = 8, 512
    cache = jax.eval_shape(lambda: mb.model.init_cache(slots, max_len, ragged=True))
    params = mb.param_shapes()
    i32 = jnp.int32
    compiled = decode_program(mb.model).lower(
        params, cache, jax.ShapeDtypeStruct((slots, 1), i32),
        jax.ShapeDtypeStruct((slots,), i32),
    ).compile()

    def nbytes(tree):
        return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))

    kv = [cache["groups"][0]["attn"][n] for n in ("k", "v")]
    kv_bytes = nbytes(kv)
    assert kv_bytes == 188_743_680
    one_layer = (nbytes(params["groups"]) + kv_bytes) // cfg.n_layers
    assert one_layer == 14_160_384 + 6_291_456
    kv_sizes = {kv[0].size, kv[0].size // cfg.n_layers}
    copies = []
    for line in compiled.as_text().splitlines():
        m = re.search(r"= \w+\[([\d,]*)\]\S* copy\(", line)
        if m and int(np.prod([int(d) for d in m.group(1).split(",") if d])) in kv_sizes:
            copies.append(line.strip()[:120])
    assert not copies, copies
    assert compiled.memory_analysis().temp_size_in_bytes < kv_bytes / 10 + one_layer


def test_engine_vlm_extras():
    """Pixtral: prefill with patch embeddings routed through extras."""
    mb, params = _mk("pixtral-12b")
    cfg = mb.cfg
    pe = jax.random.normal(
        jax.random.key(1), (1, cfg.frontend_len, cfg.frontend_dim), jnp.bfloat16
    )
    prompt = list(range(1, 9))
    eng = Engine(mb, params, EngineConfig(max_slots=2, max_len=64,
                                          bucket_prefill=False))
    eng.submit(Request(rid="v0", prompt=prompt, max_new_tokens=4,
                       extras={"patch_embeds": pe}))
    done = eng.run()
    want = _naive_generate(mb, params, prompt, 4, extras={"patch_embeds": pe})
    assert done[0].tokens == want


def test_engine_slot_reuse_and_stats():
    mb, params = _mk("smollm-135m")
    eng = Engine(mb, params, EngineConfig(max_slots=2, max_len=32))
    for i in range(5):
        eng.submit(Request(rid=f"q{i}", prompt=[1 + i, 2, 3], max_new_tokens=3))
    done = eng.run()
    assert len(done) == 5
    assert eng.stats["prefills"] == 5
    assert eng.n_active == 0 and not eng.queue
    # 5 requests through 2 slots => slots were recycled
    assert eng.stats["tokens"] == sum(len(c.tokens) for c in done)


def test_engine_eos_stops_early():
    mb, params = _mk("smollm-135m")
    # discover what token the model greedily emits, then use it as EOS
    probe = _naive_generate(mb, params, [5, 6, 7], 1)[0]
    eng = Engine(mb, params, EngineConfig(max_slots=1, max_len=32))
    eng.submit(Request(rid="e", prompt=[5, 6, 7], max_new_tokens=8, eos_id=probe))
    done = eng.run()
    assert done[0].finish_reason == "eos"
    assert done[0].tokens[-1] == probe and len(done[0].tokens) < 8


def test_ragged_equals_uniform_when_lengths_equal():
    """All slots at the same position: ragged decode == uniform decode_fn."""
    mb, params = _mk("smollm-135m")
    B, P = 3, 6
    toks = jax.random.randint(jax.random.key(2), (B, P), 1, 255)
    # uniform path
    logits_u, cache_u = mb.prefill_fn(params, {"tokens": toks}, max_len=32)
    nxt_u = jnp.argmax(logits_u[:, -1], -1)
    logits2_u, _ = mb.decode_fn(params, cache_u, nxt_u[:, None], jnp.int32(P))
    # ragged path
    from repro.serving.kvcache import insert_prefix

    cache_r = mb.model.init_cache(B, 32, ragged=True)
    for b in range(B):
        _, pref = mb.prefill_fn(params, {"tokens": toks[b:b + 1]}, max_len=32)
        cache_r = insert_prefix(cache_r, pref, jnp.int32(b), jnp.int32(P))
    lengths = jnp.full((B,), P, jnp.int32)
    logits2_r, _, _ = mb.model.forward(
        params, {"tokens": nxt_u[:, None]}, cache=cache_r,
        positions=lengths[:, None],
    )
    np.testing.assert_allclose(
        np.asarray(logits2_r, np.float32), np.asarray(logits2_u, np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_engine_int8_kv_cache():
    """int8-KV serving: generation matches fp within greedy-token agreement
    on a tiny model (quantization noise can flip rare near-ties, so compare
    the first decode step's logits instead of demanding token equality)."""
    from repro.models import layers as L

    mb, params = _mk("smollm-135m")
    prompt = [3, 1, 4, 1, 5]
    # fp reference step
    logits_fp, cache_fp = mb.prefill_fn(
        params, {"tokens": jnp.asarray([prompt], jnp.int32)}, max_len=32
    )
    nxt = jnp.argmax(logits_fp[0, -1])[None, None]
    step_fp, _ = mb.decode_fn(params, cache_fp, nxt, jnp.int32(len(prompt)))
    # int8-KV step
    L.set_kv_quant(True)
    try:
        logits_q8, cache_q8 = mb.prefill_fn(
            params, {"tokens": jnp.asarray([prompt], jnp.int32)}, max_len=32
        )
        assert cache_q8["groups"][0]["attn"]["k"].dtype == jnp.int8
        step_q8, _ = mb.decode_fn(params, cache_q8, nxt, jnp.int32(len(prompt)))
    finally:
        L.set_kv_quant(False)
    np.testing.assert_allclose(
        np.asarray(step_q8, np.float32), np.asarray(step_fp, np.float32),
        atol=0.15, rtol=0.15,
    )
    # and the full engine path still completes with a quantized cache
    L.set_kv_quant(True)
    try:
        eng = Engine(mb, params, EngineConfig(max_slots=2, max_len=32))
        eng.submit(Request(rid="q", prompt=prompt, max_new_tokens=4))
        done = eng.run()
    finally:
        L.set_kv_quant(False)
    assert len(done) == 1 and len(done[0].tokens) == 4


# ---------------------------------------------------------------------------
# paged KV cache
# ---------------------------------------------------------------------------
def test_block_allocator_roundtrip():
    a = BlockAllocator(8)
    t0 = a.allocate(0, 3)
    t1 = a.allocate(1, 2)
    assert len(set(t0) | set(t1)) == 5 and a.n_free == 3
    a.free(0)
    assert a.n_free == 6
    t2 = a.allocate(2, 6)
    assert len(set(t2) | set(t1)) == 8 and a.n_free == 0
    with pytest.raises(MemoryError):
        a.allocate(3, 1)


def test_paged_decode_matches_contiguous():
    """Paged gather + ragged mask == contiguous decode attention oracle."""
    key = jax.random.key(3)
    B, H, HKV, D, BS, NB = 2, 4, 2, 16, 4, 8  # pool: 8 blocks of 4 tokens
    max_blocks = 4
    cache = PagedKVCache.create(NB, BS, HKV, D, jnp.float32)
    alloc = BlockAllocator(NB)
    lengths = [13, 7]
    kv = {}
    for b, L in enumerate(lengths):
        n_blocks = -(-L // BS)
        alloc.allocate(b, n_blocks)
        ks = jax.random.normal(jax.random.fold_in(key, b), (L, HKV, D))
        vs = jax.random.normal(jax.random.fold_in(key, 10 + b), (L, HKV, D))
        kv[b] = (ks, vs)
        for t in range(L):
            blk = alloc.table(b)[t // BS]
            cache = cache.append(jnp.int32(blk), jnp.int32(t % BS), ks[t], vs[t])
    tables = np.zeros((B, max_blocks), np.int32)
    for b in range(B):
        tb = alloc.table(b)
        tables[b, : len(tb)] = tb
    q = jax.random.normal(jax.random.fold_in(key, 99), (B, 1, H, D))
    got = paged_decode_attention(
        q, cache, jnp.asarray(tables), jnp.asarray(lengths, jnp.int32)
    )
    # contiguous oracle, one sequence at a time
    for b, L in enumerate(lengths):
        ks, vs = kv[b]
        want = kref.decode_attention_ref(
            q[b:b + 1], jnp.moveaxis(ks, 0, -1)[None], jnp.moveaxis(vs, 0, -1)[None],
            length=L,
        )
        np.testing.assert_allclose(
            np.asarray(got[b:b + 1]), np.asarray(want), rtol=1e-5, atol=1e-5
        )


def test_ragged_decode_attention_vector_length():
    """(B,) lengths mask each row independently (ref oracle property)."""
    key = jax.random.key(4)
    B, S, H, D = 3, 16, 2, 8
    q = jax.random.normal(key, (B, 1, H, D))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, H, D, S))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, H, D, S))
    lens = jnp.asarray([4, 16, 9], jnp.int32)
    got = kref.decode_attention_ref(q, k, v, length=lens)
    for b in range(B):
        want = kref.decode_attention_ref(
            q[b:b + 1], k[b:b + 1], v[b:b + 1], length=int(lens[b])
        )
        np.testing.assert_allclose(np.asarray(got[b:b + 1]), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# placement-integrated cluster
# ---------------------------------------------------------------------------
def test_replica_profile_scales_with_arch():
    small = replica_profile("smollm-135m", max_batch=4, max_len=2048)
    big = replica_profile("deepseek-v3-671b", max_batch=4, max_len=2048)
    assert small.memory_slices < big.memory_slices
    assert big.memory_slices * TPU_V5E_POD.mem_per_slice_gb >= 1340  # > 671B bf16


@pytest.mark.parametrize("policy", ["heuristic", "mip", "first_fit", "load_balanced"])
def test_cluster_deploy_policies(policy):
    srv = ClusterServer(n_nodes=4, policy=policy)
    rep = srv.deploy("chat", "smollm-135m", n_replicas=6, max_batch=4, max_len=2048)
    assert len(rep.placed) == 6 and not rep.pending
    srv.state.validate()
    assert srv.metrics().n_gpus >= 1


def test_cluster_compaction_saves_nodes():
    srv = ClusterServer(n_nodes=6, policy="heuristic")
    # fragment the cluster: deploy then retire interleaved replicas
    srv.deploy("a", "smollm-135m", 8, profile_id=3)   # 2-row blocks
    srv.deploy("b", "smollm-135m", 4, profile_id=4)   # 1-row blocks
    srv.retire("a", 6)
    frag = srv.metrics()
    report = srv.compact()
    srv.state.validate()
    assert report.after.n_gpus <= frag.n_gpus
    assert report.plan.n_moves >= 0  # plan is executable
    # every surviving replica still placed exactly once
    for wid in srv.replicas:
        assert srv.state.gpu_of(wid) is not None


def test_cluster_reconfigure_eviction_retires_ghosts():
    """A committed reconfigure that cannot re-place a replica must retire it
    from every server-side map (no ghost in routing/engines/footprints)."""
    from repro.core.state import Workload

    srv = ClusterServer(n_nodes=4, policy="heuristic")
    srv.deploy("m", "smollm-135m", 3, profile_id=4)
    victim = sorted(srv.replicas)[0]
    srv.attach_engine(victim, object())

    real = srv.engine.reconfigure

    def evicting(state):
        res = real(state)
        gid = state.gpu_of(victim)
        state.gpus[gid].remove(victim)  # the replay "failed" to re-place it
        res.pending = [Workload(victim, 4, model="m")]
        return res

    srv.engine.reconfigure = evicting
    rep = srv.reconfigure()
    assert rep.evicted == [victim]
    assert victim not in srv.replicas
    assert victim not in srv.engines
    assert victim not in srv.state.workloads
    assert victim not in srv.replicas_of("m")
    srv.state.validate()


def test_cluster_reconfigure_and_route():
    srv = ClusterServer(n_nodes=8, policy="heuristic")
    srv.deploy("m", "smollm-135m", 5, profile_id=4)
    rep = srv.reconfigure()
    assert rep.after.n_gpus <= rep.before.n_gpus
    picks = [srv.route("m") for _ in range(10)]
    assert len(set(picks)) == len(srv.replicas_of("m"))  # round robin covers all


def test_cluster_end_to_end_serving():
    """Deploy 2 models, attach real engines, route + pump to completion."""
    srv = ClusterServer(n_nodes=2, policy="heuristic")
    mb1, p1 = _mk("smollm-135m")
    mb2, p2 = _mk("xlstm-125m")
    srv.deploy("chat", "smollm-135m", 2, profile_id=4)
    srv.deploy("draft", "xlstm-125m", 1, profile_id=4)
    for wid in srv.replicas_of("chat"):
        srv.attach_engine(wid, Engine(mb1, p1, EngineConfig(max_slots=2, max_len=32)))
    for wid in srv.replicas_of("draft"):
        srv.attach_engine(wid, Engine(mb2, p2, EngineConfig(max_slots=2, max_len=32)))
    for i in range(4):
        srv.submit("chat", Request(rid=f"c{i}", prompt=[1, 2, 3 + i], max_new_tokens=3))
    srv.submit("draft", Request(rid="d0", prompt=[9, 8], max_new_tokens=3))
    total = srv.pump()
    done = [c for e in srv.engines.values() for c in e.completed]
    assert len(done) == 5
    assert total == sum(len(c.tokens) for c in done)
    # placement metrics still coherent after serving
    srv.state.validate()
