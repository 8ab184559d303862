"""Per-kernel shape/dtype sweeps: pallas(interpret=True) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas
from repro.kernels import ops as kops


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,hq,hkv,d,bq,bk",
    [
        (1, 128, 4, 4, 64, 64, 64),  # MHA
        (2, 256, 8, 2, 64, 128, 64),  # GQA 4:1
        (1, 256, 6, 1, 32, 64, 128),  # MQA, uneven blocks
        (2, 128, 4, 2, 80, 128, 128),  # non-128 head dim (MLA-ish)
    ],
)
def test_flash_attention_sweep(dtype, b, s, hq, hkv, d, bq, bk):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, s, hq, d)).astype(dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, d)).astype(dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, d)).astype(dtype)
    got = flash_attention_pallas(q, k, v, causal=True, block_q=bq, block_k=bk, interpret=True)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("window", [32, 100, 256])
def test_flash_attention_sliding_window(window):
    ks = jax.random.split(jax.random.key(1), 3)
    b, s, hq, hkv, d = 2, 256, 4, 2, 64
    q = jax.random.normal(ks[0], (b, s, hq, d))
    k = jax.random.normal(ks[1], (b, s, hkv, d))
    v = jax.random.normal(ks[2], (b, s, hkv, d))
    got = flash_attention_pallas(
        q, k, v, causal=True, sliding_window=window, block_q=64, block_k=64, interpret=True
    )
    want = ref.attention_ref(q, k, v, causal=True, sliding_window=window)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_flash_attention_noncausal():
    ks = jax.random.split(jax.random.key(2), 3)
    b, s, h, d = 1, 128, 4, 64
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, h, d))
    v = jax.random.normal(ks[2], (b, s, h, d))
    got = flash_attention_pallas(q, k, v, causal=False, block_q=64, block_k=64, interpret=True)
    want = ref.attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,smax,hq,hkv,d,length,bk",
    [
        (2, 256, 8, 2, 64, 137, 64),
        (1, 512, 4, 4, 64, 512, 128),  # full cache
        (3, 128, 4, 1, 32, 1, 64),  # single valid slot
        (2, 256, 16, 2, 64, 200, 256),  # big GQA group, one block
    ],
)
def test_decode_attention_sweep(dtype, b, smax, hq, hkv, d, length, bk):
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (b, 1, hq, d)).astype(dtype)
    k = jax.random.normal(ks[1], (b, hkv, d, smax)).astype(dtype)
    v = jax.random.normal(ks[2], (b, hkv, d, smax)).astype(dtype)
    ln = jnp.array(length, jnp.int32)
    got = decode_attention_pallas(q, k, v, length=ln, block_k=bk, interpret=True)
    want = ref.decode_attention_ref(q, k, v, length=ln)
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), **_tol(dtype)
    )


@pytest.mark.parametrize(
    "b,smax,hq,hkv,d,length,bk",
    [
        (2, 256, 8, 2, 64, 137, 64),
        (1, 512, 4, 4, 64, 512, 128),
        (2, 256, 16, 2, 64, 200, 256),
    ],
)
def test_decode_attention_q8_sweep(b, smax, hq, hkv, d, length, bk):
    """int8-KV kernel == int8-KV oracle, and both track fp attention."""
    from repro.kernels.decode_attention import decode_attention_q8_pallas

    ks = jax.random.split(jax.random.key(8), 3)
    q = jax.random.normal(ks[0], (b, 1, hq, d))
    k = jax.random.normal(ks[1], (b, hkv, d, smax))
    v = jax.random.normal(ks[2], (b, hkv, d, smax))
    kq, ksc = ref.quantize_kv(k, axis=-2)
    vq, vsc = ref.quantize_kv(v, axis=-2)
    ln = jnp.array(length, jnp.int32)
    got = decode_attention_q8_pallas(q, kq, ksc, vq, vsc, length=ln,
                                     block_k=bk, interpret=True)
    want = ref.decode_attention_q8_ref(q, kq, ksc, vq, vsc, length=ln)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    # quantization error vs full-precision attention stays small
    fp = ref.decode_attention_ref(q, k, v, length=ln)
    err = float(jnp.max(jnp.abs(got - fp)))
    assert err < 0.05, f"int8 KV error too large: {err}"


def test_decode_attention_q8_ragged():
    from repro.kernels.decode_attention import decode_attention_q8_pallas

    ks = jax.random.split(jax.random.key(9), 3)
    b, smax, hq, hkv, d = 3, 256, 8, 2, 64
    q = jax.random.normal(ks[0], (b, 1, hq, d))
    k = jax.random.normal(ks[1], (b, hkv, d, smax))
    v = jax.random.normal(ks[2], (b, hkv, d, smax))
    kq, ksc = ref.quantize_kv(k, axis=-2)
    vq, vsc = ref.quantize_kv(v, axis=-2)
    lens = jnp.asarray([7, 256, 100], jnp.int32)
    got = decode_attention_q8_pallas(q, kq, ksc, vq, vsc, length=lens,
                                     block_k=64, interpret=True)
    want = ref.decode_attention_q8_ref(q, kq, ksc, vq, vsc, length=lens)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("quant", [False, True])
@pytest.mark.parametrize("mode", ["jnp", "pallas"])
def test_decode_attention_reads_layer_of_stack(mode, quant):
    """Given ``layer``, decode attention over a (L, B, Hkv, D, S) stack equals
    attention over that layer alone, in both kernel modes."""
    ks = jax.random.split(jax.random.key(12), 3)
    n_layers, b, smax, hq, hkv, d = 3, 2, 256, 8, 2, 64
    q = jax.random.normal(ks[0], (b, 1, hq, d))
    k = jax.random.normal(ks[1], (n_layers, b, hkv, d, smax))
    v = jax.random.normal(ks[2], (n_layers, b, hkv, d, smax))
    lens = jnp.asarray([77, 256], jnp.int32)
    layer = jnp.int32(1)
    try:
        kops.set_impl(mode, interpret=True)
        if quant:
            (kq, ksc), (vq, vsc) = ref.quantize_kv(k, axis=-2), ref.quantize_kv(v, axis=-2)
            got = kops.decode_attention_q8(q, kq, ksc, vq, vsc, lens, layer=layer)
            want = ref.decode_attention_q8_ref(q, kq[1], ksc[1], vq[1], vsc[1], lens)
        else:
            got = kops.decode_attention(q, k, v, lens, layer=layer)
            want = ref.decode_attention_ref(q, k[1], v[1], lens)
    finally:
        kops.set_impl("jnp")
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("quant", [False, True])
@pytest.mark.parametrize("mode", ["jnp", "pallas"])
def test_decode_attention_new_token_equals_written(mode, quant):
    """A token not yet in the cache, attended to in place of slot ``pos``,
    equals writing it at ``pos`` first and attending to one more slot: a
    fresh slot, the ring's oldest slot once wrapped, and the clamped last
    slot of a full cache."""
    ks = jax.random.split(jax.random.key(13), 5)
    b, smax, hq, hkv, d = 3, 256, 8, 2, 64
    q = jax.random.normal(ks[0], (b, 1, hq, d))
    k = jax.random.normal(ks[1], (b, hkv, d, smax))
    v = jax.random.normal(ks[2], (b, hkv, d, smax))
    k_new = jax.random.normal(ks[3], (b, hkv, d))
    v_new = jax.random.normal(ks[4], (b, hkv, d))
    lens = jnp.asarray([77, 300, 256], jnp.int32)  # old tokens per slot
    pos = jnp.asarray([77, 300 % smax, smax - 1], jnp.int32)
    written = [x.at[jnp.arange(b), :, :, pos].set(n) for x, n in ((k, k_new), (v, v_new))]
    try:
        kops.set_impl(mode, interpret=True)
        if quant:
            (kq, ksc), (vq, vsc) = (ref.quantize_kv(x, axis=-2) for x in written)
            deq = [(xq[jnp.arange(b), :, :, pos] * xs[jnp.arange(b), :, pos][..., None])
                   for xq, xs in ((kq, ksc), (vq, vsc))]
            got = kops.decode_attention_q8(q, kq, ksc, vq, vsc, lens, new=(*deq, pos))
            want = ref.decode_attention_q8_ref(q, kq, ksc, vq, vsc, lens + 1)
        else:
            got = kops.decode_attention(q, k, v, lens, new=(k_new, v_new, pos))
            want = ref.decode_attention_ref(q, *written, lens + 1)
    finally:
        kops.set_impl("jnp")
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_decode_attention_ragged_pallas():
    """fp ragged decode: per-slot lengths, pallas vs oracle."""
    ks = jax.random.split(jax.random.key(10), 3)
    b, smax, hq, hkv, d = 4, 256, 8, 2, 64
    q = jax.random.normal(ks[0], (b, 1, hq, d))
    k = jax.random.normal(ks[1], (b, hkv, d, smax))
    v = jax.random.normal(ks[2], (b, hkv, d, smax))
    lens = jnp.asarray([1, 64, 137, 256], jnp.int32)
    got = decode_attention_pallas(q, k, v, length=lens, block_k=64, interpret=True)
    want = ref.decode_attention_ref(q, k, v, length=lens)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,h,p,n,chunk",
    [
        (1, 128, 2, 16, 8, 32),
        (2, 256, 4, 32, 16, 64),
        (1, 64, 8, 8, 64, 64),  # single chunk
    ],
)
def test_ssd_scan_sweep(dtype, b, s, h, p, n, chunk):
    ks = jax.random.split(jax.random.key(4), 5)
    x = (jax.random.normal(ks[0], (b, s, h, p)) * 0.5).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))).astype(jnp.float32)
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    Bm = (jax.random.normal(ks[3], (b, s, n)) * 0.5).astype(dtype)
    Cm = (jax.random.normal(ks[4], (b, s, n)) * 0.5).astype(dtype)
    y1, h1 = ssd_scan_pallas(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    y2, h2 = ref.ssd_scan_ref(x, dt, A, Bm, Cm)
    tol = dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 else dict(atol=5e-5, rtol=5e-4)
    np.testing.assert_allclose(y1.astype(jnp.float32), y2.astype(jnp.float32), **tol)
    np.testing.assert_allclose(h1, h2, **tol)


def test_ssd_scan_initial_state_chain():
    """Running two halves with carried state == running the whole sequence."""
    ks = jax.random.split(jax.random.key(5), 5)
    b, s, h, p, n = 1, 128, 2, 8, 8
    x = jax.random.normal(ks[0], (b, s, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    Bm = jax.random.normal(ks[3], (b, s, n)) * 0.5
    Cm = jax.random.normal(ks[4], (b, s, n)) * 0.5
    y_full, h_full = ref.ssd_scan_ref(x, dt, A, Bm, Cm)
    half = s // 2
    y1, h1 = ssd_scan_pallas(
        x[:, :half], dt[:, :half], A, Bm[:, :half], Cm[:, :half], chunk=32, interpret=True
    )
    y2, h2 = ssd_scan_pallas(
        x[:, half:], dt[:, half:], A, Bm[:, half:], Cm[:, half:],
        chunk=32, initial_state=h1, interpret=True,
    )
    np.testing.assert_allclose(
        jnp.concatenate([y1, y2], 1), y_full, atol=5e-5, rtol=5e-4
    )
    np.testing.assert_allclose(h2, h_full, atol=5e-5, rtol=5e-4)


# ---- ops.py dispatch layer (jnp fast paths vs oracle) -----------------------
def test_chunked_attention_matches_ref():
    ks = jax.random.split(jax.random.key(6), 3)
    b, s, hq, hkv, d = 2, 1024, 4, 2, 32
    q = jax.random.normal(ks[0], (b, s, hq, d))
    k = jax.random.normal(ks[1], (b, s, hkv, d))
    v = jax.random.normal(ks[2], (b, s, hkv, d))
    kops.set_impl("jnp")
    got = kops.flash_attention(q, k, v, causal=True, q_chunk=256)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_chunked_ssd_matches_ref():
    ks = jax.random.split(jax.random.key(7), 5)
    b, s, h, p, n = 1, 512, 2, 8, 8
    x = jax.random.normal(ks[0], (b, s, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    Bm = jax.random.normal(ks[3], (b, s, n)) * 0.5
    Cm = jax.random.normal(ks[4], (b, s, n)) * 0.5
    kops.set_impl("jnp")
    y1, h1 = kops.ssd_scan(x, dt, A, Bm, Cm, chunk=128)
    y2, h2 = ref.ssd_scan_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(y1, y2, atol=5e-5, rtol=5e-4)
    np.testing.assert_allclose(h1, h2, atol=5e-5, rtol=5e-4)


def test_pallas_impl_through_ops():
    """ops dispatch honors set_impl('pallas', interpret=True)."""
    ks = jax.random.split(jax.random.key(8), 3)
    b, s, hq, hkv, d = 1, 128, 4, 2, 64
    q = jax.random.normal(ks[0], (b, s, hq, d))
    k = jax.random.normal(ks[1], (b, s, hkv, d))
    v = jax.random.normal(ks[2], (b, s, hkv, d))
    try:
        kops.set_impl("pallas", interpret=True)
        got = kops.flash_attention(q, k, v, causal=True)
        want = ref.attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    finally:
        kops.set_impl("jnp")


@pytest.mark.parametrize("mode", ["jnp", "pallas"])
def test_decode_attention_refuses_window_narrower_than_cache(mode):
    """Decode attends to every valid slot, so a sliding window narrower than
    the cache is refused; a cache of at most one window is exact."""
    ks = jax.random.split(jax.random.key(11), 3)
    q = jax.random.normal(ks[0], (2, 1, 4, 32))
    k = jax.random.normal(ks[1], (2, 2, 32, 64))
    v = jax.random.normal(ks[2], (2, 2, 32, 64))
    ln = jnp.asarray([10, 64], jnp.int32)
    try:
        kops.set_impl(mode, interpret=True)
        with pytest.raises(ValueError, match="sliding window"):
            kops.decode_attention(q, k, v, ln, sliding_window=32)
        got = kops.decode_attention(q, k, v, ln, sliding_window=64)
    finally:
        kops.set_impl("jnp")
    want = ref.decode_attention_ref(q, k, v, ln)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
