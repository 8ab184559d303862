"""The main-path Pallas kernels compile for a TPU v5e at real model widths.

Nothing runs: each kernel is lowered and compiled for one chip of a
described (not attached) ``v5e:2x2`` topology, which applies the TPU
compiler's tiling and memory rules that interpret mode does not.  The
topology is described inside a fixture, never at import, so every test
worker collects the same tests and only the worker running this file loads
the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import (
    decode_attention_pallas,
    decode_attention_q8_pallas,
)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas
from repro.models.ssm import mamba_dims

BF16, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32
SLOTS, CACHE, SEQ = 4, 2048, 1024


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries compiled for a described chip cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _attn_widths(arch):
    cfg = get_config(arch)
    return cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_


@pytest.mark.parametrize("arch", ["smollm-135m", "chatglm3-6b"])
def test_flash_attention_compiles(one_chip, arch):
    hq, hkv, d = _attn_widths(arch)
    text = _compiled_text(
        flash_attention_pallas, one_chip,
        ((1, SEQ, hq, d), BF16), ((1, SEQ, hkv, d), BF16), ((1, SEQ, hkv, d), BF16),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("arch", ["smollm-135m", "chatglm3-6b"])
def test_decode_attention_compiles(one_chip, arch):
    hq, hkv, d = _attn_widths(arch)
    text = _compiled_text(
        decode_attention_pallas, one_chip,
        ((SLOTS, 1, hq, d), BF16), ((SLOTS, hkv, d, CACHE), BF16),
        ((SLOTS, hkv, d, CACHE), BF16), ((SLOTS,), I32),
    )
    assert "tpu_custom_call" in text


def test_decode_attention_layer_of_stack_compiles(one_chip):
    """As the decode loop calls it: the kernel reads a layer's tiles straight
    from the whole (L, B, Hkv, D, S) stack through prefetched block indices,
    and attends to this step's token in place of the slot it will take."""
    hq, hkv, d = _attn_widths("smollm-135m")
    stack = (get_config("smollm-135m").n_layers, SLOTS, hkv, d, CACHE)
    text = _compiled_text(
        lambda q, k, v, lens, layer, kn, vn: decode_attention_pallas(
            q, k, v, lens, layer=layer, new=(kn, vn, lens)),
        one_chip,
        ((SLOTS, 1, hq, d), BF16), (stack, BF16), (stack, BF16), ((SLOTS,), I32),
        ((), I32), ((SLOTS, hkv, d), BF16), ((SLOTS, hkv, d), BF16),
    )
    assert "tpu_custom_call" in text


def test_served_decode_writes_cache_in_place(one_chip):
    """smollm-chat's decode step (smollm-135m in bf16, 128 slots of 2048)
    compiles for the chip with no copy of a buffer of a layer's or the
    stack's K/V size, and with temporaries under a tenth of the cache: the
    layer loop reads each layer in place and the new tokens are written in
    place.  When the loop scanned the cache as xs/ys, XLA relaid out every
    layer's K/V four times a step and the whole stacks twice (temp
    7,114,367,488 B); now temp is 577,024 B."""
    import dataclasses
    import re

    import numpy as np

    from repro.models import bundle
    from repro.serving.engine import decode_program

    cfg = dataclasses.replace(get_config("smollm-135m"), dtype="bfloat16")
    mb = bundle(cfg)
    slots, max_len = 128, 2048

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    cache = jax.tree.map(on_chip, jax.eval_shape(
        lambda: mb.model.init_cache(slots, max_len, ragged=True)))
    compiled = decode_program(mb.model).lower(
        jax.tree.map(on_chip, mb.param_shapes()), cache,
        on_chip(jax.ShapeDtypeStruct((slots, 1), I32)),
        on_chip(jax.ShapeDtypeStruct((slots,), I32)),
    ).compile()
    k = cache["groups"][0]["attn"]["k"]
    kv_sizes = {k.size, k.size // cfg.n_layers}
    copies = [
        line.strip()[:120] for line in compiled.as_text().splitlines()
        if (m := re.search(r"= \w+\[([\d,]*)\]\S* copy\(", line))
        and int(np.prod([int(x) for x in m.group(1).split(",") if x])) in kv_sizes
    ]
    assert not copies, copies
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * k.size * 2 / 10


def test_decode_attention_q8_compiles(one_chip):
    hq, hkv, d = _attn_widths("chatglm3-6b")
    text = _compiled_text(
        decode_attention_q8_pallas, one_chip,
        ((SLOTS, 1, hq, d), BF16),
        ((SLOTS, hkv, d, CACHE), I8), ((SLOTS, hkv, CACHE), F32),
        ((SLOTS, hkv, d, CACHE), I8), ((SLOTS, hkv, CACHE), F32),
        ((SLOTS,), I32),
    )
    assert "tpu_custom_call" in text


def test_ssd_scan_compiles(one_chip):
    cfg = get_config("zamba2-1.2b")
    _, h, p, n = mamba_dims(cfg)
    assert (h, p, n, cfg.ssm_chunk) == (32, 128, 64, 256)
    text = _compiled_text(
        lambda x, dt, a, b, c: ssd_scan_pallas(x, dt, a, b, c, chunk=cfg.ssm_chunk),
        one_chip,
        ((1, SEQ, h, p), BF16), ((1, SEQ, h), F32), ((h,), F32),
        ((1, SEQ, n), BF16), ((1, SEQ, n), BF16),
    )
    assert "tpu_custom_call" in text
