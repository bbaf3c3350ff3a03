"""The serve path's Pallas kernels compile for a TPU v5e at h2o-danube-1.8b
widths — no chip needed: the TPU compiler compiles for a described,
unattached v5e. Interpret-mode tests cannot see what these catch (casts
the TPU lowering refuses, blocks off the (8, 128) tiling, scoped-VMEM and
SMEM overflow).

The topology is described inside a module fixture, never at import: only
the worker that runs this file loads the TPU library."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.paged_attention import (paged_attention_pallas,
                                           paged_attention_quant_pallas)
from repro.kernels.quant_matmul import quant_matmul_pallas

# danube: d_model 2560, 8 KV heads x head_dim 80, 32 query heads, d_ff 6912
LEAVES = {"wq": (2560, 2560), "wk": (2560, 640), "w_up": (2560, 6912),
          "w_down": (6912, 2560)}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    # described-chip compiles are written to the persistent cache but can
    # never be read back without a chip: keep the cache off around them
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()
    return compiled


@pytest.mark.parametrize("M", [8, 512], ids=["decode", "prefill"])
@pytest.mark.parametrize("cpb", [1, 2, 4])
@pytest.mark.parametrize("leaf", sorted(LEAVES))
def test_quant_matmul_compiles_for_v5e(one_chip, leaf, cpb, M):
    K, N = LEAVES[leaf]
    _compile(lambda x, u, s, z: quant_matmul_pallas(x, u, s, z, cpb=cpb),
             one_chip, ((M, K), jnp.float32), ((K, N // cpb), jnp.uint8),
             ((N,), jnp.float32), ((N,), jnp.float32))


@pytest.mark.parametrize("kv_bits", [0, 8, 4])
def test_paged_attention_compiles_for_v5e(one_chip, kv_bits):
    B, H, KV, hd, BS, NB, MAXB = 8, 32, 8, 80, 16, 2048, 64
    q = ((B, H, hd), jnp.bfloat16)
    tables = (((B, MAXB), jnp.int32), ((B,), jnp.int32))
    if not kv_bits:
        page = ((NB, BS, KV, hd), jnp.bfloat16)
        _compile(lambda q, k, v, bt, ln: paged_attention_pallas(
            q, k, v, bt, ln, window=4096), one_chip, q, page, page, *tables)
        return
    cpb = 1 if kv_bits == 8 else 2
    page = ((NB, BS, KV, hd // cpb), jnp.int8 if kv_bits == 8 else jnp.uint8)
    scale = ((NB, KV), jnp.float32)
    _compile(lambda q, k, v, ks, vs, bt, ln: paged_attention_quant_pallas(
        q, k, v, ks, vs, bt, ln, window=4096, kv_bits=kv_bits), one_chip,
        q, page, page, scale, scale, *tables)


def test_flash_attention_keeps_its_name_for_v5e(one_chip):
    """The kernel's call is named after it in the compiled program, so a
    profiler trace finds it by name."""
    q, kv = ((32, 512, 128), jnp.bfloat16), ((8, 512, 128), jnp.bfloat16)
    hlo = _compile(lambda q, k, v: flash_attention_pallas(q, k, v),
                   one_chip, q, kv, kv).as_text()
    calls = [line.split("=", 1)[0].split()[-1].lstrip("%")
             for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert [c.rsplit(".", 1)[0] for c in calls] == ["flash_attention"]
