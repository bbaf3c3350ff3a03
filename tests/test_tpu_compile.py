"""The serve path's Pallas kernels compile for a TPU v5e at h2o-danube-1.8b
widths — no chip needed: the TPU compiler compiles for a described,
unattached v5e. Interpret-mode tests cannot see what these catch (casts
the TPU lowering refuses, blocks off the (8, 128) tiling, scoped-VMEM and
SMEM overflow).

The topology is described inside a module fixture, never at import: only
the worker that runs this file loads the TPU library."""
import math
import re

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
    """Both kernels read the layer-stacked, lane-dense pool at a layer
    index."""
    L, B, H, KV, hd, BS, NB, MAXB = 2, 8, 32, 8, 80, 16, 2048, 64
    q = ((B, H, hd), jnp.bfloat16)
    layer = ((), jnp.int32)
    tables = (((B, MAXB), jnp.int32), ((B,), jnp.int32))
    if not kv_bits:
        page = ((L, NB, BS, KV * hd), jnp.bfloat16)
        _compile(lambda q, k, v, l, bt, ln: paged_attention_pallas(
            q, k, v, l, bt, ln, window=4096), one_chip, q, page, page, layer,
            *tables)
        return
    cpb = 1 if kv_bits == 8 else 2
    page = ((L, NB, BS, KV * hd // cpb),
            jnp.int8 if kv_bits == 8 else jnp.uint8)
    scale = ((L, NB, KV), jnp.float32)
    _compile(lambda q, k, v, ks, vs, l, bt, ln: paged_attention_quant_pallas(
        q, k, v, ks, vs, l, bt, ln, window=4096, kv_bits=kv_bits), one_chip,
        q, page, page, scale, scale, layer, *tables)


# ops that move a whole array: none may produce a layer's pool or more
MOVES = ("copy", "copy-start", "slice", "dynamic-slice",
         "dynamic-update-slice")
# `%name = <shape or (tuple of shapes)> opcode(`; a copy-start's tuple
# leads with the array it copies into
_HLO_OP = re.compile(r"^\s*(?:ROOT )?%?([\w.-]+) = (\([^)]*\)|\S+) "
                     r"([\w-]+)\(")
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s32": 4,
          "u32": 4, "f32": 4}


def _pool_moves(hlo: str, layer_bytes: int):
    """Instructions, fused or not, of a MOVES opcode whose result holds
    at least `layer_bytes`."""
    out = []
    for line in hlo.splitlines():
        m = _HLO_OP.match(line)
        if m is None or m.group(3) not in MOVES:
            continue
        dtype, dims = _SHAPE.search(m.group(2)).groups()
        dims = [int(d) for d in dims.split(",") if d]
        if math.prod(dims) * _BYTES.get(dtype, 4) >= layer_bytes:
            out.append(f"{m.group(1)} {m.group(3)} {dtype}{dims}")
    return out


def test_pool_moves_reads_fused_and_async_ops():
    """The census sees a copy-start's tuple, a fused computation's root and
    plain ops, and only at or above the size asked for."""
    hlo = "\n".join([
        "  %copy-start.2 = (s8[2,64,8]{2,1,0}, s8[2,64,8]{2,1,0}, u32[])"
        " copy-start(%p)",
        "  ROOT %dynamic_slice.9 = bf16[1,64,16]{2,1,0} dynamic-slice(%a, %b)",
        "  %copy.3 = f32[4]{0} copy(%c)",
        "  %fusion.1 = bf16[2,64,16]{2,1,0} fusion(%d), kind=kLoop",
    ])
    assert _pool_moves(hlo, 1024) == ["copy-start.2 copy-start s8[2, 64, 8]",
                                      "dynamic_slice.9 dynamic-slice "
                                      "bf16[1, 64, 16]"]
    assert _pool_moves(hlo, 4096) == []


def _danube_pool_program(sharding, kv_bits, program):
    """The serve runtime's decode step or prefill write program at danube
    widths (2 layers, 64 slots, 128 pages a slot, the cell's 4096 pages of
    16), with the pool donated, compiled for the described chip. Returns
    (compiled HLO, one layer's pool bytes)."""
    from repro.configs import get_config
    from repro.models import BuildPlan
    from repro.models.model import decode_step_paged, init_params
    from repro.serve.kv_cache import init_paged_cache, write_prefill
    cfg = get_config("h2o-danube-1.8b").replace(n_layers=2, head_dim=80)
    plan = BuildPlan(remat=False, kv_bits=kv_bits, kernel_mode="pallas")
    B, MAXB, NB, BS, S = 64, 128, 4096, 16, 1024
    pool = jax.eval_shape(lambda: init_paged_cache(cfg, plan, NB, BS))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    pool_in = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), pool)
    if program == "decode":
        params = jax.eval_shape(
            lambda: init_params(jax.random.PRNGKey(0), cfg, plan))
        fn = jax.jit(lambda p, pool, bt, t, pos: decode_step_paged(
            p, cfg, plan, pool, bt, t, pos), donate_argnums=(1,))
        args = (jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype),
                                       params),
                pool_in, sds((B, MAXB), jnp.int32), sds((B, 1), jnp.int32),
                sds((B,), jnp.int32))
    else:
        kv_rows = sds((cfg.n_layers, S, cfg.n_kv_heads, 80), jnp.bfloat16)
        fn = jax.jit(lambda pool, k, v, pos, tbl: write_prefill(
            pool, k, v, pos, tbl, kv_bits=kv_bits), donate_argnums=(0,))
        args = (pool_in, kv_rows, kv_rows, sds((S,), jnp.int32),
                sds((MAXB,), jnp.int32))
    hlo = fn.lower(*args).compile().as_text()
    k = pool["k"]
    return hlo, math.prod(k.shape[1:]) * k.dtype.itemsize


@pytest.mark.parametrize("program", ["decode", "write"])
@pytest.mark.parametrize("kv_bits", [0, 8], ids=["bf16", "int8"])
def test_pool_is_read_and_written_in_place_for_v5e(one_chip, kv_bits,
                                                   program):
    """No copy, slice or update-slice of a layer's pool or more anywhere
    in the decode step or the prefill write: the pool rides the layer
    scan's carry, the kernels read it by layer index, and its lane-dense
    rows keep it in the layout they read. The decode step still holds its
    kernel by name."""
    hlo, layer_bytes = _danube_pool_program(one_chip, kv_bits, program)
    assert _pool_moves(hlo, layer_bytes) == []
    if program == "decode":
        calls = {line.split("=", 1)[0].split()[-1].lstrip("%")
                 .rsplit(".", 1)[0]
                 for line in hlo.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in line}
        assert ("paged_attention_quant" if kv_bits
                else "paged_attention") in calls


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
