"""The benchmark's operation and byte counts against hand-worked danube
shapes (24 layers, d 2560, 32 heads over 8 KV heads of head_dim 80,
d_ff 6912, vocab 32000)."""
import json

import pytest

from bench import counts, weights
from bench.spec import BENCH_DIR

DANUBE = weights.dims(json.loads(
    (BENCH_DIR / "configs/h2o-danube-1.8b.json").read_text())["model"])


def test_danube_widths():
    assert DANUBE == {"d_model": 2560, "n_heads": 32, "n_kv": 8,
                      "head_dim": 80, "q_dim": 2560, "kv_dim": 640,
                      "d_ff": 6912, "vocab": 32000, "n_layers": 24}


def test_kv_bytes_per_token_at_logical_width():
    # K and V, 24 layers, 8 KV heads, head_dim 80 (not the 128-lane
    # layout), bf16: 2 * 24 * 8 * 80 * 2
    assert counts.kv_bytes_per_token(DANUBE) == 61_440
    assert counts.kv_bytes_per_token(DANUBE, kv_bits=8) == 30_720


def test_code_bytes():
    # per layer: wq 2560*2560, wk/wv 2560*640, wo 2560*2560, three MLP
    # leaves 2560*6912 -> 69,468,160 weights; 24 layers at half a byte
    assert counts.weight_codes_bytes(DANUBE) == 833_617_920
    b = weights.program_bytes(DANUBE)
    assert b["codes"] == 833_617_920
    # the packed checkpoint the quantizer writes for danube holds the same
    # tree: codes, per-column scale + zero, norms, f32 embed/unembed
    assert sum(b.values()) == 1_493_854_208


@pytest.mark.parametrize("m,k,n", [(64, 2560, 640), (1, 6912, 2560)])
def test_quant_matmul_call(m, k, n):
    f, b = counts.quant_matmul_call(m, k, n)
    assert f == 2 * m * k * n
    assert b == k * n / 2 + 8 * n + 2 * (m * k + m * n)


def test_paged_attention_step():
    # two slots at contexts 100 and 300: 400 live tokens
    f, b = counts.paged_attention_step(DANUBE, [100, 300])
    assert f == 4 * 24 * 32 * 80 * 400
    assert b == 61_440 * 400 + 24 * 2 * 2 * 32 * 80 * 2


def test_decode_token_flops():
    per_layer = 69_468_160
    f = counts.decode_token_flops(DANUBE, 0)
    assert f == 2 * (24 * per_layer + 2560 * 32000)
    assert (counts.decode_token_flops(DANUBE, 10) - f
            == 4 * 24 * 32 * 80 * 10)
