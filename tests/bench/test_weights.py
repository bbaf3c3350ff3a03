"""Weights from the seed: the tree the program serves is what its own
`serving_params` makes of the drawn codes, and the reference regenerates
every layer bit for bit."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.reference import dense_gqa
from conftest import TINY_CONFIG

DM = weights.dims(TINY_CONFIG["model"])


@pytest.fixture(scope="module")
def tree():
    from bench.drivers.serve_offline import model_config
    return weights.program_params(2**33 + 5, DM, 0.5,
                                  model_config(TINY_CONFIG))


def test_layers_regenerate_bit_for_bit(tree):
    """The program's own dequantization of the served tree is the
    reference's reading of each regenerated layer, bit for bit."""
    key = weights.seed_key(2**33 + 5)
    for l in range(DM["n_layers"]):
        leaves, ln1, ln2 = weights.layer_arrays(key, l, DM, 0.5)
        for name, leaf in leaves.items():
            qt = tree["layers"][weights.LEAVES[name][0]][name]
            got = np.asarray(qt.dequant(jnp.float32)[l])
            want = np.asarray(dense_gqa.dequant(*leaf))
            assert np.array_equal(got.reshape(want.shape), want)
        assert np.array_equal(tree["layers"]["ln1"]["scale"][l], ln1)
        assert np.array_equal(tree["layers"]["ln2"]["scale"][l], ln2)


def test_tree_is_what_serving_params_makes(tree):
    """The tree is the program's packed serving form: every projection a
    4-bit QT stacked over the layers at its logical shape, its codes
    taking half a byte each, as `program_bytes` counts them."""
    from repro.core.apply import QT
    codes = 0
    for mod in ("attn", "mlp"):
        for name, qt in tree["layers"][mod].items():
            assert isinstance(qt, QT) and qt.bits == 4
            assert qt.shape == (DM["n_layers"],
                                *weights.logical_shape(name, DM))
            assert qt.codes.dtype == jnp.uint8
            codes += qt.codes.size
    assert codes == weights.program_bytes(DM)["codes"]


def test_scale_targets():
    key = weights.seed_key(3)
    leaves, _, _ = weights.layer_arrays(key, 0, DM, 0.5)
    w = dense_gqa.dequant(*leaves["wq"])
    assert float(jnp.std(w)) == pytest.approx(DM["d_model"] ** -0.5,
                                              rel=0.15)
    w = dense_gqa.dequant(*leaves["w_down"])
    assert float(jnp.std(w)) == pytest.approx(0.5 * DM["d_ff"] ** -0.5,
                                              rel=0.15)
