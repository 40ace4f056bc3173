"""``tools/precision_control.py``'s rounding: matrices move by a few percent
(3 stored bits of mantissa), vectors stay, types stay."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
spec = importlib.util.spec_from_file_location(
    "precision_control",
    os.path.join(HERE, "..", "tools", "precision_control.py"))
control = importlib.util.module_from_spec(spec)
spec.loader.exec_module(control)


def test_matrices_are_rounded_and_vectors_stay():
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    tree = {"w": (0.01275 * jax.random.normal(k1, (4, 64, 32))
                  ).astype(jnp.bfloat16),
            "g": jax.random.normal(k2, (4, 64)).astype(jnp.bfloat16)[0],
            "ids": jnp.arange(6).reshape(2, 3)}
    before = jax.tree.map(np.asarray, tree)
    low, moved = control.round_weights(tree)
    assert 0.01 < moved < 0.04
    assert low["w"].dtype == jnp.bfloat16
    w, w0 = (np.asarray(a, np.float32) for a in (low["w"], before["w"]))
    # 3 stored bits: a value lies within 2**-4 of what it was, and on a
    # grid of 16 mantissas an octave
    assert np.all(np.abs(w - w0) <= np.abs(w0) * 2.0 ** -4)
    mant = np.frexp(w[w != 0])[0] * 16
    assert np.all(mant == np.round(mant))
    np.testing.assert_array_equal(np.asarray(low["g"]), before["g"])
    np.testing.assert_array_equal(np.asarray(low["ids"]), before["ids"])
