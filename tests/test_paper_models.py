"""Paper Table-1 models: smoke forwards, shapes, no NaNs, kernel parity."""

import jax
import numpy as np
import pytest

from repro.models.paper_models import (PAPER_MODELS, make_random_batch,
                                       mtwnd_apply, mtwnd_init)


@pytest.mark.parametrize("name,out_dim", [
    ("candle", 1), ("resnet50", 1000), ("vgg19", 1000), ("mtwnd", 2),
    ("dien", 1),
])
def test_forward_shapes_and_finite(name, out_dim):
    model = PAPER_MODELS[name]
    params = model.init(jax.random.PRNGKey(0), "smoke")
    batch = make_random_batch(name, "smoke", 4)
    out = model.apply(params, batch)
    assert out.shape == (4, out_dim)
    assert np.all(np.isfinite(np.asarray(out)))


def test_mtwnd_outputs_are_probabilities():
    params = mtwnd_init(jax.random.PRNGKey(1), "smoke")
    batch = make_random_batch("mtwnd", "smoke", 8)
    out = np.asarray(mtwnd_apply(params, batch))
    assert np.all((out >= 0) & (out <= 1))


def test_mtwnd_kernel_embedding_parity():
    """Recsys embedding path through the Pallas embedding_bag kernel must
    match the plain gather path."""
    params = mtwnd_init(jax.random.PRNGKey(2), "smoke")
    batch = make_random_batch("mtwnd", "smoke", 4)
    plain = mtwnd_apply(params, batch, use_kernel=False)
    kern = mtwnd_apply(params, batch, use_kernel=True, interpret=True)
    np.testing.assert_allclose(np.asarray(plain), np.asarray(kern),
                               rtol=1e-5, atol=1e-5)


def test_dien_attention_focuses_on_target():
    """Sanity: with a history containing the target item, prediction differs
    from a history without it (attention is doing something)."""
    from repro.models.paper_models import dien_apply, dien_init
    params = dien_init(jax.random.PRNGKey(3), "smoke")
    batch = make_random_batch("dien", "smoke", 2)
    base = dien_apply(params, batch)
    batch2 = dict(batch, hist=(batch["hist"] + 17) % 128)
    other = dien_apply(params, batch2)
    assert not np.allclose(np.asarray(base), np.asarray(other))
