"""Shape/dtype sweeps + property tests: contingency Pallas kernel vs oracle."""
import numpy as np
import jax.numpy as jnp
import pytest

from _hyp import given, settings, st  # optional-hypothesis shim: property tests skip on bare envs

from repro.core.plan import candidate_contingency
from repro.kernels.contingency import contingency, contingency_ref


def _case(rng, nc, g, n_bins, m, zero_tail=0):
    packed = rng.integers(0, n_bins, size=(nc, g)).astype(np.int32)
    d = rng.integers(0, m, size=(g,)).astype(np.int32)
    w = rng.integers(1, 5, size=(g,)).astype(np.float32)
    if zero_tail:
        w[-zero_tail:] = 0.0
    return jnp.asarray(packed), jnp.asarray(d), jnp.asarray(w)


@pytest.mark.parametrize(
    "nc,g,n_bins,m",
    [
        (1, 64, 8, 2),
        (3, 700, 37, 5),
        (8, 1024, 128, 2),       # tile-aligned
        (2, 1000, 130, 26),      # bins just over one tile
        (5, 513, 300, 3),        # G just over one tile
        (1, 33, 1, 2),           # single bin
        (4, 2048, 512, 17),
    ],
)
def test_contingency_matches_ref(nc, g, n_bins, m):
    rng = np.random.default_rng(nc * 1000 + g)
    packed, d, w = _case(rng, nc, g, n_bins, m, zero_tail=g // 10)
    out = contingency(packed, d, w, n_bins=n_bins, n_dec=m)
    ref = contingency_ref(packed, d, w, n_bins=n_bins, n_dec=m)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize("bk,bg", [(8, 64), (128, 128), (64, 512), (256, 1024)])
def test_contingency_block_shape_invariance(bk, bg):
    """Result must not depend on the BlockSpec tiling."""
    rng = np.random.default_rng(7)
    packed, d, w = _case(rng, 3, 500, 77, 4)
    out = contingency(packed, d, w, n_bins=77, n_dec=4, bk=bk, bg=bg)
    ref = contingency_ref(packed, d, w, n_bins=77, n_dec=4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6, atol=1e-5)


def test_contingency_total_mass():
    """Σ_k Σ_j counts == Σ w for every candidate (nothing lost in tiling)."""
    rng = np.random.default_rng(11)
    packed, d, w = _case(rng, 6, 900, 41, 7, zero_tail=100)
    out = contingency(packed, d, w, n_bins=41, n_dec=7)
    total = np.asarray(out.sum(axis=(1, 2)))
    np.testing.assert_allclose(total, np.full(6, float(np.asarray(w).sum())), rtol=1e-6)


def test_backends_bit_equivalent_paths():
    """segment / onehot / pallas backends agree (DESIGN.md §3.1 invariant)."""
    rng = np.random.default_rng(13)
    packed, d, w = _case(rng, 4, 600, 50, 3)
    valid = w > 0
    outs = {
        b: np.asarray(
            candidate_contingency(packed, d, w, valid, n_bins=50, m=3, backend=b)
        )
        for b in ("segment", "onehot", "pallas")
    }
    np.testing.assert_allclose(outs["segment"], outs["onehot"], rtol=1e-6, atol=1e-5)
    np.testing.assert_allclose(outs["segment"], outs["pallas"], rtol=1e-6, atol=1e-5)


@settings(max_examples=25, deadline=None)
@given(
    nc=st.integers(1, 4),
    g=st.integers(1, 300),
    n_bins=st.integers(1, 64),
    m=st.integers(1, 8),
    seed=st.integers(0, 2**16),
)
def test_contingency_property(nc, g, n_bins, m, seed):
    rng = np.random.default_rng(seed)
    packed, d, w = _case(rng, nc, g, n_bins, m)
    out = contingency(packed, d, w, n_bins=n_bins, n_dec=m)
    ref = contingency_ref(packed, d, w, n_bins=n_bins, n_dec=m)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6, atol=1e-5)


def test_interpret_mode_follows_platform():
    import jax

    from repro.kernels.platform import interpret_mode

    assert interpret_mode() == (jax.default_backend() != "tpu")
    assert interpret_mode(True) is True and interpret_mode(False) is False
