"""Alias table (Vose) correctness — exact encoding + empirical sampling."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.alias import _div_rn, alias_probs, build_alias, sample_alias
from tests.conftest import empirical_dist, tv_distance


@pytest.mark.parametrize("n", [1, 2, 3, 5, 8, 17, 33, 64])
def test_alias_encodes_exact_distribution(n):
    rng = np.random.default_rng(n)
    w = rng.integers(0, 100, n).astype(np.float32)
    w[rng.integers(n)] = 50  # ensure nonzero
    t = build_alias(jnp.asarray(w)[None])
    got = np.asarray(alias_probs(t))[0]
    np.testing.assert_allclose(got, w / w.sum(), atol=1e-5)


def test_alias_batch_rows_independent():
    w = jnp.asarray(np.random.default_rng(0).random((16, 9)), jnp.float32)
    t = build_alias(w)
    p = np.asarray(alias_probs(t))
    np.testing.assert_allclose(p, np.asarray(w) / np.asarray(w).sum(-1, keepdims=True),
                               atol=1e-5)


def test_alias_sampling_empirical():
    w = jnp.array([5.0, 4.0, 3.0, 0.0, 8.0])
    t = build_alias(w[None])
    B = 40000
    u0, u1 = jax.random.uniform(jax.random.key(0), (2, B))
    rows = jax.tree.map(lambda x: jnp.broadcast_to(x[0], (B,) + x.shape[1:]), t)
    s = sample_alias(rows, u0, u1)
    d = empirical_dist(s, 5)
    assert tv_distance(d, np.array([5, 4, 3, 0, 8]) / 20) < 0.015


def test_degenerate_single_entry():
    t = build_alias(jnp.array([[7.0]]))
    np.testing.assert_allclose(np.asarray(alias_probs(t))[0], [1.0])


@pytest.mark.parametrize("kind", ["wide", "integer", "near_one"])
def test_integer_division_is_ieee_division(kind):
    """The alias build divides with integer ops so every backend and
    every program shape gets the same bits; those bits must be IEEE
    round-to-nearest-even quotients (subnormal results flush to 0)."""
    rng = np.random.default_rng(len(kind))
    N = 200_000
    if kind == "wide":
        a = rng.random(N) * 10.0 ** rng.integers(-18, 18, N)
        b = rng.random(N) * 10.0 ** rng.integers(-18, 18, N) + 1e-30
    elif kind == "integer":
        a = rng.integers(0, 1 << 26, N) * 16.0
        b = rng.integers(1, 1 << 30, N) * 1.0
    else:
        b = rng.integers(1, 1 << 24, N) * 1.0
        a = b - rng.integers(0, 2, N)
    a, b = a.astype(np.float32), b.astype(np.float32)
    got = np.asarray(jax.jit(_div_rn)(a, b))
    with np.errstate(under="ignore"):
        want = a / b
    want[np.abs(want) < np.finfo(np.float32).tiny] = 0.0
    np.testing.assert_array_equal(got.view(np.int32), want.view(np.int32))
