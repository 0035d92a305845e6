"""Operator layer (``repro.core.operators``) and the host CSR container
(``repro.core.sparse``): matvec shapes, pytree behaviour, coercion rules
and CSR assembly."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from repro.core.banded import band_to_dense, random_banded
from repro.core.operators import (
    BandedOperator,
    CsrOperator,
    LinearOperator,
    as_matvec,
    as_operator,
    require_square_dense,
)
from repro.core.sparse import CSR, csr_from_coo, csr_from_dense, random_sparse


def _csr(n=40, seed=0):
    csr = random_sparse(n, d=1.5, shuffle=True, seed=seed)
    return csr, csr.to_dense()


def _x(n, r=None, seed=1):
    shape = (n,) if r is None else (n, r)
    return np.random.default_rng(seed).normal(size=shape).astype(np.float32)


# ---------------------------------------------------------------------------
# CsrOperator / BandedOperator matvec
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("r", [None, 3], ids=["vector", "block"])
def test_csr_operator_matvec_matches_dense(r):
    csr, dense = _csr()
    op = CsrOperator.from_csr(csr)
    x = _x(csr.n, r)
    y = op.matvec(jnp.asarray(x))
    assert y.shape == x.shape
    np.testing.assert_allclose(np.asarray(y), dense @ x, rtol=1e-5, atol=1e-5)


def test_csr_operator_is_a_pytree_under_jit():
    csr, dense = _csr(seed=2)
    op = CsrOperator.from_csr(csr)
    leaves, treedef = jax.tree_util.tree_flatten(op)
    assert len(leaves) == 3  # data, rows, cols; n is static metadata
    y = jax.jit(lambda o, v: o.matvec(v))(op, jnp.asarray(_x(csr.n)))
    np.testing.assert_allclose(np.asarray(y), dense @ _x(csr.n), rtol=1e-5,
                               atol=1e-5)
    assert jax.tree_util.tree_unflatten(treedef, leaves).n == csr.n


def test_csr_operator_default_dtype_and_roundtrip():
    """from_csr picks the canonical float (f32 without x64); to_csr
    rebuilds the same host matrix."""
    csr, dense = _csr(seed=3)
    op = CsrOperator.from_csr(csr)
    assert op.dtype == jnp.float32 and op.rows.dtype == jnp.int32
    back = op.to_csr()
    np.testing.assert_array_equal(back.indptr, csr.indptr)
    np.testing.assert_array_equal(back.indices, csr.indices)
    np.testing.assert_allclose(back.to_dense(), dense, rtol=1e-6)


def test_banded_operator_block_matvec():
    band = np.float32(random_banded(30, 3, d=1.0, seed=4))
    op = BandedOperator.from_band(band)
    assert (op.n, op.k) == (30, 3)
    x = _x(30, 4)
    y = op(jnp.asarray(x))  # __call__ is matvec
    assert y.shape == (30, 4)
    dense = np.asarray(band_to_dense(jnp.asarray(band)))
    np.testing.assert_allclose(np.asarray(y), dense @ x, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# as_operator / as_matvec / require_square_dense
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["operator", "ndarray", "jax_array", "csr",
                                  "scipy"])
def test_as_operator_accepts(kind):
    csr, dense = _csr(seed=5)
    a = {
        "operator": lambda: CsrOperator.from_csr(csr),
        "ndarray": lambda: dense,
        "jax_array": lambda: jnp.asarray(dense, jnp.float32),
        "csr": lambda: csr,
        "scipy": lambda: sp.csr_matrix(dense),
    }[kind]()
    op = as_operator(a)
    if kind == "operator":
        assert op is a
    assert isinstance(op, CsrOperator) and op.n == csr.n
    x = _x(csr.n)
    np.testing.assert_allclose(np.asarray(op.matvec(jnp.asarray(x))),
                               dense @ x, rtol=1e-5, atol=1e-5)


class _HasMatvec:
    def matvec(self, x):
        return 2.0 * x


@pytest.mark.parametrize("kind", ["operator", "matvec_attr", "callable"])
def test_as_matvec_normalizes(kind):
    band = np.float32(random_banded(12, 2, d=1.0, seed=6))
    op = BandedOperator.from_band(band)
    obj, expect = {
        "operator": (op, lambda x: np.asarray(op.matvec(x))),
        "matvec_attr": (_HasMatvec(), lambda x: 2.0 * np.asarray(x)),
        "callable": (lambda x: x + 1.0, lambda x: np.asarray(x) + 1.0),
    }[kind]
    mv = as_matvec(obj)
    assert callable(mv) and not isinstance(mv, LinearOperator)
    x = jnp.asarray(_x(12))
    np.testing.assert_allclose(np.asarray(mv(x)), expect(x), rtol=1e-6)


@pytest.mark.parametrize("shape", [(8,), (8, 5), (2, 4, 4)],
                         ids=["1d", "non_square", "3d"])
def test_require_square_dense_rejects(shape):
    with pytest.raises(TypeError, match="dense square"):
        require_square_dense(np.zeros(shape))
    with pytest.raises(TypeError, match="dense square"):
        as_operator(np.zeros(shape))


# ---------------------------------------------------------------------------
# host CSR assembly
# ---------------------------------------------------------------------------


def test_csr_from_coo_sums_duplicates():
    csr = csr_from_coo(3, [0, 0, 2, 0], [1, 1, 2, 1], [1.0, 2.0, 5.0, 0.5])
    assert csr.nnz == 2
    np.testing.assert_array_equal(csr.indices, [1, 2])
    np.testing.assert_allclose(csr.data, [3.5, 5.0])


def test_csr_from_coo_sorts_unsorted_triplets():
    rows, cols = [2, 0, 1, 0, 2], [0, 2, 1, 0, 2]
    data = [1.0, 2.0, 3.0, 4.0, 5.0]
    csr = csr_from_coo(3, rows, cols, data)
    np.testing.assert_array_equal(csr.indptr, [0, 2, 3, 5])
    np.testing.assert_array_equal(csr.indices, [0, 2, 1, 0, 2])
    np.testing.assert_allclose(csr.data, [4.0, 2.0, 3.0, 1.0, 5.0])
    dense = np.zeros((3, 3))
    dense[rows, cols] = data
    np.testing.assert_allclose(csr.to_dense(), dense)


def test_csr_from_coo_keeps_empty_rows():
    csr = csr_from_coo(5, [0, 3], [4, 1], [1.0, 2.0])
    np.testing.assert_array_equal(csr.indptr, [0, 1, 1, 1, 2, 2])
    assert csr.row(1)[0].size == 0 and csr.row(4)[0].size == 0
    assert csr.to_dense().shape == (5, 5)


def test_csr_from_dense_drops_entries_at_or_below_tol():
    a = np.array([[1.0, 0.1, -0.05], [0.1, 2.0, 0.0], [-0.2, 0.0, 3.0]])
    csr = csr_from_dense(a, tol=0.1)
    expect = np.where(np.abs(a) > 0.1, a, 0.0)
    np.testing.assert_allclose(csr.to_dense(), expect)
    assert csr.nnz == 4  # |0.1| == tol is dropped, |-0.2| > tol is kept


def test_csr_row_and_transpose():
    csr, dense = _csr(n=25, seed=7)
    cols, vals = csr.row(3)
    np.testing.assert_allclose(dense[3, cols], vals)
    assert np.count_nonzero(dense[3]) == cols.size
    t = csr.transpose()
    assert isinstance(t, CSR) and t.nnz == csr.nnz
    np.testing.assert_allclose(t.to_dense(), dense.T)
    assert np.all(np.diff(t.indptr) >= 0)
