"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracles,
swept over shapes/dtypes, plus hypothesis properties of the bit packing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.kernels import banked_matmul as bm
from repro.kernels import bnn_xnor, ops, ref


def _rand_packed(rng, shape):
    return jnp.asarray(rng.integers(0, 2**32, shape, dtype=np.uint32))


@pytest.mark.parametrize("b,h,w,bb,bh,chunk", [
    (8, 8, 8, 8, 8, 8),
    (16, 32, 256, 8, 16, 64),     # paper h32 layout (1024B payload)
    (32, 32, 256, 32, 32, 32),
    (64, 16, 64, 16, 8, 16),
    (8, 8, 32, 4, 4, 8),
])
def test_xnor_kernel_matches_ref(rng, b, h, w, bb, bh, chunk):
    x = _rand_packed(rng, (b, w))
    wts = _rand_packed(rng, (h, w))
    got = bnn_xnor.xnor_matmul(x, wts, block_b=bb, block_h=bh, chunk=chunk,
                               interpret=True)
    want = ref.xnor_matmul_ref(x, wts)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_xnor_equals_float_dot(rng):
    """Binary dot via popcount == dense +-1 matmul."""
    x = _rand_packed(rng, (8, 16))
    w = _rand_packed(rng, (4, 16))
    d = 16 * 32
    xf = ref.unpack_bits(x, d).astype(np.float32)
    wf = ref.unpack_bits(w, d).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(ref.xnor_matmul_ref(x, w)), (xf @ wf.T).astype(np.int32))


def test_mxu_path_matches_bitwise(rng):
    x = _rand_packed(rng, (8, 32))
    w = _rand_packed(rng, (16, 32))
    np.testing.assert_array_equal(
        np.asarray(ref.xnor_matmul_mxu_ref(x, w)),
        np.asarray(ref.xnor_matmul_ref(x, w)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,d,hid,k,bb", [
    (8, 16, 8, 3, 4), (16, 32, 16, 2, 8), (32, 64, 8, 5, 8),
])
def test_banked_matmul_kernel(rng, dtype, b, d, hid, k, bb):
    x = jnp.asarray(rng.normal(size=(b, d)), dtype)
    w = jnp.asarray(rng.normal(size=(k, d, hid)), dtype)
    bias = jnp.asarray(rng.normal(size=(k, hid)), dtype)
    block_slots = jnp.asarray(rng.integers(0, k, b // bb), jnp.int32)
    got = bm.banked_matmul(x, w, bias, block_slots, block_b=bb, interpret=True)
    slots = jnp.repeat(block_slots, bb)
    want = ref.banked_matmul_ref(x, w, bias, slots)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5, atol=1e-2)


@pytest.mark.parametrize("b,h,w,k,bb,chunk", [
    (16, 8, 32, 2, 8, 16), (32, 32, 256, 16, 16, 64),
])
def test_banked_xnor_layer1_kernel(rng, b, h, w, k, bb, chunk):
    x = _rand_packed(rng, (b, w))
    bank_w1 = _rand_packed(rng, (k, h, w))
    bank_b1 = jnp.asarray(rng.normal(size=(k, h)), jnp.float32)
    block_slots = jnp.asarray(rng.integers(0, k, b // bb), jnp.int32)
    got = bm.banked_xnor_layer1(x, bank_w1, bank_b1, block_slots,
                                block_b=bb, chunk=chunk, interpret=True)
    slots = np.repeat(np.asarray(block_slots), bb)
    d = w * 32
    want = np.stack([
        np.asarray(ref.xnor_matmul_ref(x[i:i+1], bank_w1[slots[i]]))[0]
        + np.asarray(bank_b1[slots[i]])
        for i in range(b)
    ])
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 6), st.integers(1, 8), st.data())
def test_pack_unpack_roundtrip(rows, words, data):
    d = words * 32
    bits = data.draw(st.lists(
        st.lists(st.sampled_from([-1, 1]), min_size=d, max_size=d),
        min_size=rows, max_size=rows))
    x = jnp.asarray(np.asarray(bits, np.int8))
    packed = ref.pack_bits(x)
    back = ref.unpack_bits(packed, d)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))


def test_ops_backends_agree(rng):
    key = jax.random.PRNGKey(0)
    params = ref.random_bnn_params(key, 1024, 16)
    x = _rand_packed(rng, (16, 32))
    y_ref = ops.bnn_forward(params, x, backend="ref")
    y_mxu = ops.bnn_forward(params, x, backend="mxu")
    y_pal = ops.bnn_forward(params, x, backend="pallas")
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_mxu), atol=1e-3)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_pal), atol=1e-5)


@pytest.mark.parametrize("width", [1, 5, 16, 32])
def test_tree_sum_fixed_order(rng, width):
    """``tree_sum`` folds halves of a zero-padded power-of-two width, the
    one order every backend reproduces (so scores agree bit for bit)."""
    v = rng.normal(size=(6, width)).astype(np.float32)
    want = v.copy()
    n = 1 << (width - 1).bit_length()
    want = np.pad(want, ((0, 0), (0, n - width)))
    while n > 1:
        n //= 2
        want = (want[:, :n] + want[:, n:2 * n]).astype(np.float32)
    got = np.asarray(ref.tree_sum(jnp.asarray(v)))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_allclose(got[:, 0], v.sum(axis=1), rtol=1e-5, atol=1e-5)
