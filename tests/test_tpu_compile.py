"""The main-path Pallas kernels compile for a TPU v5e at the paper's widths.

Nothing here runs on a chip: the TPU compiler is given a described v5e and
must accept each kernel (interpret mode hides refusals such as unaligned
block shapes or dynamic lane offsets).  Widths: K=16 resident slots, the
H32 BNN (8192 input bits, 32 hidden, 1 output), packet rows of 16 metadata
words plus 256 payload words.

The topology is described inside a fixture, never at import time: only one
process may hold the TPU library, and test workers import every file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import packet as pkt
from repro.kernels import bnn_xnor, fused_forward as ff

K, H, C = 16, 32, 1
W, META = pkt.PAYLOAD_WORDS, pkt.META_WORDS


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _bank_specs(sharding):
    return (_spec(sharding, (K, H, W), jnp.uint32),
            _spec(sharding, (K, H), jnp.float32),
            _spec(sharding, (K, C, H), jnp.float32),
            _spec(sharding, (K, C), jnp.float32))


def _assert_kernel_compiles(fn, *specs):
    hlo = jax.jit(fn).lower(*specs).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("block_b", [32, 128])
def test_fused_gather_with_actions_compiles(one_chip, block_b):
    """The served kernel: packet rows in arrival order, DMA gather,
    inline parse and Pi action (``packet_step(strategy="fused")``)."""
    batch = 128
    n_blocks = batch // block_b + K   # worst case of group_by_slot_padded

    def step(x, w1, b1, w2, b2, block_slots, row_ids):
        return ff.fused_forward(x, w1, b1, w2, b2, block_slots, row_ids,
                                block_b=block_b, meta_words=META,
                                with_actions=True)

    _assert_kernel_compiles(
        step, _spec(one_chip, (batch, META + W), jnp.uint32),
        *_bank_specs(one_chip),
        _spec(one_chip, (n_blocks,), jnp.int32),
        _spec(one_chip, (n_blocks * block_b,), jnp.int32))


def test_fused_contiguous_compiles(one_chip):
    block_b, n_blocks = 128, 4

    def step(x, w1, b1, w2, b2, block_slots):
        return ff.fused_forward(x, w1, b1, w2, b2, block_slots, None,
                                block_b=block_b)

    _assert_kernel_compiles(
        step, _spec(one_chip, (n_blocks * block_b, W), jnp.uint32),
        *_bank_specs(one_chip), _spec(one_chip, (n_blocks,), jnp.int32))


def test_bnn_xnor_compiles(one_chip):
    """Layer 1 of the single-slot executor (``pipeline.inference_only``)."""
    _assert_kernel_compiles(
        bnn_xnor.xnor_matmul, _spec(one_chip, (256, W), jnp.uint32),
        _spec(one_chip, (H, W), jnp.uint32))



@pytest.mark.parametrize("num_slots", [1, K])
def test_served_tick_step_compiles(one_chip, monkeypatch, num_slots):
    """The tick's one launch (``pipeline.packet_step_queues``: 4 queues of
    128 packet rows, block_b 32) compiles as a module whose name holds
    ``jit_packet_step``, around the kernel op ``%fused_forward.<n>``, and
    returns the (4, 3, 128) int32 packed result."""
    import re
    from repro.core import pipeline
    from repro.kernels import ops
    # the step asks the default backend (the CPU here) whether to
    # interpret its kernel: steer it to the chip's compiled kernel, and
    # keep interpret-mode traces of other tests out of the jit caches
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    jax.clear_caches()
    w1, b1, w2, b2 = (_spec(one_chip, (num_slots,) + s.shape[1:], s.dtype)
                      for s in _bank_specs(one_chip))
    try:
        compiled = pipeline.packet_step_queues.lower(
            {"w1p": w1, "b1": b1, "w2": w2, "b2": b2},
            _spec(one_chip, (4, 128, META + W), jnp.uint32),
            num_slots=num_slots, strategy="fused", backend="pallas",
            block_b=32).compile()
    finally:
        jax.clear_caches()
    hlo = compiled.as_text()
    assert re.search(r"^HloModule jit_packet_step_queues\b", hlo, re.M)
    assert re.search(r"%fused_forward(\.\d+)? = .*"
                     r'custom_call_target="tpu_custom_call"', hlo)
    out = compiled.out_info
    assert out.shape == (4, 3, 128) and out.dtype == jnp.int32
