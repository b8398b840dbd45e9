"""Seeded BNN weights, made on the device in one jitted call.

A slot model is the paper's Eq. 1 network over a packed payload:
``w1p`` (H, d_bits/32) uint32 sign bits of layer 1 (bit 1 means -1),
``b1`` (H,) f32, ``w2`` (C, H) f32 and ``b2`` (C,) f32 -- the pytree the
program's bank holds, stacked on a leading slot axis.  The draw follows
the program's own random initialisation (``b1 ~ 8 N(0,1)``,
``w2 ~ N(0,1)/sqrt(H)``, ``b2 ~ 0.1 N(0,1)``), so about half of random
payloads score above 0 and every action of Pi occurs.

Streams: the resident bank and the swap models come from separate
streams of the seed, so a cell's swap models never repeat a resident one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BANK_STREAM = 1
SWAP_STREAM = 2


def seed_key(seed: int, stream: int):
    """A threefry key from any whole ``seed`` (64-bit and beyond)."""
    words = np.random.SeedSequence([int(seed) % 2**64, stream]).generate_state(
        2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


@functools.partial(jax.jit, static_argnames=("n", "d_bits", "hidden", "n_out"))
def _models(key, *, n: int, d_bits: int, hidden: int, n_out: int) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "w1p": jax.random.bits(k1, (n, hidden, d_bits // 32), jnp.uint32),
        "b1": jax.random.normal(k2, (n, hidden), jnp.float32) * 8.0,
        "w2": jax.random.normal(k3, (n, n_out, hidden), jnp.float32)
        / np.float32(np.sqrt(hidden)),
        "b2": jax.random.normal(k4, (n, n_out), jnp.float32) * 0.1,
    }


def models(cfg: dict, seed: int, stream: int, n: int) -> dict:
    """``n`` slot models stacked on axis 0, on the default device."""
    return _models(seed_key(seed, stream), n=n, d_bits=cfg["d_bits"],
                   hidden=cfg["hidden"], n_out=cfg["n_out"])


def bank(cfg: dict, seed: int) -> dict:
    """The resident bank of ``cfg["slots"]`` models."""
    return models(cfg, seed, BANK_STREAM, cfg["slots"])


def swap_models(cfg: dict, seed: int, n: int) -> list[dict]:
    """``n`` models in host memory, one pytree each, as a control plane
    holds them before delivery."""
    if n == 0:
        return []
    host = jax.device_get(models(cfg, seed, SWAP_STREAM, n))
    return [{k: np.ascontiguousarray(v[i]) for k, v in host.items()}
            for i in range(n)]

