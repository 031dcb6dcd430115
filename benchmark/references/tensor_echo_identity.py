"""The plain reference of the tensor echo: what the tensor of every call
holds, and the answer to a call, which is the call. ``numpy`` alone; it
imports nothing of the program.

A call's tensor is a function of ``(seed, caller, call, word)``: the first
three make a 64-bit key (splitmix64 over the tuple, in Python's whole
numbers), the key and the word index a uint32 by two rounds of murmur3's
finaliser, as ``kv_block_pool.py`` makes a block's. Every step is uint32
arithmetic that wraps, so ``jax.numpy`` computes the same words on a chip:
``device_words`` is that twin, for whoever makes or judges a tensor where it
lies (it imports ``jax.numpy`` when called; ``tests/test_unary_device.py``
compares the two). No two calls of a run share a key, so a stale answer or
one swapped between two callers cannot compare equal.
"""

import numpy as np

GOLDEN = 0x9E3779B1
_M64 = (1 << 64) - 1


def _splitmix64(x: int) -> int:
    x = (x + 0x9E3779B97F4A7C15) & _M64
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & _M64
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & _M64
    return x ^ (x >> 31)


def tensor_key(seed: int, caller: int, call: int) -> np.ndarray:
    """``uint32[2]``: the key of that call's tensor, ``(k0, k1)``."""
    h = 0
    for part in (seed, caller, call):
        h = _splitmix64(h ^ (part & _M64))
    return np.array([h & 0xFFFFFFFF, h >> 32], np.uint32)


def _fmix32(x, u32):
    x = x ^ (x >> u32(16))
    x = x * u32(0x85EBCA6B)
    x = x ^ (x >> u32(13))
    x = x * u32(0xC2B2AE35)
    return x ^ (x >> u32(16))


def tensor_words(key, words: int) -> np.ndarray:
    """``uint32[words]``: the tensor of that key."""
    index = np.arange(words, dtype=np.uint32)
    first = _fmix32(index * np.uint32(GOLDEN) + np.uint32(key[0]), np.uint32)
    return _fmix32(first ^ np.uint32(key[1]), np.uint32)


def content(seed: int, caller: int, call: int, words: int) -> np.ndarray:
    return tensor_words(tensor_key(seed, caller, call), words)


def device_words(key, words: int):
    """``tensor_words`` in ``jax.numpy``: ``key`` is ``uint32[2]`` where the
    tensor is wanted, and the tensor is made there."""
    import jax.numpy as jnp

    index = jnp.arange(words, dtype=jnp.uint32)
    first = _fmix32(index * jnp.uint32(GOLDEN) + key[0], jnp.uint32)
    return _fmix32(first ^ key[1], jnp.uint32)


def expected(request: bytes, attachment) -> tuple:
    """The identity: the answer to a call is its payload and its
    attachment, the tensor word for word."""
    return request, attachment
