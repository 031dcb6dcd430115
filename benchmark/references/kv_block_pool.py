"""The plain reference of the KV block stream: what every page of the
decode node's pool must hold, in ``numpy`` alone. It imports nothing of
the program.

A block's content is a function of ``(seed, caller, transfer, block,
word)``: the first four make a 64-bit key (splitmix64 over the tuple, in
Python's whole numbers), the key and the word index a uint32 by two rounds
of murmur3's finaliser. Every step is uint32 arithmetic that wraps, so
``jax.numpy`` computes the same words on the device (the deployment holds
that twin; ``tests/test_kv_page_pool.py`` compares the two). No two blocks
of a run share a key, so a stale or swapped block cannot compare equal.

``Pool`` is the pool as a dict from page to the block placed there last;
``place`` puts a transfer's blocks in consecutive pages, wrapping, as the
deployment's round robin does.
"""

import numpy as np

GOLDEN = 0x9E3779B1
_M64 = (1 << 64) - 1


def _splitmix64(x: int) -> int:
    x = (x + 0x9E3779B97F4A7C15) & _M64
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & _M64
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & _M64
    return x ^ (x >> 31)


def block_key(seed: int, caller: int, transfer: int, block: int) -> tuple:
    """The block's key as two uint32 halves ``(k0, k1)``."""
    h = 0
    for part in (seed, caller, transfer, block):
        h = _splitmix64(h ^ (part & _M64))
    return h & 0xFFFFFFFF, h >> 32


def block_keys(seed: int, caller: int, transfer: int, blocks: int) -> np.ndarray:
    """``uint32[blocks, 2]``: the keys of a transfer's blocks, in order."""
    return np.array(
        [block_key(seed, caller, transfer, b) for b in range(blocks)], np.uint32)


def _fmix32(x: np.ndarray) -> np.ndarray:
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> np.uint32(13))
    x = x * np.uint32(0xC2B2AE35)
    return x ^ (x >> np.uint32(16))


def block_words(key: tuple, words: int) -> np.ndarray:
    """``uint32[words]``: the block of that key."""
    index = np.arange(words, dtype=np.uint32)
    first = _fmix32(index * np.uint32(GOLDEN) + np.uint32(key[0]))
    return _fmix32(first ^ np.uint32(key[1]))


def content(seed: int, caller: int, transfer: int, block: int, words: int):
    return block_words(block_key(seed, caller, transfer, block), words)


class Pool:
    """What the pool must hold: page -> ``(caller, transfer, block)``."""

    def __init__(self, pages: int, page_words: int, seed: int):
        self.pages, self.page_words, self.seed = pages, page_words, seed
        self.placed = {}

    def place(self, caller: int, transfer: int, first_page: int, blocks: int) -> list:
        """Block ``b`` of the transfer goes to page ``(first_page + b) %
        pages``; returns those pages in block order."""
        pages = [(first_page + b) % self.pages for b in range(blocks)]
        for b, page in enumerate(pages):
            self.placed[page] = (caller, transfer, b)
        return pages

    def page(self, page: int) -> np.ndarray:
        """The words page ``page`` must hold: the block placed there last,
        zeros where none was."""
        if page not in self.placed:
            return np.zeros(self.page_words, np.uint32)
        return content(self.seed, *self.placed[page], self.page_words)


def expected(request: bytes, attachment: bytes) -> tuple:
    """What the harness compares a transfer's answer with. The blocks are
    born on the device and judged there; the deployment's adapter answers
    the request's own bytes exactly when the transfer's pages passed."""
    return request, attachment
