"""KV page pool — a decode node's paged cache as device-resident state.

The other half of a tensor stream (``rpc/stream.py`` over the link's lane,
``transport/device_link.py``): a prefill node streams a prompt's key-value
blocks, a layer at a time, and the decode node keeps them in a pool of
fixed-size pages in its HBM until the request has decoded (Mooncake,
arXiv:2407.00079). The pool is one ``uint32[pages, page_words]`` array on
one device, a block a page; which pages a request gets is its owner's to
decide (the source's Conductor; an eviction policy is ROADMAP.md R4's).

``write`` is the operation a transfer needs: blocks that are already on
the pool's device go into the pages named for them, and the pool is
donated, so it is updated where it lies, as ``models/record_table.py``'s
table is. ``read`` hands pages back as one array (what a decode step, or a
check, takes). What a word means (bf16 keys and values, packed two a word)
is the model's, not the pool's.
"""

from __future__ import annotations

from typing import Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import SingleDeviceSharding

from incubator_brpc_tpu.bvar import Adder

# pages ``write`` was given, counted when it is called
m_pages_written = Adder(name="device_transport_kv_pages_written")


def _blocks(blocks) -> jnp.ndarray:
    """``[k, page_words]`` from what ``write`` is given: that array, or a
    sequence of ``k`` arrays of ``page_words`` (the stack fuses into the
    program that writes them)."""
    return jnp.stack(blocks) if isinstance(blocks, (tuple, list)) else blocks


def kv_page_write(pool, page_ids, blocks):
    """``pool`` with ``blocks[i]`` in page ``page_ids[i]``, the later of two
    blocks for one page winning. One slice update a block: each is made in
    the donated pool, whatever the page ids repeat."""
    blocks = _blocks(blocks)
    for i in range(blocks.shape[0]):
        pool = lax.dynamic_update_slice(
            pool, blocks[i][None, :], (page_ids[i], jnp.int32(0))
        )
    return pool


def kv_page_read(pool, page_ids):
    """``uint32[k, page_words]``: the pages ``page_ids``, one slice a page.
    Not ``pool[page_ids]``: on a TPU that gather is compiled through a
    second buffer of the pool's size, which an 8 GiB pool on a 16 GiB chip
    has no room for."""
    return jnp.concatenate([
        lax.dynamic_slice_in_dim(pool, page_ids[i], 1, axis=0)
        for i in range(page_ids.shape[0])
    ])


def write_plain(pool, page_ids, blocks):
    """``kv_page_write``'s plain ``jax.numpy`` twin, for tests only: one
    indexed assignment a block, in order, nothing jitted or donated."""
    blocks = _blocks(blocks)
    for i in range(blocks.shape[0]):
        pool = pool.at[page_ids[i]].set(blocks[i])
    return pool


class KvPagePool:
    """``pages`` pages of ``page_words`` uint32 words on one device.

    The pool itself is the caller's to keep: ``init_state`` makes it,
    ``write`` takes it donated and returns the next one, and whoever calls
    ``write`` serialises its calls (a stream's handler runs on one ordered
    consumer, so a sink that writes from its handler does)."""

    def __init__(self, pages: int, page_words: int):
        if pages < 1 or page_words < 1:
            raise ValueError("a pool has at least one page of one word")
        self.pages, self.page_words = pages, page_words
        self._write = jax.jit(kv_page_write, donate_argnums=0)
        self._read = jax.jit(kv_page_read)

    @property
    def nbytes(self) -> int:
        return 4 * self.pages * self.page_words

    def init_state(self, device) -> jnp.ndarray:
        """The pool on ``device``, every word 0. One program whose only
        buffer is its output makes it where it lies, so set-up never holds
        two pools (nor a host copy of one)."""
        shape = (self.pages, self.page_words)
        return jax.jit(
            lambda: jnp.zeros(shape, jnp.uint32),
            out_shardings=SingleDeviceSharding(device),
        )()

    def write(
        self, pool: jnp.ndarray, page_ids,
        blocks: Union[jnp.ndarray, Sequence[jnp.ndarray]],
    ) -> jnp.ndarray:
        """``pool'``: ``blocks`` (``uint32[k, page_words]``, or ``k`` arrays
        of ``uint32[page_words]`` as a stream's handler is handed them) in
        the pages ``page_ids`` (``int32[k]``, each in ``[0, pages)``; one
        out of range is clamped into it, as ``dynamic_update_slice``
        clamps). ``pool`` is donated: the caller keeps what is returned and
        never reads the argument again. One program a ``k``."""
        m_pages_written << len(page_ids)
        return self._write(pool, page_ids, blocks)

    def read(self, pool: jnp.ndarray, page_ids) -> jnp.ndarray:
        """``uint32[k, page_words]`` on the pool's device: the pages
        ``page_ids`` (``int32[k]``) as they are now. The pool is not
        donated and stays the caller's. One program a ``k``."""
        return self._read(pool, page_ids)
