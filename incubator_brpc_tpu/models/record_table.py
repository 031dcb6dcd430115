"""Record table — a keyed record store as a device-resident RPC step.

The service ``models/tensor_echo`` is not: state that lives in HBM between
calls, that one call reads and another changes (a parameter or embedding
shard, a prefix cache, YCSB's ``usertable``). The table is one
``uint32[records, row_words]`` array, a record a row: ``fields`` fields of
``field_words`` words and the rest of the row spare. It is the
``DeviceEndpoint``'s to hold (docs/DEVICE_PLANE.md): every dispatch is
handed the table, donated, and hands the next one back, so an update is
made where the table lies.

On the wire (payload words, little-endian; the frame is ops/framing's):

- ``READ`` (method 1): the key, a u64 ordinal in ``[0, records)`` (words
  0-1), answered by the record's fields, ``fields * field_words`` words.
- ``UPDATE`` (method 2): the key (words 0-1), a field index in ``[0,
  fields)`` (word 2), the field's new value (``field_words`` words),
  answered by one status word, 0: written.

A key or field out of range is answered ``EREQUEST`` and touches nothing,
a bad frame and an unknown method as ``TensorEchoService.step`` answers
them; a dispatch's pad rows (zero payload, method 0) are unknown methods.

**The calls of one dispatch are in flight together**, so any order among
them is linearizable; this is the one the step keeps: its reads see the
table as it was before its updates, and of two updates of one field the
later row wins, whole (the earlier one is acknowledged: it was written and
then replaced). XLA's scatter promises neither with repeated indices, so
the earlier row is masked out of the scatter.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import SingleDeviceSharding

from incubator_brpc_tpu.bvar import Adder
from incubator_brpc_tpu.ops import framing

READ, UPDATE = 1, 2
ENOMETHOD, EREQUEST = 1002, 1003  # utils/status.ErrorCode's, as tensor_echo's

# rows of the table filled by one program execution while it is built: 256
# MiB at 256 words a row, so set-up never holds a second table
PIECE_ROWS = 1 << 18

# fed by ``account`` from a completed dispatch's frames, on the watcher
m_reads = Adder(name="device_transport_table_reads")
m_updates = Adder(name="device_transport_table_updates")
# updates that a later update of the same field in their own dispatch replaced
m_overwritten_rows = Adder(name="device_transport_table_overwritten_rows")


def first_content(seed: int, keys: jnp.ndarray, words: jnp.ndarray) -> jnp.ndarray:
    """Word ``words`` of record ``keys`` before any update: an integer mix
    of the three in uint32 arithmetic, which numpy computes alike (the
    benchmark's reference holds its own copy)."""
    x = (
        keys.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
        + words.astype(jnp.uint32) * jnp.uint32(0x85EBCA77)
        + jnp.uint32((seed * 0xC2B2AE3D) & 0xFFFFFFFF)
    )
    x = (x ^ (x >> 15)) * jnp.uint32(0x2C1B3C6D)
    x = (x ^ (x >> 12)) * jnp.uint32(0x297A2D39)
    return x ^ (x >> 15)


class RecordTableService:
    """``records`` records of ``fields`` fields of ``field_words`` words in
    rows of ``row_words``, first filled from ``seed``."""

    def __init__(
        self, records: int, fields: int = 10, field_words: int = 25,
        row_words: int = 256, seed: int = 0,
    ):
        if fields * field_words > row_words:
            raise ValueError("a record does not fit its row")
        if not 0 < records < 1 << 31:
            raise ValueError("records are indexed by an int32")
        self.records, self.fields, self.field_words = records, fields, field_words
        self.row_words, self.seed = row_words, seed
        self.record_words = fields * field_words
        self.update_words = 3 + field_words  # key, field, value

    # -- what a DeviceEndpoint asks of its service --------------------------

    def init_state(self, device) -> jnp.ndarray:
        """The table on ``device``, built where it lies a piece at a time."""
        piece = min(self.records, PIECE_ROWS)
        if self.records % piece:
            raise ValueError(f"records must be a multiple of {piece}")
        on_device = SingleDeviceSharding(device)
        shape = (self.records, self.row_words)

        def fill(table, start):
            keys = start.astype(jnp.uint32) + jnp.arange(piece, dtype=jnp.uint32)
            words = jnp.arange(self.row_words, dtype=jnp.uint32)
            block = first_content(self.seed, keys[:, None], words[None, :])
            return lax.dynamic_update_slice(table, block, (start, jnp.int32(0)))

        fill = jax.jit(fill, donate_argnums=0)
        table = jax.jit(
            lambda: jnp.zeros(shape, jnp.uint32), out_shardings=on_device
        )()
        for start in range(0, self.records, piece):
            table = fill(table, np.int32(start))
        return table

    def answer_bytes(self, method_id: int, request_bytes: int) -> int:
        if method_id == READ:
            return 4 * self.record_words
        if method_id == UPDATE:
            return 4
        return request_bytes  # answered ENOMETHOD, as long as it came

    def step(self, table, rows, cids, mids):
        """One dispatch over the whole batch: ``(table, rows[b, w], cids[b],
        mids[b]) -> (table', response frames[b, 8 + w])``. Jittable; the
        table is meant to be donated. Word 1 of an update's answer, past
        its status, says whether a later row replaced it (``account``)."""
        b, width = rows.shape
        header, payload, ok = jax.vmap(
            lambda padded, cid_lo, mid: framing.parse(
                framing.frame(padded, (cid_lo, jnp.uint32(0)), method_id=mid)
            )
        )(rows, cids, mids)
        mid = header.method_id
        is_read, is_update = mid == jnp.uint32(READ), mid == jnp.uint32(UPDATE)
        key_ok = (payload[:, 1] == 0) & (payload[:, 0] < jnp.uint32(self.records))
        key = jnp.where(key_ok, payload[:, 0], 0).astype(jnp.int32)
        # a program narrower than a record or an update can hold neither
        read_ok = ok & is_read & key_ok & (width >= self.record_words)
        field = payload[:, 2]
        update_ok = (
            ok & is_update & key_ok & (field < jnp.uint32(self.fields))
            & (width >= self.update_words)
        )

        # reads see the table as it was before this dispatch's updates
        record = jnp.take(table, key, axis=0, mode="clip")
        shown = min(width, self.row_words)
        answer = jnp.zeros((b, width), jnp.uint32)
        answer = answer.at[:, :shown].set(
            jnp.where(read_ok[:, None], record[:, :shown], 0))

        # of two updates of one field the later row wins, whole: the
        # earlier one is left out of the scatter, as is every row that is
        # no update (its index lies past the table and is dropped)
        same = (
            (key[:, None] == key[None, :]) & (field[:, None] == field[None, :])
            & update_ok[None, :]
        )
        order = jnp.arange(b)
        overwritten = update_ok & jnp.any(
            same & (order[None, :] > order[:, None]), axis=1)
        written = update_ok & ~overwritten
        at = jnp.stack(
            [
                jnp.where(written, key, self.records),
                jnp.where(written, field, 0).astype(jnp.int32) * self.field_words,
            ],
            axis=1,
        )
        value = payload[:, 3 : 3 + self.field_words]
        if value.shape[1] == self.field_words:
            table = lax.scatter(
                table, at, value,
                lax.ScatterDimensionNumbers(
                    update_window_dims=(1,), inserted_window_dims=(0,),
                    scatter_dims_to_operand_dims=(0, 1),
                ),
                mode=lax.GatherScatterMode.FILL_OR_DROP,
            )
        answer = answer.at[:, 1].set(
            jnp.where(overwritten, jnp.uint32(1), answer[:, 1]))

        served = read_ok | update_ok
        err = jnp.where(
            ok & (is_read | is_update),
            jnp.where(served, jnp.uint32(0), jnp.uint32(EREQUEST)),
            jnp.where(ok, jnp.uint32(ENOMETHOD), jnp.uint32(EREQUEST)),
        )
        frames = jax.vmap(
            lambda result, lo, hi, m, e: framing.frame(
                result, (lo, hi), method_id=m,
                flags=framing.FLAG_RESPONSE, error_code=e,
            )
        )(answer, header.cid_lo, header.cid_hi, mid, err)
        return table, frames

    dispatch_step = step  # the name the endpoint calls; a batch is seen whole

    def account(self, mids: np.ndarray, frames: np.ndarray) -> None:
        """Host side, from a completed dispatch's method ids and response
        frames: the table's counters."""
        served = frames[:, framing.HEADER_WORDS - 1] == 0  # word 7: error code
        updates = served & (mids == UPDATE)
        m_reads << int((served & (mids == READ)).sum())
        m_updates << int(updates.sum())
        m_overwritten_rows << int(frames[updates, framing.HEADER_WORDS + 1].sum())
