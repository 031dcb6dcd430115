"""The plain reference of a keyed record store under YCSB's core workload:
records of ``FIELDCOUNT`` fields of ``FIELDLENGTH`` bytes in a ``dict``,
``read(key)`` the whole record, ``update(key, field, value)`` one field;
YCSB's generators, ported in the open; how a seeded payload becomes an
operation; and the bookkeeping that says which values a read may show
when calls overlap. It imports nothing of the program.

**Generators** (brianfrankcooper/YCSB, ``core/.../generator``):
``zeta(n, theta)`` and ``Zipfian.draw`` are ``ZipfianGenerator.zeta`` and
the closed form of ``ZipfianGenerator.nextLong``; ``fnv1a64`` is
``Utils.fnvhash64`` (xor an octet, multiply by the prime, eight times,
``Math.abs`` of the signed result). A key is ``fnv1a64(rank) % records``
as ``ScrambledZipfianGenerator`` scatters its ranks, the rank drawn over
the deployment's own ``records`` items (upstream's scrambled generator
draws over a fixed 10^10 items and folds; over ``records`` the hottest
key has the share ``1 / zeta(records, theta)`` that ``ZipfianGenerator``
gives it).

**An operation from a payload.** The harness's generator sends seeded
random bytes; the operation is read off them, so ``--seed`` decides every
operation and every value: bytes 0-7 a u64 for read or update
(``DiscreteGenerator``'s order: read below ``READPROPORTION``), bytes 8-15
a u64 through the Zipfian draw and the scramble for the key, byte 16 the
field (mod ``FIELDCOUNT``), bytes 17-116 the value.

**What a read may show** (``Register``). Per ``(key, field)`` the updates
are kept with the time each was sent and acknowledged. A read sent at
``s`` and answered at ``e`` may show, in that field, whole: the first
content or the value of an update ``W`` sent before ``e``, unless another
update was sent after ``W`` was acknowledged and was itself acknowledged
before ``s`` (then ``W`` was replaced for certain before the read began).
Updates that overlap each other or the read leave both values open: the
store may put calls that are in flight together in any order. A field that
shows bytes of two values equals neither and is wrong.

``expected(request, attachment)`` is what ``benchmark/generator.py`` asks
of any reference. A keyed store's answer depends on what was written
before, which no function of one request knows: the deployment's client
judges each answer against this file's ``Register`` once the call's clock
has stopped, and hands the generator the request's own bytes back exactly
when the answer passed (the store's answer itself, never 128 B long, when
it did not). So the expected answer, in the client's encoding, is the
request.
"""

import struct
import threading

import numpy as np

# CoreWorkload.java's defaults and workloads/workloadb
FIELDCOUNT = 10
FIELDLENGTH = 100
READPROPORTION = 0.95
ZIPFIAN_CONSTANT = 0.99
RECORDCOUNT = 8388608  # the configuration's; a deployment may hold fewer

READ, UPDATE = "read", "update"
KEY = struct.Struct("<Q")
UPDATE_HEAD = struct.Struct("<QI")
STATUS_OK = b"\x00\x00\x00\x00"
PAYLOAD_BYTES = 17 + FIELDLENGTH

FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211
_U64 = (1 << 64) - 1


def expected(request: bytes, attachment: bytes) -> tuple:
    return request, attachment


# -- the store ---------------------------------------------------------------


def first_content(table_seed: int, key: int) -> bytes:
    """The record before any update: word ``w`` of record ``key`` is an
    integer mix of ``(table_seed, key, w)`` in uint32 arithmetic, little-
    endian; the program fills its table with the same function."""
    w = np.arange(FIELDCOUNT * FIELDLENGTH // 4, dtype=np.uint32)
    x = (
        np.uint32((key * 0x9E3779B1) & 0xFFFFFFFF)
        + w * np.uint32(0x85EBCA77)
        + np.uint32((table_seed * 0xC2B2AE3D) & 0xFFFFFFFF)
    )
    x = (x ^ (x >> np.uint32(15))) * np.uint32(0x2C1B3C6D)
    x = (x ^ (x >> np.uint32(12))) * np.uint32(0x297A2D39)
    return (x ^ (x >> np.uint32(15))).astype("<u4").tobytes()


def field_of(record: bytes, field: int) -> bytes:
    return bytes(record[field * FIELDLENGTH:(field + 1) * FIELDLENGTH])


class RecordStore:
    """The store, one call at a time: a ``dict`` of ``bytearray`` records
    over the first content."""

    def __init__(self, records: int, table_seed: int):
        self.records, self.table_seed = records, table_seed
        self._held = {}

    def _record(self, key: int) -> bytearray:
        if not 0 <= key < self.records:
            raise KeyError(key)
        if key not in self._held:
            self._held[key] = bytearray(first_content(self.table_seed, key))
        return self._held[key]

    def read(self, key: int) -> bytes:
        return bytes(self._record(key))

    def update(self, key: int, field: int, value: bytes) -> bytes:
        if not 0 <= field < FIELDCOUNT or len(value) != FIELDLENGTH:
            raise ValueError((field, len(value)))
        self._record(key)[field * FIELDLENGTH:(field + 1) * FIELDLENGTH] = value
        return STATUS_OK


# -- YCSB's generators -------------------------------------------------------


def zeta(n: int, theta: float) -> float:
    """``sum(1 / i**theta for i in 1..n)``."""
    total = 0.0
    for lo in range(1, n + 1, 1 << 22):
        i = np.arange(lo, min(lo + (1 << 22), n + 1), dtype=np.float64)
        total += float((1.0 / i ** theta).sum())
    return total


def fnv1a64(value: int) -> int:
    h = FNV_OFFSET_BASIS_64
    for _ in range(8):
        h = ((h ^ (value & 0xFF)) * FNV_PRIME_64) & _U64
        value >>= 8
    return (1 << 64) - h if h >> 63 else h  # Math.abs of the signed long


def unit(x: int) -> float:
    """A u64 as a double in [0, 1): its top 53 bits."""
    return (x >> 11) / float(1 << 53)


class Zipfian:
    """``ZipfianGenerator(0, items - 1, theta)``: rank 0 the most popular."""

    def __init__(self, items: int, theta: float = ZIPFIAN_CONSTANT):
        self.items, self.theta = items, theta
        self.zetan = zeta(items, theta)
        self.zeta2theta = zeta(2, theta)
        self.alpha = 1.0 / (1.0 - theta)
        self.eta = (1 - (2.0 / items) ** (1 - theta)) / (
            1 - self.zeta2theta / self.zetan)

    def draw(self, u: float) -> int:
        uz = u * self.zetan
        if uz < 1.0:
            return 0
        if uz < 1.0 + 0.5 ** self.theta:
            return 1
        return int(self.items * (self.eta * u - self.eta + 1) ** self.alpha)

    def share(self, rank: int) -> float:
        """The analytic share of ``rank``: ``1 / (rank + 1)**theta / zetan``."""
        return 1.0 / (rank + 1) ** self.theta / self.zetan


class Workload:
    """Core workload B over ``records`` records: ``operation(payload)``."""

    def __init__(self, records: int = RECORDCOUNT):
        self.records = records
        self.zipfian = Zipfian(records)

    def key(self, x: int) -> int:
        return fnv1a64(self.zipfian.draw(unit(x))) % self.records

    def parts(self, payload: bytes) -> tuple:
        """``(method, key, field, value)``; the last two ``None`` for a read."""
        if len(payload) < PAYLOAD_BYTES:
            raise ValueError(f"an operation needs {PAYLOAD_BYTES} seeded bytes")
        choice, x = struct.unpack_from("<QQ", payload)
        key = self.key(x)
        if unit(choice) < READPROPORTION:
            return READ, key, None, None
        return UPDATE, key, payload[16] % FIELDCOUNT, bytes(payload[17:PAYLOAD_BYTES])

    def operation(self, payload: bytes) -> tuple:
        """``(method, request bytes)`` as they go on the wire."""
        method, key, field, value = self.parts(payload)
        if method == READ:
            return READ, KEY.pack(key)
        return UPDATE, UPDATE_HEAD.pack(key, field) + value


_workloads = {}


def operation(payload: bytes, records: int = RECORDCOUNT) -> tuple:
    if records not in _workloads:
        _workloads[records] = Workload(records)
    return _workloads[records].operation(payload)


# -- what a read may show when calls overlap ---------------------------------


class Register:
    """Every update by key and field with when it was sent and when it was
    acknowledged (``None``: in flight, or its call failed and nobody
    knows), on one clock with the reads' times. Safe from many threads."""

    def __init__(self, table_seed: int):
        self.table_seed = table_seed
        self._updates = {}  # key -> {field -> [[sent, acknowledged, value]]}
        self._lock = threading.Lock()

    def sent(self, key: int, field: int, value: bytes, at: int) -> list:
        entry = [at, None, value]
        with self._lock:
            self._updates.setdefault(key, {}).setdefault(field, []).append(entry)
        return entry

    @staticmethod
    def acknowledged(entry: list, at: int) -> None:
        entry[1] = at

    def updated(self) -> dict:
        """``{key: [fields]}`` of everything an update was ever sent to."""
        with self._lock:
            return {key: sorted(fields) for key, fields in self._updates.items()}

    def _of(self, key: int) -> dict:
        with self._lock:
            return {f: list(u) for f, u in self._updates.get(key, {}).items()}

    @staticmethod
    def may_show(first: bytes, updates: list, sent: int, answered: int) -> set:
        """The values a read sent at ``sent`` and answered at ``answered``
        may show in a field whose first content is ``first`` and whose
        updates are ``updates``."""
        # the first content is an update acknowledged before time began
        writes = [[-2, -1, first]] + updates
        settled = [w for w in writes if w[1] is not None and w[1] < sent]
        return {
            w[2] for w in writes
            if w[0] < answered
            and not (w[1] is not None and any(x[0] > w[1] for x in settled))
        }

    def wrong_fields(self, key: int, record: bytes, sent: int, answered: int) -> int:
        """Fields of a read's answer that show none of the values they may."""
        if len(record) != FIELDCOUNT * FIELDLENGTH:
            return FIELDCOUNT
        first, updates = first_content(self.table_seed, key), self._of(key)
        return sum(
            field_of(record, f) not in self.may_show(
                field_of(first, f), updates.get(f, []), sent, answered)
            for f in range(FIELDCOUNT))
