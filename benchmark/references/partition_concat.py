"""The plain reference of a partitioned echo deployment: the request is cut
into consecutive rows of ``row_bytes``, one a partition (the last may be
shorter or empty), each shard answers its own row as an echo server does,
and the call's answer is the shards' answers joined in partition order. The
cell sends no attachment; one is handed back as it came. It imports nothing
of the program."""

PARTITIONS = 3
ROW_BYTES = 1048576  # the configuration's; a deployment may pass a smaller row


def slices(request: bytes, row_bytes: int = ROW_BYTES,
           partitions: int = PARTITIONS) -> list:
    """Sub-request ``i`` is bytes ``[i * row_bytes, (i + 1) * row_bytes)``."""
    if row_bytes < 1 or len(request) > partitions * row_bytes:
        raise ValueError(
            f"{len(request)} bytes do not fit {partitions} rows of {row_bytes}")
    return [request[i * row_bytes:(i + 1) * row_bytes] for i in range(partitions)]


def shard_answer(row: bytes) -> bytes:
    return row


def merged(request: bytes, row_bytes: int = ROW_BYTES,
           partitions: int = PARTITIONS) -> bytes:
    return b"".join(
        shard_answer(row) for row in slices(request, row_bytes, partitions))


def expected(request: bytes, attachment: bytes) -> tuple:
    return merged(request), attachment
