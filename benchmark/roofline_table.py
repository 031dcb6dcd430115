"""What the record table's step has to move, from what it served. Kept
with the benchmark so that no PR that claims a gain can change the count.

The least HBM traffic of the steps of a window, from the program's own
counters: every row a step ran, pad rows too, is a request row it had to
read to know what it asks (counted at the narrowest bucket, so never more
than the program read); a read reads its record's row and writes a
response frame of the record's fields; an update writes one field. What
the program moves besides (the frame it builds to parse, two checksums,
the selects, the rows' padding to their bucket, an update's status frame)
is what a share under 100% shows; a step that starts to move the table
shows the other way, as a step time in milliseconds."""

REQUEST_ROW_BYTES = 4 * 64  # transport/device.py's narrowest bucket
RECORD_ROW_BYTES = 4 * 256  # a record's row in the table
FRAME_HEADER_BYTES = 4 * 8  # ops/framing.py's header
RECORD_BYTES = 10 * 100  # fieldcount x fieldlength, what a read answers
FIELD_BYTES = 100  # what an update writes


def table_step_bytes(reads: int, updates: int, rows_run: int) -> int:
    """``reads`` and ``updates`` served by steps that ran ``rows_run``
    rows in all (``device_transport_table_reads``, ``..._table_updates``,
    ``device_transport_dispatch_pad_rows``)."""
    return (
        rows_run * REQUEST_ROW_BYTES
        + reads * (RECORD_ROW_BYTES + FRAME_HEADER_BYTES + RECORD_BYTES)
        + updates * FIELD_BYTES
    )
