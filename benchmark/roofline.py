"""What a kernel has to move, from its shapes. Kept with the benchmark so
that no PR that claims a gain can change the count."""

FRAME_HEADER_WORDS = 8  # ops/framing.py's header, copied


def echo_step_bytes(bucket_words: int, rows: int = 1) -> int:
    """The least HBM traffic of one echo step over ``rows`` frames of a
    ``bucket_words`` payload: read each padded payload once and write each
    response frame (header + payload) once, in uint32 words. The program
    as it is also builds the request frame, folds two checksums and
    selects on the verdict; those passes are what a share under 100%
    shows."""
    return rows * 4 * (bucket_words + FRAME_HEADER_WORDS + bucket_words)


def bucket_words(payload_bytes: int, least: int = 64) -> int:
    """``transport/device.py``'s power-of-two payload bucket, copied."""
    words = max(1, (payload_bytes + 3) // 4)
    bucket = least
    while bucket < words:
        bucket <<= 1
    return bucket
