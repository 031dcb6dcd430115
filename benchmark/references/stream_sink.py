"""The plain reference of a stream sink deployment: what the sink must
hold is what the writer wrote, byte for byte, cut where a writer of
``message_bytes`` a message cuts it. It imports nothing of the program."""


def expected(request: bytes, attachment: bytes) -> tuple:
    return request, attachment


def messages(attachment: bytes, message_bytes: int) -> list:
    """The transfer's messages in order: consecutive cuts of
    ``message_bytes``; the last may be shorter, none is empty."""
    if message_bytes < 1:
        raise ValueError("message_bytes must be at least 1")
    return [
        attachment[at:at + message_bytes]
        for at in range(0, len(attachment), message_bytes)
    ]
