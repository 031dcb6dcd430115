"""The plain reference of an echo deployment: the answer to a request is
the request, payload and attachment, byte for byte. It imports nothing of
the program."""


def expected(request: bytes, attachment: bytes) -> tuple:
    return request, attachment
