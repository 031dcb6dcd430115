"""The allocator policy a configuration states, applied to this process.

With glibc's defaults the threshold above which ``malloc`` maps and unmaps
each buffer moves with the history of frees, and the 4 MiB cell ran in one
of two modes by whichever way it fell (65 or 95 calls/s, chip runs of
PR 24, PERF.md). A configuration fixes the policy, as upstream's
benchmarks do by linking tcmalloc; ``mallopt`` with a fixed
``M_MMAP_THRESHOLD`` also switches the moving threshold off.
"""

import ctypes

_PARAMS = {"trim_threshold": -1, "top_pad": -2, "mmap_threshold": -3}


def apply(settings: dict) -> None:
    libc = ctypes.CDLL(None)
    libc.mallopt.argtypes = [ctypes.c_int, ctypes.c_int]
    libc.mallopt.restype = ctypes.c_int
    for name, value in settings.items():
        if libc.mallopt(_PARAMS[name], int(value)) != 1:
            raise RuntimeError(f"mallopt refused {name}={value}")
