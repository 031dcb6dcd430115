"""Server process (bvar/lock_probe.py): the window's silences of the
interpreter lock, ms: gain of ``device_transport_lock_stall_us``, the sum
of the probe's waits over its ``STALL_NS`` (100 ms), each of which the
program names in its log and in ``lock_probe.stalls()``. 0 in most runs.
``None`` on a program without the probe or a window without a tick."""


def read(run):
    stalled = run.counters.get("device_transport_lock_stall_us")
    if stalled is None or not run.counters.get("device_transport_lock_probes"):
        return None
    return stalled / 1e3
