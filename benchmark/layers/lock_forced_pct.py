"""Server process (bvar/lock_probe.py): the share of the probe's ticks that
waited the interpreter's whole switch interval out
(``device_transport_lock_forced``, waits of ``sys.getswitchinterval()`` or
more, over ``device_transport_lock_probes``): the holder gave the lock up
only because it was made to, as a thread of pure Python does. ``None`` on
a program without the probe or a window without a tick."""
from benchmark import stages


def read(run):
    share = stages.ratio(
        run, "device_transport_lock_forced", "device_transport_lock_probes")
    return None if share is None else 100.0 * share
