"""Server process (bvar/lock_probe.py): the share of the probe's ticks that
found the interpreter lock in another thread's hand
(``device_transport_lock_busy``, waits over the probe's ``BUSY_NS``, over
``device_transport_lock_probes``): the share of instants at which the
interpreter was taken, **the layer's saturation**, which ``host_cpu_cores``
at or over 1.0 only hinted at. ``None`` on a program without the probe or
a window without a tick."""
from benchmark import stages


def read(run):
    share = stages.ratio(
        run, "device_transport_lock_busy", "device_transport_lock_probes")
    return None if share is None else 100.0 * share
