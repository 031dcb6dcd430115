"""Server process (bvar/lock_probe.py): the wait for the interpreter lock.
Mean over the window of the program's ``device_transport_lock_wait_us``: a
probe thread that does nothing else asks for the lock every ~10 ms, and a
row is the time from the instant the native library woke it (stamped
before ``ctypes`` queues for the lock) to the instant it ran: what any
thread of the process that came back from native code then would have
waited. On a traced run the first call also prints the probe's line for
each of the seven longest idle gaps and the processors by thread name
(``benchmark/timeline_lock.py``). ``None`` on a program without the probe."""
from benchmark import stages, timeline_lock


def read(run):
    timeline_lock.report(run)
    return stages.mean(run, "device_transport_lock_wait_us")
