"""Server process (bvar/processors.py): processors the threads the
interpreter knows kept busy over the window: gain of
``device_transport_cpu_python_threads_us`` (``/proc/self/task/<tid>/schedstat``
of every task whose ``native_id`` a ``threading.Thread`` has, read at the
window's edges; a task that ended keeps what it read when last seen) over
the window's length. With ``host_cpu_other_threads_cores`` it adds up to
``host_cpu_cores`` but for what a task ran after the last reading that saw
it: threads that live for one dispatch are never seen. ``None`` on a
program without the counter."""


def read(run):
    gain = run.counters.get("device_transport_cpu_python_threads_us")
    if not isinstance(gain, (int, float)) or not run.window_s:
        return None
    return gain / 1e6 / run.window_s
