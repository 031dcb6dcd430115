"""Server process (bvar/processors.py): processors the process's other
tasks kept busy over the window, the runtime's pools and ``tbnet``'s
reactors: gain of ``device_transport_cpu_other_threads_us`` over the
window's length. ``None`` on a program without the counter."""


def read(run):
    gain = run.counters.get("device_transport_cpu_other_threads_us")
    if not isinstance(gain, (int, float)) or not run.window_s:
        return None
    return gain / 1e6 / run.window_s
