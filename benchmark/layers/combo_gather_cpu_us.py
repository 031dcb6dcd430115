"""Combo (rpc/combo.py): the CPU time (``time.thread_time_ns``) of
``combo_gather_us``'s stage, on the caller's thread. Mean of
``device_link_combo_gather_cpu_us`` over the window; the wall mean less this
is time the caller was off the processor (the interpreter lock, the
runtime). A program from before PR 35 has no such recorder and reads
``None``."""
from benchmark import stages


def read(run):
    return stages.mean(run, "device_link_combo_gather_cpu_us")
