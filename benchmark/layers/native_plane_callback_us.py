"""Host plane, native only (src/tbnet, transport/native_plane.py): the
C++ cutter's stamp on a request's frame until the reactor's frame callback
had the interpreter, a part of ``host_plane_ingress_us``. Mean of the
program's ``device_transport_plane_callback_us`` recorder over the window;
the Python plane never feeds it."""
from benchmark import stages


def read(run):
    return stages.device_stage(run, "plane_callback")
