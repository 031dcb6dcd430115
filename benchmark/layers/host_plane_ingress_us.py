"""Host plane (transport/sock.py, rpc/server.py): the messenger cutting a
request's frame off the wire until ``server_handler`` is entered — the
server's half of the host plane in front of the device path. Mean of the
program's ``device_transport_ingress_us`` recorder over the window."""
from benchmark import stages


def read(run):
    return stages.device_stage(run, "ingress")
