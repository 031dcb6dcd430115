"""Host plane (rpc/server.py, transport/sock.py or src/tbnet): the
server's way out, ``server_handler`` returned until the packed response was
handed to the connection's write. Mean of the program's
``device_transport_egress_us`` recorder over the window."""
from benchmark import stages


def read(run):
    return stages.device_stage(run, "egress")
