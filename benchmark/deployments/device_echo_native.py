"""``device_echo`` with the native plane on both ends: the server is
``Server(ServerOptions(**config["server_options"]))``, so ``src/tbnet``'s
reactors accept, cut and verify the frames and hand each to the
interpreter through the plane's frame callback; the generator's
``Channel`` takes ``channel_options`` as every configuration's does.
Handler, endpoint, controls and warm-up are ``device_echo``'s own.

``holds()`` reads the third guarantee from the server: the kind of its
plane, and the frames its native callback delivered against the calls the
handler served (over the server's life, which holds the window). A server
that fell back to the Python plane reads ``NOT HELD``."""

from __future__ import annotations

import itertools

from benchmark import manifest

_echo = manifest.load_module("deployments", "device_echo.py")
CONTROLS = _echo.CONTROLS
PLANE = "NativeServerPlane"


class Deployment(_echo.Deployment):
    def __init__(self, config: dict, control, spans):
        import jax

        from incubator_brpc_tpu.models.tensor_echo import TensorEchoService
        from incubator_brpc_tpu.rpc import Server, ServerOptions
        from incubator_brpc_tpu.transport.device import DeviceEndpoint

        service = TensorEchoService()
        method_id = int(config["method_id"])
        if control == "flip_bit":
            method_id = 1
            service.add_method(method_id, _echo._flip_bit)
        self.endpoint = DeviceEndpoint(service=service, **config["endpoint"])
        handler = self.endpoint.server_handler(method_id=method_id)
        if control == "stale":
            handler = _echo._stale(handler)
        if spans is not None:
            handler = spans.wrap(handler)
        self._served = itertools.count()  # next() is one step under the GIL
        self.server = Server(ServerOptions(**config["server_options"]))
        self.server.add_service("tensor", {"echo": self._counted(handler)})
        if not self.server.start(0):
            raise RuntimeError("the server did not start")
        self.port = self.server.port
        self.devices = [self.endpoint.device]
        self._jax = jax

    def _counted(self, handler):
        served = self._served

        def counted(cntl, request):
            next(served)
            return handler(cntl, request)

        return counted

    def holds(self) -> list:
        plane = getattr(self.server, "_native_plane", None)
        kind = type(plane).__name__ if plane is not None else "python"
        native = kind == PLANE
        served = next(self._served)
        delivered = plane.stats()["cb_frames"] if native else 0
        return [
            ("server_plane", kind, PLANE, native),
            ("frames_the_native_callback_delivered", delivered,
             f">={served} calls served, >=1",
             native and delivered >= served >= 1),
            ("native_reactors", plane.num_reactors if native else 0, ">=1",
             native and plane.num_reactors >= 1),
        ]
