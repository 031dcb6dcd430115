"""``Channel(transport="tpu")`` to a plain host echo handler: handshake on
the host socket, frames over the device link the handshake built. Both
ends live in the process that drives both devices (a single-controller
link), so the generator's callers are threads of this process. The
set-up is ``__graft_entry__.link_leg``'s."""

from __future__ import annotations


def _flip_bit(handler):
    """Control: one bit of every echoed attachment flips, in the first or
    the second byte by the second byte's parity."""

    def flipped(cntl, request):
        out = handler(cntl, request)
        data = bytearray(cntl.response_attachment)
        data[data[1] & 1] ^= 1
        cntl.response_attachment = bytes(data)
        return out

    return flipped


def _stale(handler):
    """Control: a call is answered with the previous call's attachment
    where the lengths agree."""
    last = {}

    def stale(cntl, request):
        out = handler(cntl, request)
        data = cntl.response_attachment
        cntl.response_attachment = last.get(len(data), data)
        last[len(data)] = data
        return out

    return stale


CONTROLS = ("flip_bit", "stale")


def _echo(cntl, request):
    cntl.response_attachment = cntl.request_attachment
    return request


class Deployment:
    def __init__(self, config: dict, control, spans):
        from incubator_brpc_tpu.rpc import Server

        handler = _echo
        if control is not None:
            handler = {"flip_bit": _flip_bit, "stale": _stale}[control](handler)
        if spans is not None:
            handler = spans.wrap(handler)
        self.server = Server()
        self.server.add_service("EchoService", {"Echo": handler})
        if not self.server.start(0):
            raise RuntimeError("the server did not start")
        self.port = self.server.port
        self._options = dict(config["channel_options"])
        self._want = config["link"]
        self._channel = None

    def warm(self, traffic: dict) -> None:
        """The link's one exchange program compiles in the handshake and
        the callers' untimed calls; nothing else has a shape."""

    def channel(self):
        from incubator_brpc_tpu.rpc import Channel, ChannelOptions

        if self._channel is None:
            channel = Channel()
            ok = channel.init(
                f"127.0.0.1:{self.port}",
                options=ChannelOptions(**self._options),
            )
            if not ok:
                raise RuntimeError("cannot reach the server")
            self._channel = channel
        return self._channel

    @property
    def link(self):
        return self._channel._device_sock.link

    @property
    def devices(self) -> list:
        return list(self.link.devices)

    def holds(self) -> list:
        link = self.link
        distinct = len({d.id for d in link.devices})
        return [
            ("link_geometry", link.geometry, self._want["geometry"],
             link.geometry == self._want["geometry"]),
            ("link_distinct_devices", distinct, self._want["devices"],
             distinct == self._want["devices"]),
        ]

    def close(self) -> None:
        self.server.stop()
        self.server.join(timeout=10)
