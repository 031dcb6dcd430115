"""``PartitionChannel`` over ``Channel(transport="tpu")`` to three shards:
upstream's ``example/partition_echo_c++`` (``client.cpp``: a
``PartitionChannel`` of ``partition_num`` 3 over servers tagged ``N/M``,
``fail_limit`` 1; ``server.cpp``: an echo) with ``combo_channel.md``'s
``UseFieldAsSubRequest`` mapper, over device links as
``examples/partition_echo.py`` builds them. Each shard server binds a mesh
device of its own and serves ``PartitionEcho.Echo`` as a device method, so
a call through the channel is one ``shard_map`` dispatch with an all-gather
(``rpc/combo.py``) and no handler runs: a traced run has no handler span.

Only public API of ``incubator_brpc_tpu.rpc`` is on the timed path; what
``holds()`` reads of the links and the counters it reads after the window.
The mapper's row is the configuration's ``row_bytes``, cut to a third of
the largest request, rounded up, where that is smaller (the CPU rehearsal
sends 4 KiB), so that every partition has bytes of its own to answer.
"""

from __future__ import annotations

import threading

from benchmark import spans

CONTROLS = ("flip_bit", "stale", "swap")


def echo_kernel(data, n):
    return data, n


def flipped_kernel(data, n):
    """Control ``flip_bit``: one bit of every row a shard answers flips."""
    return data.at[0].set(data[0] ^ 1), n


def _row_mapper(row_bytes: int):
    from incubator_brpc_tpu.rpc import CallMapper, SubCall

    class RowMapper(CallMapper):
        """Sub-request ``i`` is bytes ``[i * row_bytes, (i + 1) * row_bytes)``
        of the request; the last may be shorter or empty. Counts the calls
        it mapped."""

        def __init__(self):
            self.mapped = 0
            self._lock = threading.Lock()

        def map(self, channel_index, nchannels, service, method, request):
            if channel_index == 0:
                with self._lock:
                    self.mapped += 1
            at = channel_index * row_bytes
            return SubCall(request=request[at:at + row_bytes])

    return RowMapper()


def _merger(control, partitions: int):
    """The default merger (answers joined in channel order), or a control's:
    ``swap`` exchanges the first two partitions' answers, ``stale`` hands
    back the previous call's whole answer where the lengths agree. A call's
    merges run in order on one thread, so the position is the thread's."""
    from incubator_brpc_tpu.rpc import ResponseMerger

    if control not in ("stale", "swap"):
        return ResponseMerger()
    tls, last, lock = threading.local(), {}, threading.Lock()

    class Spoiled(ResponseMerger):
        def merge(self, merged, sub_response):
            at = getattr(tls, "at", 0)
            tls.at = (at + 1) % partitions
            if control == "swap":
                if at == 0:
                    tls.held = sub_response
                    return merged
                if at == 1:
                    return merged + sub_response + tls.held
                return merged + sub_response
            merged += sub_response
            if at == partitions - 1:
                with lock:
                    merged, last[len(merged)] = last.get(len(merged), merged), merged
            return merged

    return Spoiled()


class Deployment:
    def __init__(self, config: dict, control, handler_spans):
        from incubator_brpc_tpu.rpc import Server, ServerOptions, device_method

        self._config, self._control = config, control
        self.partitions = int(config["partitions"])
        kernel = flipped_kernel if control == "flip_bit" else echo_kernel
        # one handler for the three shards; it carries the device kernel, so
        # it is served bare (a wrapped one would advertise no kernel) and
        # ``handler_spans`` stays empty
        handler = device_method(kernel, width=int(config["row_bytes"]))
        self.servers = []
        for i in range(self.partitions):
            server = Server(ServerOptions(device_index=i + 1, usercode_inline=True))
            server.add_service("PartitionEcho", {"Echo": handler})
            if not server.start(0):
                raise RuntimeError(f"shard {i} did not start")
            self.servers.append(server)
        self.port = self.servers[0].port
        self.row_bytes = None  # warm() cuts it from the traffic
        self._mapper = None
        self._channel = None

    def warm(self, traffic: dict) -> None:
        """The three handshakes, then one call of every size from this
        thread: the one fused program compiles here, before the callers'
        untimed calls."""
        from incubator_brpc_tpu.rpc import Controller

        third = -(-max(traffic["sizes"]) // self.partitions)
        self.row_bytes = min(int(self._config["row_bytes"]), third)
        channel = self.channel()
        for size in sorted(set(traffic["sizes"])):
            cntl = channel.call_method(
                traffic["service"], traffic["method"], bytes(size),
                cntl=Controller(timeout_ms=self._config["channel_options"]["timeout_ms"]),
            )
            if cntl.failed():
                raise RuntimeError(f"the warming call failed: {cntl.error_text}")

    def channel(self):
        from incubator_brpc_tpu.rpc import ChannelOptions, PartitionChannel

        if self._channel is None:
            url = "list://" + ",".join(
                f"127.0.0.1:{s.port} {i}/{self.partitions}"
                for i, s in enumerate(self.servers)
            )
            self._mapper = _row_mapper(self.row_bytes)
            channel = PartitionChannel(fail_limit=int(self._config["fail_limit"]))
            ok = channel.init(
                url, partition_count=self.partitions, lb_name=self._config["lb"],
                options=ChannelOptions(**self._config["channel_options"]),
                call_mapper=self._mapper,
                response_merger=_merger(self._control, self.partitions),
            )
            if not ok:
                raise RuntimeError("the partition channel did not initialise")
            self._channel = channel
        return self._channel

    @property
    def links(self) -> list:
        return [sub[0]._device_sock.link for sub in self._channel._subs]

    @property
    def devices(self) -> list:
        return [link.devices[1] for link in self.links]

    def holds(self) -> list:
        want = self._config["link"]
        links = self.links
        clients = {link.devices[0].id for link in links}
        shards = {link.devices[1].id for link in links} - clients
        geometries = sorted({link.geometry for link in links})
        adders = spans.counters()  # whole-process values, not a window's gains
        made = self._mapper.mapped
        not_fused = (
            adders.get("device_link_combo_host_fanout", 0)
            + adders.get("device_link_combo_mc_lowered", 0)
            + max(0, made - adders.get("device_link_combo_fused", 0))
        )
        return [
            ("partition_distinct_devices", len(shards), want["devices"],
             len(shards) == want["devices"] == len(links)),
            ("partition_geometry", ",".join(geometries), want["geometry"],
             geometries == [want["geometry"]]),
            ("calls_not_fused", not_fused, f"0 of {made} calls made",
             not_fused == 0 and made > 0),
        ]

    def close(self) -> None:
        if self._channel is not None:
            self._channel.stop()
        for server in self.servers:
            server.stop()
        for server in self.servers:
            server.join(timeout=10)
