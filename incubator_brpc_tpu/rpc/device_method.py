"""Device methods — RPC methods with a jittable device kernel, the seam
through which combo channels lower to ICI collectives.

The reference's ParallelChannel fans one call out over N sub-channels and
merges the replies on the caller (parallel_channel.cpp:36-101); SURVEY
§2.5 maps that row to an all-gather over the device mesh, and BASELINE
configs #3/#4 name the lowering ("parallel_echo/partition_echo lowered to
ICI all-gather/all-to-all"). The lowering is only sound when the method's
server-side work is a pure device function — so services DECLARE it:

    kernel(data: uint8[width], n: int32) -> (uint8[width], int32)

``device_method(kernel)`` wraps that kernel into an ordinary host handler
(the server runs the same jitted kernel on its own device for point-to-
point calls), and registers it so a ParallelChannel/PartitionChannel whose
sub-channels all ride device links can fuse the whole scatter→execute→
gather into ONE shard_map dispatch (rpc/combo.py). Both paths execute the
same compiled kernel, so fused and host fan-out produce byte-identical
merged responses.

Registering a device method is an explicit contract: the kernel sees only
request bytes (no Controller, no auth fight, no per-request admission), so
it must be pure — exactly the class of method the reference would have
made an RDMA-side fast path.
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, Optional, Tuple

import numpy as np

DEFAULT_WIDTH = 4096


class DeviceMethod:
    """A jittable bytes-in/bytes-out kernel with fixed row geometry.

    A request becomes the kernel's operand as a zero-padded row of
    ``width`` uint8 and its length: ``pack`` makes the row (the host
    handler, ``mc_dispatch``), ``pack_into`` writes it in place into a row
    of the caller's buffer (the fused combo call, which stages all its
    rows in one). The width check and the padding rule are ``pack_into``'s.

    ``chunkable=True`` declares the kernel CHUNK-SAFE: applying it to any
    contiguous slice of the row produces the same bytes as slicing the
    full-width result (elementwise along the width, collectives included
    — psum of a slice is the slice of the psum), and it passes ``n``
    through unchanged.  Only chunk-safe kernels may run chunked overlap
    sessions (``parallel/mc_dispatch.py``: the step's operand is split on
    its leading axis into independently-dispatched sub-collectives); a
    session proposing ``chunks > 1`` against a method registered without
    the declaration is cleanly rejected before any lockstep entry.  The
    declaration is a capability, not part of the kernel's identity — it
    does not enter the fingerprint."""

    def __init__(
        self,
        kernel: Callable,
        width: int = DEFAULT_WIDTH,
        chunkable: bool = False,
    ):
        self.kernel = kernel
        self.width = width
        self.chunkable = bool(chunkable)
        # chunk boundaries must fall on multiples of this many bytes for
        # the chunk-safety contract to hold (1 = any divisor of width).
        # Block-wise quantized kernels set it to the scale-block byte
        # span: a chunk cut mid-block would recompute block scales from a
        # partial block and diverge from the full-width bytes.
        self.chunk_align = 1
        # quantization surface (parallel/quantized.py): "none" for exact
        # kernels; a quantized VARIANT carries its mode + block size, and
        # collective_bytes declares how many bytes this kernel actually
        # puts on the wire per party per step (None = the full row width
        # — the exact-kernel default). Variants are separate DeviceMethods
        # (own kernel, own fingerprint) reachable via quantized().
        self.quant_mode = "none"
        self.quant_block = 0
        self.quant_variants: Dict[str, "DeviceMethod"] = {}
        self.collective_bytes: Optional[int] = None
        self._jitted = None
        self._lock = threading.Lock()
        self._fingerprint: Optional[str] = None

    def quantized(self, mode: Optional[str]) -> Optional["DeviceMethod"]:
        """Resolve the session-uniform ``quantize=`` knob against this
        method: "none" (or empty) is the method itself; a quantized mode
        returns the registered variant — a DISTINCT DeviceMethod whose
        fingerprint the accept phase validates like any other — or None
        when the method declares no such variant (the clean pre-lockstep
        reject)."""
        mode = (mode or "none").strip() or "none"
        if mode == "none" or mode == self.quant_mode:
            return self
        return self.quant_variants.get(mode)

    def wire_bytes(self) -> int:
        """Bytes this kernel ships across the party axis per party per
        step — the quantized wire footprint when declared, else the full
        row width (the exact float path)."""
        return (
            int(self.collective_bytes)
            if self.collective_bytes
            else int(self.width)
        )

    def fingerprint(self) -> str:
        """Stable identity of the kernel+geometry, advertised by servers in
        the device-link handshake and checked by the fused dispatch: the
        client only lowers a call when the peer registered the SAME kernel
        under that name (a name collision across servers must kill fusion,
        not silently diverge from the host path). Source text is included
        when obtainable so same-name/different-body kernels differ."""
        if self._fingerprint is None:
            import hashlib
            import inspect

            ident = (
                f"{getattr(self.kernel, '__module__', '')}."
                f"{getattr(self.kernel, '__qualname__', repr(self.kernel))}"
                f":{self.width}"
            )
            try:
                ident += ":" + inspect.getsource(self.kernel)
            except (OSError, TypeError):
                pass
            # closure cells and defaults: two kernels minted by one factory
            # with different captured parameters share source text but must
            # NOT share a fingerprint (the fused path would silently run
            # the wrong parametrization for some shards)
            clo = getattr(self.kernel, "__closure__", None) or ()
            for cell in clo:
                try:
                    ident += f"|cell:{cell.cell_contents!r}"
                except Exception:  # noqa: BLE001 — unrepr-able: be cautious
                    ident += "|cell:?"
            defaults = getattr(self.kernel, "__defaults__", None) or ()
            for d in defaults:
                try:
                    ident += f"|def:{d!r}"
                except Exception:  # noqa: BLE001
                    ident += "|def:?"
            self._fingerprint = hashlib.sha1(ident.encode()).hexdigest()[:16]
        return self._fingerprint

    def jitted(self):
        import jax

        with self._lock:
            if self._jitted is None:
                self._jitted = jax.jit(self.kernel)
            return self._jitted

    def pack(self, request: bytes) -> Tuple[np.ndarray, np.int32]:
        row = np.empty(self.width, dtype=np.uint8)
        return row, np.int32(self.pack_into(row, request))

    def pack_into(self, row: np.ndarray, request: bytes) -> int:
        """``pack`` in place: ``request`` written once at the front of
        ``row`` (``width`` contiguous uint8 of a buffer that need not be
        zeroed) and only the tail beyond it zeroed, so a kernel that reads
        its whole row sees what ``pack`` would hand it. Returns ``n``."""
        n = len(request)
        if n > self.width:
            raise ValueError(
                f"request of {n}B exceeds device-method width {self.width}"
            )
        # numpy's assignment drops the interpreter lock over a large copy and
        # keeps it over a small one: callers' 1 MiB rows are copied side by side
        row[:n] = np.frombuffer(request, dtype=np.uint8)
        if n < self.width:
            row[n:] = 0
        return n

    def unpack(self, row, n) -> bytes:
        n = int(n)
        return bytes(np.asarray(row[:n], dtype=np.uint8))

    def pack_state(self, row_bytes: bytes, n: int) -> Tuple[np.ndarray, np.int32]:
        """Re-materialize a checkpointed FULL-WIDTH state row — the
        elastic-session reshard format (parallel/mc_dispatch): unlike an
        operand (``pack``, ≤ width, zero-padded), a mid-chain state row
        must be exactly ``width`` bytes — the values beyond the original
        operand length are live kernel state, and silently padding a
        short row would resume a corrupted chain."""
        if len(row_bytes) != self.width:
            raise ValueError(
                f"state row of {len(row_bytes)}B != method width "
                f"{self.width}"
            )
        row = np.frombuffer(bytes(row_bytes), dtype=np.uint8).copy()
        return row, np.int32(int(n))


# (service, method) -> DeviceMethod; filled by Server.add_service when a
# handler carries ._device_method (process-global, like the reference's
# method map being reachable from the protocol layer)
_registry: Dict[Tuple[str, str], DeviceMethod] = {}
_registry_lock = threading.Lock()


def register_device_method(service: str, method: str, dm: DeviceMethod) -> None:
    with _registry_lock:
        _registry[(service, method)] = dm


def lookup_device_method(service: str, method: str) -> Optional[DeviceMethod]:
    with _registry_lock:
        return _registry.get((service, method))


def unregister_device_method(service: str, method: str) -> Optional[DeviceMethod]:
    """Remove a registration (tests restoring a clean registry; a
    registered name SHADOWS the builtin width-minting resolvers, so a
    leaked fixture registration changes resolution for every later
    width).  Returns the removed DeviceMethod or None."""
    with _registry_lock:
        return _registry.pop((service, method), None)


def registry_fingerprints() -> Dict[str, str]:
    """Snapshot of every registered method's identity ("svc.m" ->
    fingerprint) — what a multi-controller handshake advertises so the
    peer can validate session proposals and collective lowerings against
    a name it has actually seen (transport/mc_link.py)."""
    with _registry_lock:
        items = list(_registry.items())
    return {f"{s}.{m}": dm.fingerprint() for (s, m), dm in items}


def device_method(
    kernel: Callable,
    width: int = DEFAULT_WIDTH,
    chunkable: bool = False,
) -> Callable:
    """Wrap a device kernel into a host RPC handler.

    The handler runs the SAME jitted kernel the fused collective path
    runs, on this process's default device — point-to-point calls and the
    fused ParallelChannel dispatch therefore return identical bytes.
    ``chunkable`` declares chunk-safety for overlap sessions (see
    :class:`DeviceMethod`).
    """
    dm = DeviceMethod(kernel, width=width, chunkable=chunkable)

    def handler(cntl, request: bytes) -> bytes:
        row, n = dm.pack(request)
        out_row, out_n = dm.jitted()(row, n)
        return dm.unpack(np.asarray(out_row), out_n)

    handler._device_method = dm
    return handler
