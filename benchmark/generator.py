"""The one load generator: every traffic mix is a data file it reads.

A mix (``traffic/<name>.json``) gives

- ``arrival``: ``"closed"`` (``callers`` threads, each sends its next call
  when the last one returned) or ``"open"`` (calls fall due at seeded
  exponential gaps of mean ``1 / rate_per_s``, are sent by ``callers``
  threads and are timed from when they were due; how late each was sent
  is recorded);
- ``sizes``: payload bytes. Each caller walks seeded shuffles of
  ``sizes * size_block`` end to end, so every seed sends the same mix in
  another order;
- ``carrier``: ``"payload"`` or ``"attachment"`` (the request is then
  ``b"ping"`` and the bytes ride as the attachment);
- ``pool_per_size``: payloads per caller and size, made from the seed
  during set-up and cycled. Callers have pools of their own, so calls in
  flight together never carry equal bytes and a swapped or stale response
  cannot pass the comparison;
- ``warm_calls_per_caller`` and ``warm_seconds``: each caller makes
  untimed calls until it has made that many and that long has passed; the
  first seconds of a fresh process run slower than the rest.

Nothing of the generator's own is timed: pools are built before the
window, and a call's clock runs from just before ``call_method`` to just
after it returns. The response is compared with the reference's answer
after the clock has stopped.

Run as a program, this file is the generator child of an ``own_process``
configuration: it never touches the chip (``JAX_PLATFORMS=cpu``, and it
imports only what ``Channel`` needs), talks to its parent over stdin and
stdout and hands the per-call records back in a ``.npy`` file.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

# columns of the record array, one row per call
CALLER, DUE_NS, SEND_NS, END_NS, SIZE, STATUS = range(6)
OK, RPC_FAILED, MISMATCH = 0, 1, 2

_MASK = (1 << 63) - 1
CALL_TIMEOUT_MS = 60000


def _rng(seed: int, caller: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed & _MASK, caller, stream])


def make_pool(traffic: dict, seed: int, caller: int) -> dict:
    rng = _rng(seed, caller, 0)
    return {
        size: [rng.bytes(size) for _ in range(traffic["pool_per_size"])]
        for size in sorted(set(traffic["sizes"]))
    }


def size_walk(traffic: dict, seed: int, caller: int):
    rng = _rng(seed, caller, 1)
    block = np.asarray(traffic["sizes"] * traffic.get("size_block", 1))
    while True:
        yield from (int(s) for s in rng.permutation(block))


def due_offsets_ns(traffic: dict, seed: int, seconds: float) -> np.ndarray:
    """Open loop: when each call falls due, from the window's opening."""
    rate = float(traffic["rate_per_s"])
    n = int(rate * seconds * 1.2) + 16
    gaps = _rng(seed, 0, 2).exponential(1.0 / rate, size=n)
    due = np.cumsum(gaps)
    return (due[due < seconds] * 1e9).astype(np.int64)


def channel_caller(channel, traffic: dict, reference):
    """``send(bytes) -> (end_ns, status)`` over one ``Channel``."""
    from incubator_brpc_tpu.rpc import Controller

    service, method = traffic["service"], traffic["method"]
    as_attachment = traffic["carrier"] == "attachment"

    def send(data: bytes):
        request, attachment = (b"ping", data) if as_attachment else (data, b"")
        cntl = channel.call_method(
            service, method, request, attachment=attachment,
            cntl=Controller(timeout_ms=CALL_TIMEOUT_MS),
        )
        end = time.monotonic_ns()
        if cntl.failed():
            return end, RPC_FAILED
        want = reference.expected(request, attachment)
        got = (cntl.response_payload, cntl.response_attachment)
        return end, OK if got == want else MISMATCH

    return send


def run_load(send, traffic: dict, seed: int, seconds: float, on_open=None):
    """Drive ``send`` for ``seconds`` and return ``(records, t_open_ns)``.
    The window opens when every caller has made its untimed calls."""
    callers = int(traffic["callers"])
    open_loop = traffic["arrival"] == "open"
    if traffic["arrival"] not in ("closed", "open"):
        raise ValueError(f"unknown arrival {traffic['arrival']!r}")
    pools = [make_pool(traffic, seed, c) for c in range(callers)]
    walks = [size_walk(traffic, seed, c) for c in range(callers)]
    offsets = due_offsets_ns(traffic, seed, seconds) if open_loop else None
    window_ns = int(seconds * 1e9)
    state = {"open": 0, "next": 0}
    lock = threading.Lock()
    records = [[] for _ in range(callers)]
    errors = []

    def opened():
        state["open"] = time.monotonic_ns()
        if on_open is not None:
            on_open(state["open"])

    barrier = threading.Barrier(callers, action=opened)

    def payloads(c):
        turn = dict.fromkeys(pools[c], 0)
        for size in walks[c]:
            pool = pools[c][size]
            turn[size] = (turn[size] + 1) % len(pool)
            yield size, pool[turn[size]]

    def closed_caller(c, feed, rows):
        close = state["open"] + window_ns
        while True:
            t0 = time.monotonic_ns()
            if t0 >= close:
                return
            size, data = next(feed)
            end, status = send(data)
            rows.append((c, t0, t0, end, size, status))

    def open_caller(c, feed, rows):
        while True:
            with lock:
                i = state["next"]
                state["next"] = i + 1
            if i >= len(offsets):
                return
            due = state["open"] + int(offsets[i])
            wait = due - time.monotonic_ns()
            if wait > 0:
                time.sleep(wait / 1e9)
            size, data = next(feed)
            t0 = time.monotonic_ns()
            end, status = send(data)
            rows.append((c, due, t0, end, size, status))

    def caller(c):
        try:
            feed = payloads(c)
            warm_calls = int(traffic["warm_calls_per_caller"])
            warm_until = time.monotonic() + float(traffic["warm_seconds"])
            while warm_calls > 0 or time.monotonic() < warm_until:
                warm_calls -= 1
                _, status = send(next(feed)[1])
                if status == RPC_FAILED:
                    raise RuntimeError("an untimed warm call failed")
            barrier.wait(timeout=300)
            (open_caller if open_loop else closed_caller)(c, feed, records[c])
        except BaseException as e:  # noqa: BLE001 — reported by the parent
            errors.append(repr(e))
            barrier.abort()

    threads = [
        threading.Thread(target=caller, args=(c,), name=f"caller-{c}")
        for c in range(callers)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=seconds + 600)
    if errors or any(t.is_alive() for t in threads):
        raise RuntimeError(f"load generator failed: {errors or 'callers hung'}")
    rows = [r for rows in records for r in rows]
    table = np.asarray(rows, dtype=np.int64).reshape(len(rows), 6)
    return table, state["open"]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--job", required=True, help="JSON: what to send and where")
    args = p.parse_args(argv)
    job = json.loads(args.job)
    sys.path.insert(0, job["root"])
    from benchmark import allocator, manifest
    from incubator_brpc_tpu.rpc import Channel, ChannelOptions

    allocator.apply(job["allocator"])
    reference = manifest.load_module("references", job["reference"] + ".py")
    channel = Channel()
    target = f"127.0.0.1:{job['port']}"
    if not channel.init(target, options=ChannelOptions(**job["channel_options"])):
        raise SystemExit(f"generator: cannot reach {target}")
    send = channel_caller(channel, job["traffic"], reference)
    print("READY", flush=True)
    if sys.stdin.readline().strip() != "GO":
        return 1
    table, _ = run_load(
        send, job["traffic"], job["seed"], job["seconds"],
        on_open=lambda ns: print(f"OPEN {ns}", flush=True),
    )
    np.save(job["out"], table)
    print("DONE", flush=True)
    return 0


if __name__ == "__main__":
    os._exit(main())  # daemon reactor threads of the channel never join
