"""DeviceEndpoint micro-batching: the share of the rows the step programs
ran that were padding (a batch of b calls runs over the next power of two of
rows)."""


def read(run):
    rows = run.counters.get("device_transport_dispatch_rows")
    ran = run.counters.get("device_transport_dispatch_pad_rows")
    if rows is None or not ran:
        return None
    return 100.0 * (ran - rows) / ran
