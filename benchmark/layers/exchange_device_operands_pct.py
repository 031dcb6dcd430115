"""Host to HBM crossing: of the window's calls into the endpoints that
carried a tensor, the share whose operand was a ``jax.Array`` on the
endpoint's chip and was served where it lay
(``device_transport_device_operands`` over it and
``device_transport_device_operand_fallbacks``, the tensors that came as host
bytes or lay on another device). 100 where every link has a lane. ``None``
on a program without the adders or a window without such a call."""


def read(run):
    served = run.counters.get("device_transport_device_operands")
    fallbacks = run.counters.get("device_transport_device_operand_fallbacks")
    if served is None or fallbacks is None or not served + fallbacks:
        return None
    return 100.0 * served / (served + fallbacks)
