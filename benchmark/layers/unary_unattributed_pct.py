"""Host plane: the inside view of a unary call with a device attachment
checked against the lane's own recorders. The share of the mean call
(``unary_call_us``) that is covered neither by the four stages of the call
path (request_tx, server_dispatch, reply_tx, client_wake), nor by the
handler (the harness's spans), nor by the lane's flight from a launch to
the hand-over, once each way (``lane_ready_us`` + ``lane_pair_wait_us`` of
the same link, whose rows are the requests' and the answers' programs
alike). Negative where the stages overlap. ``None`` unless every recorder
has rows and the handler has spans."""
from benchmark import stages_unary


def read(run):
    call = stages_unary.link_recorder(run, "unary_call_us")
    path = [stages_unary.link_recorder(run, s) for s in stages_unary.CALL_STAGES]
    flight = [stages_unary.link_recorder(run, s) for s in stages_unary.FLIGHT_STAGES]
    if not call or None in path or None in flight or len(run.handler) == 0:
        return None
    handler = float((run.handler[:, 1] - run.handler[:, 0]).mean()) / 1e3
    return 100.0 * (call - sum(path) - 2 * sum(flight) - handler) / call
