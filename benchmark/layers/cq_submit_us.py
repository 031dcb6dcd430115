"""Host to HBM crossing and completion (runtime/device_butex.py): the
dispatching thread's time inside ``_WatcherPool.submit``, the hand-over of
a watch to the completion watchers (an endpoint's drain or ``-tx`` thread,
a link's drive, a stream's writer: the thread that dispatches next). It is
the head of the stage that follows the owner's ``launched`` stamp
(``device_cq_wait_us``, ``link_ready_us``, ``lane_ready_us``). Mean of the
program's ``device_transport_cq_submit_us`` recorder over the window, a row
a ``submit``; ``None`` on a program without the recorder."""
from benchmark import stages


def read(run):
    return stages.mean(run, "device_transport_cq_submit_us")
