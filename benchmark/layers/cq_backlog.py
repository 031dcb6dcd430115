"""Host to HBM crossing and completion (runtime/device_butex.py): jobs
handed to the completion watchers that no watcher was free to take, seen at
each ``submit`` and counting its own job: 0 while a watcher is idle, over 0
once all ``CQ_THREADS`` are inside a job, so the job waits for one to end.
Mean of the program's ``device_transport_cq_backlog`` recorder over the
window, a row a ``submit``; ``None`` on a program without the recorder."""
from benchmark import stages


def read(run):
    return stages.mean(run, "device_transport_cq_backlog")
