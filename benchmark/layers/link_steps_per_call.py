"""Link: exchange steps the window dispatched (``device_link_steps``) for
each correct call it completed."""


def read(run):
    steps = run.counters.get("device_link_steps")
    if not steps or len(run.done) == 0:
        return None
    return steps / len(run.done)
