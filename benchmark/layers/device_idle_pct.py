"""Device: the share of the window in which no operation ran on the chip,
averaged over the chips that did any work."""


def read(run):
    if run.busy_s is None:
        return None
    return 100.0 * (1.0 - run.busy_s / run.window_s)
