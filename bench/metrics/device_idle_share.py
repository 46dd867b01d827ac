"""device_idle_share: 1 - (union of the device's operation intervals) /
traced window, in %. The longest gaps, named by the host span they fall
in, go to the result's breakdown."""


def read(run):
    if run.trace is None:
        return None
    share = run.trace.idle_share()
    return None if share is None else 100.0 * share
