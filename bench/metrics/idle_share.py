"""Share of the traced window in which no program ran on the device."""


def read(run, metric):
    if run.trace is None or not run.trace["window_s"]:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
