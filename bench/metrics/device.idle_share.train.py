"""Share of the traced window in which the device ran no program."""


def read(run):
    red = run["trace"]
    if not red["window_s"]:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
