"""Share of the traced window in which no operation ran on the device:
1 - union of the device's op intervals / window, on the most idle chip."""


def read(run):
    tr = run["trace"]
    if not tr:
        return None
    w = tr["window_s"]
    return max(100.0 * (1.0 - d["busy_s"] / w) for d in tr["devices"].values())
