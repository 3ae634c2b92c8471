"""Share of the traced window in which a collective runs on a chip and no
compute does, on the chip where it is largest: ``trace.reduce``'s
``collective_exposed_s`` (collectives named as such, less the compute over
them), plus the time of the collective ops whose names it does not know
(the TPU compiler's ``async-collective-start``/``-done`` fusions, which a
chip's op line runs alone).  The log breaks collective time down by kind
and by the part of the step it serves (``scopes.step_split``)."""

from chip import scopes


def read(run):
    tr = run["trace"]
    if not tr:
        return None
    s = scopes.step_split(run)
    unnamed = s["unnamed_collective_s"] if s else {}
    return max(100.0 * (d["collective_exposed_s"] + unnamed.get(dev, 0.0))
               / tr["window_s"] for dev, d in tr["devices"].items())
