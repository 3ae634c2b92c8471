"""Device time a step of the forward pass: the ops under the program's
``embed``, ``blocks`` and ``final_norm`` scopes outside any
``transpose(...)`` (``scopes.step_split``)."""

from chip import scopes


def read(run):
    return scopes.part_ms(run, "forward")
