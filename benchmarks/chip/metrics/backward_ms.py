"""Device time a step of the backward pass: the ops under
``transpose(jvp(...))`` of the program's ``embed``, ``blocks`` and
``final_norm`` scopes, the flash-attention backward kernels among them
(``scopes.step_split``)."""

from chip import scopes


def read(run):
    return scopes.part_ms(run, "backward")
