"""Device time a step of the optimizer: the ops under the program's
``grad_clip`` (global norm, clip factor) and ``adamw`` (moments and
parameter update) scopes (``scopes.step_split``)."""

from chip import scopes


def read(run):
    return scopes.part_ms(run, "optimizer")
