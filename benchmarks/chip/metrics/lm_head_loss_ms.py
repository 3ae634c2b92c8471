"""Device time a step of the LM head and the cross entropy over the
vocabulary, forward and backward: the ops under the program's
``lm_head`` and ``xent_loss`` scopes (``scopes.step_split``)."""

from chip import scopes


def read(run):
    return scopes.part_ms(run, "lm_head_loss")
