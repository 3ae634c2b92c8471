"""Model FLOP/s utilization of the traced window: FLOPs a token needs
(the configuration's reference module's ``train_flops_per_token``, no
recomputation) x tokens per second per chip over the traced window (host
clock, ended by a sync) / the chip's bf16 peak from ``peaks.json``."""


def read(run):
    rec = run["record"]
    if not rec.get("traced_steps"):
        return None
    per_chip = rec["traced_steps"] * rec["tokens_per_step"] \
        / rec["traced_window_s"] / run["chips"]
    return 100.0 * rec["flops_per_token"] * per_chip \
        / run["peak"]["bf16_flops_per_s"]
