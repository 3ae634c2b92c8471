"""Device time of the flash-attention kernels (forward, backward dq and
dkv) per step, summed per chip from the trace and averaged over chips."""

from chip import trace


def read(run):
    tr, rec = run["trace"], run["record"]
    if not tr:
        return None
    s = trace.seconds_per_step(tr, r"flash_attention_(fwd|bwd_dq|bwd_dkv)",
                               rec.get("traced_steps", 0))
    return None if s is None else 1e3 * s
