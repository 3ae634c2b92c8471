"""Share of its roofline that the flash-attention kernels reach: the least
time the chip could take for the FLOPs and bytes the kernels need (the
reference module's ``flash_attention_cost``, from shapes) over their
measured device time per step and chip.  The bound (compute or memory)
is logged."""

from chip import flops, trace


def read(run):
    tr, rec = run["trace"], run["record"]
    if not tr:
        return None
    s = trace.seconds_per_step(tr, r"flash_attention_(fwd|bwd_dq|bwd_dkv)",
                               rec.get("traced_steps", 0))
    if s is None:
        return None
    cost, chips = rec["attention_cost"], run["chips"]
    share, bound = flops.roofline(cost["flops"] / chips,
                                  cost["bytes"] / chips, s, run["peak"])
    print(f"[roofline] flash_attention is {bound}-bound: {share:.2f}% of "
          "its roofline", flush=True)
    return share
