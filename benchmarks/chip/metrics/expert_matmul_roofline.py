"""Share of its roofline that the held experts' grouped matrix products
reach: the least time the chip could take for the FLOPs and bytes they
need (the reference module's ``expert_matmul_cost`` of the routed items
the program counted in the timed steps) over their measured device time
per step and chip.  The bound (compute or memory) is logged."""

from chip import flops, moe_trace


def read(run):
    rec = run["record"]
    cost = rec.get("expert_matmul_cost")
    s = moe_trace.grouped_matmul_seconds(run)
    if cost is None or s is None:
        return None
    chips = run["chips"]
    share, bound = flops.roofline(cost["flops"] / chips,
                                  cost["bytes"] / chips, s, run["peak"])
    print(f"[roofline] expert matmuls are {bound}-bound: {share:.2f}% of "
          "their roofline", flush=True)
    return share
