"""The share of the routers' assignments that fell on the experts this chip
holds (percent), summed over the window's decode blocks: ``moe_assignments``
(those that fell on held experts) over ``moe_routed`` (every assignment the
routers made over all ``n_experts`` for the block's rows), both from the
tick ring's ``decode.block`` records.  16 of 256 experts held take 6.25%
when the routing is even; more is this chip's experts drawing more than
their share of the deployment's load.  Nothing to read where the program
has no ``moe_routed`` counter (it is this cell's PR's).
Source: program counter."""

from benchmark import tick_readers


def read(run):
    blocks = [r for r in tick_readers.ticks(run)
              if r.get("name") == "decode.block" and "moe_routed" in r]
    routed = sum(r["moe_routed"] for r in blocks)
    if not routed:
        return None
    return 100.0 * sum(r["moe_assignments"] for r in blocks) / routed
