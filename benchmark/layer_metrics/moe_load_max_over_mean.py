"""How uneven the routing is over the held experts (x): in each tick that
ran a decode block, the most assignments any one held expert of any layer
took (the ring's ``moe_expert_max``) over the mean per held expert and layer
(``moe_assignments`` / (layers x held experts)); the median over the window's
ticks.  1.0 is perfectly even; a grouped kernel's step is as long as its
fullest expert's tiles.  A program without the two fields (no grouped expert
layer) leaves nothing to read.  Source: program counter."""

import statistics

from benchmark import tick_readers


def read(run):
    config = run["config"]
    slots = int(config["num_hidden_layers"]) * int(config["num_local_experts"])
    ratios = [r["moe_expert_max"] * slots / r["moe_assignments"]
              for r in tick_readers.ticks(run)
              if r.get("moe_assignments", 0) > 0]
    return statistics.median(ratios) if ratios else None
