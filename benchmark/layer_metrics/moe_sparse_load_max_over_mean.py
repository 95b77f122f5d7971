"""How uneven the routing is over the held experts (x), for a stack whose
expert layers and held experts are the adapter's to count:
``moe_load_max_over_mean``'s arithmetic (in each tick that ran a decode
block, the most assignments any one held expert of any layer took, the
ring's ``moe_expert_max``, over the mean per held expert and layer,
``moe_assignments`` / slots; the median over the window's ticks) with the
slots counted as the adapter's ``expert_layers`` x ``held_experts`` (that
reader counts ``num_hidden_layers`` x ``num_local_experts``: a fifth high at
4 sparse layers of 5, and a key this configuration does not have).  1.0 is
perfectly even; with 256 small experts a step gives each ~4 rows, so the
fullest takes several times the mean and its tiles set the kernels' step.
Nothing to read under an adapter without the two counts or a program
without the two fields.  Source: program counter."""

import statistics

from benchmark import tick_readers


def read(run):
    model, config = run["model"], run["config"]
    if not (hasattr(model, "expert_layers")
            and hasattr(model, "held_experts")):
        return None
    slots = model.expert_layers(config) * model.held_experts(config)
    ratios = [r["moe_expert_max"] * slots / r["moe_assignments"]
              for r in tick_readers.ticks(run)
              if r.get("moe_assignments", 0) > 0 and "moe_expert_max" in r]
    return statistics.median(ratios) if ratios else None
