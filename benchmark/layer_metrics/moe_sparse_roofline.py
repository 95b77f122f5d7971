"""The grouped expert kernels' share of their HBM roofline in decode
(percent), for a stack whose leading layers hold no experts:
``moe_expert_roofline``'s reader with the layer-steps counted over the
adapter's ``expert_layers`` (the layers that run the kernels), not
``num_hidden_layers`` (that reader would read a fifth low at 4 sparse layers
of 5): bytes of expert weights the traced decode steps had to read (the
adapter's ``expert_step_bytes`` at the tick ring's ``moe_experts_touched`` a
layer-step) over the device time of ``moe_grouped_swiglu`` /
``moe_grouped_matmul`` at the decode step's shape and the chip's HBM
bandwidth.  Nothing to read where the adapter counts no expert layers, the
ring has no such count, or no such kernel ran.  Source: device trace."""

from benchmark import harness


def read(run):
    model, config = run["model"], run["config"]
    if not hasattr(model, "expert_layers"):
        return None
    layers = model.expert_layers(config)
    return harness.load_reader("moe_expert_roofline")(
        dict(run, config=dict(config, num_hidden_layers=layers)))
