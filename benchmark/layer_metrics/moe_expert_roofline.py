"""The grouped expert kernels' share of their HBM roofline in decode
(percent): bytes of the held experts' weights the traced decode steps had to
read (the adapter's ``expert_step_bytes``: an expert's gate, up and down
matrices where at least one of the step's assignments fell on it; how many
experts a layer's step touched is the program's own count, the tick ring's
``moe_experts_touched`` over the traced seconds' blocks), over the
device time of the kernels ``moe_grouped_swiglu`` / ``moe_grouped_matmul``
(Pallas, told by name) at the decode step's shape (their result's leading dim
is the adapter's ``expert_kernel_rows`` for ``rows`` tokens) and the chip's
HBM bandwidth.  A decode step gives an expert ~9 of 64 rows, ~18 flops a byte
of weights: the bytes bound it.  Nothing to read where the adapter counts no
expert bytes, the ring has no such count, or no such kernel ran.
Source: device trace."""

from benchmark import hybrid_readers, tick_readers, trace_reduce
from benchmark.hybrid_readers import KERNELS
from benchmark.readers import _dims


def read(run):
    tr = run.get("trace")
    tw0, tw1 = run.get("trace_window") or (None, None)
    model, config = run["model"], run["config"]
    if tr is None or tw0 is None or not tr.devices \
            or not hasattr(model, "expert_step_bytes"):
        return None
    rows = model.expert_kernel_rows(config, run["counters"]["rows"])
    time_s = {k: 0.0 for k in KERNELS}
    runs = {k: 0 for k in KERNELS}
    for name, _, d in tr.devices[0].ops:
        p = trace_reduce.parse_op(name)
        k = hybrid_readers.expert_kernel(p)
        if k and p["shape"] != "(tuple)" and _dims(p["shape"])[0] == rows:
            time_s[k] += d
            runs[k] += 1
    if not runs[KERNELS[0]] or sum(time_s.values()) <= 0:
        return None
    # held experts a layer's step touched, in the mean over the traced
    # seconds' decode blocks (a block of k steps runs k x layers of them)
    blocks = [r for r in tick_readers.ring()
              if tw0 <= r["t"] < tw1 and r.get("name") == "decode.block"
              and "moe_experts_touched" in r]
    layer_steps = sum(r["k"] for r in blocks) * int(
        config["num_hidden_layers"])
    if not layer_steps:
        return None
    per = model.expert_step_bytes(
        config, sum(r["moe_experts_touched"] for r in blocks) / layer_steps)
    nbytes = sum(runs[k] * per[k] for k in KERNELS)
    return 100.0 * nbytes / run["device"]["peaks"]["hbm_bytes_per_s"] \
        / sum(time_s.values())
