"""The attention forward's share of the chip's peak in prefill (percent): the
multiply-adds the prefills lying WHOLE inside the traced window had to do in
the attention forward of both kinds of layer (the adapter's
``attn_fwd_flops`` at each such prompt's REAL length: the padding to a
bucket is the program's cost, not work it had to do), over the device time
of those prefills' forward kernels (every ``flash_attention_fwd*``
custom-call that starts inside such a run of ``jit_prefill``: the sinkless
forward of the full layers, the sink forward of the window layers, the
segments of a prompt past ``flash_max_keys``) and the chip's bfloat16 peak.

A run's real length is the longest prompt the cell offers that fits its
padded width (``trace_reduce.module_runs`` reads the width from the run's own
instructions; in a cell whose lengths map one to one onto widths, as
``agent_batch``'s 15 do, that IS the prompt's length; where two lengths
share a width the longer is counted, which the kernels' work at the padded
width still covers).  A prefill the trace's edge cuts is no whole run and is
left out of both sides, so the share cannot pass 100%.  Nothing to read
where the adapter counts no such flops or no whole prefill ran.
Source: device trace."""

import bisect

from benchmark import trace_reduce


def read(run):
    tr = run.get("trace")
    model = run["model"]
    if tr is None or not tr.devices or not hasattr(model, "attn_fwd_flops"):
        return None
    offered = sorted({len(p.prompt) for p in run["schedule"].requests})
    whole = [r for r in trace_reduce.module_runs(tr)
             if r["kind"] == "prefill" and r["width"]]
    spans = sorted((r["start"], r["start"] + r["dur"]) for r in whole)
    flops = 0
    for r in whole:
        i = bisect.bisect_right(offered, r["width"])
        if i:
            flops += model.attn_fwd_flops(run["config"], offered[i - 1])
    starts = [s for s, _ in spans]
    kernel_s = 0.0
    for name, s, d in tr.devices[0].ops:
        if d <= 0 or "flash_attention_fwd" not in name.split(" = ", 1)[0]:
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < spans[i][1]:
            kernel_s += d
    if not flops or kernel_s <= 0:
        return None
    return 100.0 * flops / run["device"]["peaks"]["bf16_flops"] / kernel_s
