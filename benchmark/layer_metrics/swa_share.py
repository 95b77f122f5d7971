"""Share of device busy time in the sliding-window layers' attention kernels
(percent), decode and prefill together: ``flash_decode`` over the rings (the
program's scope ``swa.decode``) and the windowed ``flash_attention_fwd`` of
their prompts (``swa.prefill``), told as ``benchmark/swa_readers.py`` says.
The projections, the rope, the gate and the ring's writes around them are XLA
instructions under no name of their own and are not counted.  Nothing to
read where the adapter names no window layers, or no such kernel ran.
Source: device trace."""

from benchmark import swa_readers, trace_reduce


def read(run):
    tr = run.get("trace")
    if tr is None or not tr.devices \
            or not hasattr(run["model"], "swa_kernel_shape"):
        return None
    spans = [(s, s + d) for s, d in swa_readers.decode_ops(run)
             + swa_readers.prefill_ops(run)]
    busy = sum(e - s for s, e in trace_reduce.busy_intervals(tr.devices[0]))
    if not spans or busy <= 0:
        return None
    return 100.0 * sum(e - s for s, e in trace_reduce.union(spans)) / busy
