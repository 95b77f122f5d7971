"""Share of device busy time in the grouped expert kernels (percent):
``moe_grouped_swiglu`` and ``moe_grouped_matmul`` (Pallas, told by name),
prefill and decode together.  The routing and the sort around them are XLA
instructions under no name of their own and are not counted.  Nothing to
read where no such kernel ran.  Source: device trace."""

from benchmark import hybrid_readers, trace_reduce


def read(run):
    tr = run.get("trace")
    if tr is None or not tr.devices:
        return None
    share = trace_reduce.share_of_busy(
        tr, lambda p, text: hybrid_readers.expert_kernel(p) is not None)
    return share or None
