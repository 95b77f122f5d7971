"""Share of device busy time in the sink-carrying window layers' attention
kernels (percent), decode and prefill together: ``flash_decode`` over the
rings at the adapter's ``swa_kernel_shape`` (the program's scope
``swa.decode``; ``benchmark/swa_readers.decode_ops``) and the window layers'
forward as THIS configuration's trace tells it (the adapter's
``swa_forward_ops``: the flash forward that carries a sink is a kernel of
its own name, because both kinds of layer have the same count of query
heads here and ``swa_share``'s reader would count the full layers' forward
as the window's).  The projections, the rope and the rings' writes around
them are XLA instructions under no name of their own and are not counted.
Nothing to read where the adapter names no such forward (any other
configuration's), or no such kernel ran (a program without the sink).
Source: device trace."""

from benchmark import swa_readers, trace_reduce


def read(run):
    tr = run.get("trace")
    model = run["model"]
    if tr is None or not tr.devices or not hasattr(model, "swa_forward_ops"):
        return None
    spans = [(s, s + d) for s, d in swa_readers.decode_ops(run)
             + model.swa_forward_ops(run)]
    busy = sum(e - s for s, e in trace_reduce.busy_intervals(tr.devices[0]))
    if not spans or busy <= 0:
        return None
    return 100.0 * sum(e - s for s, e in trace_reduce.union(spans)) / busy
