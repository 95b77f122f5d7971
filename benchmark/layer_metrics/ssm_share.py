"""Share of device busy time in the SSM's instructions (percent), prefill
and decode together: the one-token update (``ssm_state_roofline`` says which
instructions) and the SSD scan of a prefill, a ``while`` loop over the
prompt's chunks told by what it carries: one row's state, ``f32[1, heads,
head size, state]`` (the adapter's ``ssd_carry_shape``), and not the state
store.  The projections, the conv and the gated norm around them are XLA
instructions under no name of their own and are not counted.  Nothing to read
where the adapter names no state, or no such instruction ran.
Source: device trace."""

from benchmark import hybrid_readers, trace_reduce


def read(run):
    tr = run.get("trace")
    model = run["model"]
    if tr is None or not tr.devices or not hasattr(model, "ssd_carry_shape"):
        return None
    spans = [(s, s + d) for s, d in hybrid_readers.state_ops(run)
             + hybrid_readers.scan_loops(run)]
    busy = sum(e - s for s, e in trace_reduce.busy_intervals(tr.devices[0]))
    if not spans or busy <= 0:
        return None
    return 100.0 * sum(e - s for s, e in trace_reduce.union(spans)) / busy
