"""Share of device busy time in the KDA recurrence (percent), prefill and
decode together: the one-token update (``kda_state_roofline`` says which
instructions) and a prefill's chunk scan, a ``while`` loop over the prompt's
chunks told by what it carries: one row's state, ``f32[1, heads, head size,
head size]`` (the adapter's ``kda_carry_shape``), and not the state store.
The projections, the conv, the gates and the norm around them are XLA
instructions under no name of their own and are not counted.  Nothing to read
where the adapter names no KDA state, or no such instruction ran.
Source: device trace."""

from benchmark import kda_readers, trace_reduce


def read(run):
    tr = run.get("trace")
    if tr is None or not tr.devices \
            or not hasattr(run["model"], "kda_carry_shape"):
        return None
    spans = [(s, s + d) for s, d in kda_readers.state_ops(run)
             + kda_readers.scan_loops(run)]
    busy = sum(e - s for s, e in trace_reduce.busy_intervals(tr.devices[0]))
    if not spans or busy <= 0:
        return None
    return 100.0 * sum(e - s for s, e in trace_reduce.union(spans)) / busy
