"""Share of device busy time spent closing EVA windows (percent): the runs
of the program that pools a window's exact entries into summaries, told by
its name (``jit_eva_roll*``, as ``module_runs`` tells decode blocks and
prefills).  A prompt's windows are closed by the same program as a decoding
row's.  Nothing to read where no such program ran.  Source: device trace."""

from benchmark import trace_reduce

PROGRAM = "jit_eva_roll"


def read(run):
    tr = run.get("trace")
    if tr is None or not tr.devices:
        return None
    busy = trace_reduce.busy_s(tr)
    rolls = [d for name, _, d in tr.devices[0].modules
             if name.split("(", 1)[0].startswith(PROGRAM)]
    if not rolls or busy <= 0:
        return None
    return 100.0 * sum(rolls) / busy
