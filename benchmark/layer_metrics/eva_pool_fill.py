"""Share of the paged pool's entry slots that held a live row's cache entries
(EVA summaries and the open windows' exact K/V), time-averaged over the
window's ticks (percent).  ``pool_fill`` counts context POSITIONS, which an
EVA pool does not hold one entry each; this reads the program's own counters,
``eva_summary_entries`` and ``eva_window_entries`` of the tick ring.  A
program without them (no EVA attention) leaves nothing to read.
Source: program counter."""

from benchmark import tick_readers


def read(run):
    # each tick's count at its end, weighted by the tick's wall time
    recs = [r for r in tick_readers.ticks(run) if "eva_summary_entries" in r]
    span = sum(r["wall_ms"] for r in recs)
    if not recs or span <= 0:
        return None
    held = sum((r["eva_summary_entries"] + r["eva_window_entries"])
               * r["wall_ms"] for r in recs) / span
    return 100.0 * held / run["model"].token_slots(run["config"],
                                                   run["counters"])
