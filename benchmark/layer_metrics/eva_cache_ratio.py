"""Context positions the live requests held over the cache entries that held
them, both time-averaged over the window: what EVA's summaries buy (1.0 for
plain attention, one entry a position).  Positions from the token callbacks
(``window.live_tokens_mean``), entries from ``eva_pool_fill``'s reading of the
tick ring (its share of the pool's entry slots, times the slots); nothing to
read where the program has no such counters.  Source: program counter."""

from benchmark import harness, window


def read(run):
    fill = harness.load_reader("eva_pool_fill")(run)
    if not fill:
        return None
    held = fill / 100.0 * run["model"].token_slots(run["config"],
                                                   run["counters"])
    return window.live_tokens_mean(run["records"], run["t0"],
                                   run["t1"]) / held
