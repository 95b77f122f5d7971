"""Context positions the decoding rows held over the positions the window
layers' rings held of them, summed over the window's decode blocks: what the
window cache saves a window layer (1.0 where every row is inside one window;
a full layer keeps every position).  Both from the tick ring: ``ctx_positions``
and ``swa_positions`` of each ``decode.block`` record.  Nothing to read where
the program has no such counters.  Source: program counter."""

from benchmark import tick_readers


def read(run):
    blocks = [r for r in tick_readers.ticks(run)
              if r.get("name") == "decode.block" and "swa_positions" in r]
    held = sum(r["swa_positions"] for r in blocks)
    if not held:
        return None
    return sum(r["ctx_positions"] for r in blocks) / held
