"""How full the grouped expert kernels' live tiles are in decode (percent):
assignment rows over the rows of the tiles they were padded to, summed over
the window's decode blocks.  The sorted buffer gives every expert that took
an assignment whole tiles (``ops/moe.grouped_layout``), so with many small
experts (4 of a step's 128 rows each, a tile of 16) most of a live tile is
padding the kernels still multiply.  Both from the tick ring:
``moe_assignments`` and ``moe_tile_rows`` of each ``decode.block`` record.
Nothing to read where the program has no such counters (``moe_tile_rows`` is
this cell's PR's).  Source: program counter."""

from benchmark import tick_readers


def read(run):
    blocks = [r for r in tick_readers.ticks(run)
              if r.get("name") == "decode.block" and "moe_tile_rows" in r]
    padded = sum(r["moe_tile_rows"] for r in blocks)
    if not padded:
        return None
    return 100.0 * sum(r["moe_assignments"] for r in blocks) / padded
