"""How a device trace names the KDA mechanisms (``layer_metrics/kda_*.py``).

Both are told by what they touch, not by who implements them, so that the
same work is read whether XLA or a kernel does it: the one-token update is
every leaf instruction that names, as its result or as an operand, the
stacked KDA state store ``f32[kda layers, rows, heads * head size, head
size]`` (the adapter's ``kda_store_shape``; the program's update views it
as ``[kda layers, rows, H, dk, dv]``, a bitcast, and the trace names that
view) or ONE layer of it over all the rows in any of its views (``[1, rows,
H * dk, dv]``, ``[rows, H * dk, dv]``, ``[rows, H, dk, dv]``: XLA copies a
layer out of the store before it reduces it, and that pass and the reduction
are passes over the state too; a Pallas update would be one ``custom-call``
naming the store twice); a prefill's chunk scan is the ``while`` loop that
carries one row's state (the adapter's ``kda_carry_shape``) and not the
store.  An adapter without the two functions (any other configuration's)
leaves nothing to read.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

from benchmark import trace_reduce


def _f32(dims) -> str:
    return "f32[" + ",".join(str(d) for d in dims) + "]"


def store_shape(run) -> Optional[str]:
    model = run["model"]
    if not hasattr(model, "kda_store_shape"):
        return None
    return _f32(model.kda_store_shape(run["config"],
                                      run["counters"]["rows"]))


def carry_shape(run) -> Optional[str]:
    model = run["model"]
    if not hasattr(model, "kda_carry_shape"):
        return None
    return _f32(model.kda_carry_shape(run["config"]))


def state_shapes(run) -> List[str]:
    """The store (heads and key channels as one dim, or apart) and one layer
    of it over all the rows, in every view."""
    model = run["model"]
    if not hasattr(model, "kda_store_shape"):
        return []
    store = model.kda_store_shape(run["config"], run["counters"]["rows"])
    heads = model.kda_carry_shape(run["config"])[1:]
    return [_f32(d) for d in (store, store[:2] + heads, [1] + store[1:],
                              store[1:], [store[1]] + heads)]


def state_ops(run, kind: Optional[str] = None) -> List[Tuple[float, float]]:
    """(start, duration) of the leaf instructions that name the state store
    or a whole layer of it; with ``kind`` (``decode`` / ``prefill``) only
    those inside the program runs of that kind."""
    tr, want = run["trace"], state_shapes(run)
    if not want:
        return []
    spans = None if kind is None else sorted(
        (r["start"], r["start"] + r["dur"])
        for r in trace_reduce.module_runs(tr) if r["kind"] == kind)
    return [(s, d) for name, s, d in tr.devices[0].ops
            if d > 0 and any(w in name for w in want)
            and trace_reduce.is_leaf(name)
            and (spans is None or any(a <= s < b for a, b in spans))]


def scan_loops(run) -> List[Tuple[float, float]]:
    """(start, duration) of the chunk scans: ``while`` loops that carry one
    row's state and not the store."""
    store, carry = store_shape(run), carry_shape(run)
    if store is None or carry is None:
        return []
    return [(s, d) for name, s, d in run["trace"].devices[0].ops
            if d > 0 and trace_reduce.parse_op(name)["opcode"] == "while"
            and carry in name and store not in name]
