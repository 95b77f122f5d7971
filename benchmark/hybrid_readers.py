"""What the readers of a typed stack's two new mechanisms share (``layer_
metrics/ssm_*.py``, ``moe_*.py``): how the device trace names them.

The grouped expert matmul is two Pallas kernels, told by name.  The SSM
mechanisms are XLA instructions, told by what they touch: the one-token
update is every leaf instruction that names the stacked state store,
``f32[mamba layers, rows, heads * head size, state]`` (the adapter's
``ssm_state_shape``), as its result or as an operand; the SSD scan of a
prefill is the ``while`` loop that carries one row's state (the adapter's
``ssd_carry_shape``) and not the store.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

from benchmark import trace_reduce

KERNELS = ("moe_grouped_swiglu", "moe_grouped_matmul")


def expert_kernel(parsed) -> Optional[str]:
    """Which grouped expert kernel a parsed instruction is, or None."""
    if parsed["opcode"] != "custom-call":
        return None
    return next((k for k in KERNELS if parsed["name"].startswith(k)), None)


def _f32(dims) -> str:
    return "f32[" + ",".join(str(d) for d in dims) + "]"


def store_shape(run) -> str:
    model, config = run["model"], run["config"]
    layers = list(config["layer_types"]).count("mamba")
    return _f32([layers] + model.ssm_state_shape(config,
                                                 run["counters"]["rows"]))


def carry_shape(run) -> str:
    return _f32(run["model"].ssd_carry_shape(run["config"]))


def state_ops(run, kind: Optional[str] = None) -> List[Tuple[float, float]]:
    """(start, duration) of the leaf instructions that name the state
    store; with ``kind`` (``decode`` / ``prefill``) only those inside the
    program runs of that kind."""
    tr = run["trace"]
    want = store_shape(run)
    spans = None if kind is None else sorted(
        (r["start"], r["start"] + r["dur"])
        for r in trace_reduce.module_runs(tr) if r["kind"] == kind)
    return [(s, d) for name, s, d in tr.devices[0].ops
            if d > 0 and want in name and trace_reduce.is_leaf(name)
            and (spans is None or any(a <= s < b for a, b in spans))]


def scan_loops(run) -> List[Tuple[float, float]]:
    """(start, duration) of the SSD scans: ``while`` loops that carry one
    row's state and not the store."""
    store, carry = store_shape(run), carry_shape(run)
    return [(s, d) for name, s, d in run["trace"].devices[0].ops
            if d > 0 and trace_reduce.parse_op(name)["opcode"] == "while"
            and carry in name and store not in name]
