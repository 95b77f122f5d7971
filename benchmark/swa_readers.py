"""How a device trace names the sliding-window layers' attention
(``layer_metrics/swa_*.py``).

A window layer decodes through the un-paged Pallas kernel ``flash_decode``
over its ring (the program's scope ``swa.decode``): a ``custom-call`` named
so, NOT ``flash_decode_paged`` (the full layers'), whose result has the
adapter's ``swa_kernel_shape`` (``[rows, kv heads, query heads a K/V head,
head size]``).  Its prompts run the windowed ``flash_attention_fwd`` (scope
``swa.prefill``), told from the full layers' by the window layers' count of
query heads (the adapter's ``swa_prefill_heads``): the kernel's result is a
tuple whose first part is ``[1, heads, T, head size]``.  An adapter without
the two functions (any other configuration's), or a program that has no
such kernel (this PR's parent), leaves nothing to read.
"""

from __future__ import annotations

import re
from typing import List, Tuple

from benchmark import trace_reduce
from benchmark.readers import _dims

_FWD = re.compile(r"^%?flash_attention_fwd[.\d]* = \(\w+\[1,(\d+),")


def decode_ops(run) -> List[Tuple[float, float]]:
    """(start, duration) of the window layers' decode kernel."""
    model = run["model"]
    if not hasattr(model, "swa_kernel_shape"):
        return []
    want = model.swa_kernel_shape(run["config"], run["counters"]["rows"])
    out = []
    for name, s, d in run["trace"].devices[0].ops:
        p = trace_reduce.parse_op(name)
        if d > 0 and p["opcode"] == "custom-call" \
                and re.match(r"flash_decode[.\d]*$", p["name"]) \
                and p["shape"] != "(tuple)" and _dims(p["shape"]) == want:
            out.append((s, d))
    return out


def prefill_ops(run) -> List[Tuple[float, float]]:
    """(start, duration) of the window layers' prefill kernel."""
    model = run["model"]
    if not hasattr(model, "swa_prefill_heads"):
        return []
    heads = int(model.swa_prefill_heads(run["config"]))
    out = []
    for name, s, d in run["trace"].devices[0].ops:
        m = _FWD.match(name)
        if d > 0 and m and int(m.group(1)) == heads:
            out.append((s, d))
    return out
