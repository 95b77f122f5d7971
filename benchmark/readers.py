"""The reductions the per-layer readers share.  A reader is
``layer_metrics/<metric>.py`` with ``read(run) -> number | None``; most are
one line that names a function of this module.  ``run`` is what a driver
hands over: the client-side records, the window, the configuration and
its model adapter (``run["model"]``: the shapes and bytes that belong to one
block), the device with its peaks, and in a traced run the reduced trace.
A reader that finds nothing to read returns None.
"""

from __future__ import annotations

import statistics
from typing import List, Optional

from benchmark import trace_reduce, window


def _due_in_window(run):
    return [r for r in run["records"] if run["t0"] <= r.due < run["t1"]]


def gen_late_p99_ms(run) -> Optional[float]:
    """How late the generator called submit(): against the due time, over
    the requests due in the window (open loop); a backlog is due all at
    once when the generator starts, so how long after that each submit()
    was made."""
    recs = (_due_in_window(run) if run["schedule"].kind == "open_loop"
            else run["records"])
    late = [r.submit - r.due for r in recs if r.submit is not None]
    return 1e3 * window.percentile(late, 99) if late else None


def decode_rows_mean(run) -> Optional[float]:
    return window.live_rows_mean(run["records"], run["t0"], run["t1"])


def pool_fill(run) -> Optional[float]:
    """Share of the paged pool's token slots (the adapter's count; for a
    homogeneous stack n_pages x page_size) that held a live request's
    context, time-averaged over the window.  The pool is reserved whole
    whatever the traffic; this says how much of the reservation the cell's
    traffic uses."""
    slots = run["model"].token_slots(run["config"], run["counters"])
    return 100.0 * window.live_tokens_mean(
        run["records"], run["t0"], run["t1"]) / slots


def _module_durations(run, kind: str) -> List[float]:
    tr = run.get("trace")
    if tr is None:
        return []
    return [r["dur"] for r in trace_reduce.module_runs(tr)
            if r["kind"] == kind]


def prefill_p50_ms(run) -> Optional[float]:
    d = _module_durations(run, "prefill")
    return 1e3 * statistics.median(d) if d else None


def decode_block_ms_p50(run) -> Optional[float]:
    d = _module_durations(run, "decode")
    return 1e3 * statistics.median(d) if d else None


def _dims(text_shape: str) -> List[int]:
    inner = text_shape[text_shape.find("[") + 1:text_shape.rfind("]")]
    return [int(x) for x in inner.split(",") if x]


def paged_decode_roofline(run) -> Optional[float]:
    """Bytes of cached K and V the decode steps of the traced window had
    to read for their live contexts (the adapter's bytes per context
    token), over the device time of the paged-decode kernel (the Pallas
    custom-call whose output has the adapter's ``paged_kernel_shape``) and
    the chip's HBM bandwidth."""
    tr = run.get("trace")
    tw0, tw1 = run.get("trace_window", (None, None))
    if tr is None or tw0 is None or not tr.devices:
        return None
    model = run["model"]
    want = model.paged_kernel_shape(run["config"], run["counters"]["rows"])
    kernel_s = 0.0
    for name, _, d in tr.devices[0].ops:
        p = trace_reduce.parse_op(name)
        if p["opcode"] == "custom-call" and p["shape"] != "(tuple)" \
                and _dims(p["shape"]) == want:
            kernel_s += d
    if kernel_s <= 0:
        return None
    nbytes = window.decode_read_bytes(
        run["records"], tw0, tw1,
        model.kv_bytes_per_context_token(run["config"]))
    floor_s = nbytes / run["device"]["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / kernel_s


def attn_kernel_share(run) -> Optional[float]:
    """Pallas attention custom-calls' share of device busy time."""
    tr = run.get("trace")
    if tr is None:
        return None
    return trace_reduce.share_of_busy(
        tr, lambda p, text: p["opcode"] == "custom-call")


def pool_copy_share(run) -> Optional[float]:
    """Share of device busy time in ``copy`` and dynamic-slice instructions
    whose result has one of the adapter's ``pool_leaf_shapes`` (a leaf of
    the paged pool or one layer of it): whole-pool traffic that serves no
    token."""
    tr = run.get("trace")
    if tr is None:
        return None
    pool = run["model"].pool_leaf_shapes(run["config"], run["counters"])

    def pick(p, text):
        if p["shape"] == "(tuple)" or not p["shape"]:
            return False
        if not (p["opcode"] in ("copy", "dynamic-slice")
                or (p["opcode"] == "fusion" and "dynamic-slice" in p["name"])):
            return False
        return _dims(p["shape"]) in pool

    return trace_reduce.share_of_busy(tr, pick)
