"""The paged-decode kernel's share of its HBM roofline under EVA (percent):
bytes of the cache ENTRIES that the traced window's decode steps had to read
(token ``k >= 1`` of a request is one step over ``cache_entries(prompt_len +
k)`` entries; the adapter's ``decode_read_bytes``), over the device time of
the Pallas custom-call whose output has the adapter's ``paged_kernel_shape``
(``[rows, 32, 1, 128]``) and the chip's HBM bandwidth.  ``paged_decode_
roofline`` multiplies context positions by the bytes of one and would read
several times too high here.  Nothing to read where the adapter counts no
entries.  Source: device trace."""

from benchmark import trace_reduce
from benchmark.readers import _dims


def read(run):
    tr = run.get("trace")
    tw0, tw1 = run.get("trace_window") or (None, None)
    model = run["model"]
    if tr is None or tw0 is None or not tr.devices \
            or not hasattr(model, "decode_read_bytes"):
        return None
    want = model.paged_kernel_shape(run["config"], run["counters"]["rows"])
    kernel_s = 0.0
    for name, _, d in tr.devices[0].ops:
        p = trace_reduce.parse_op(name)
        if p["opcode"] == "custom-call" and p["shape"] != "(tuple)" \
                and _dims(p["shape"]) == want:
            kernel_s += d
    if kernel_s <= 0:
        return None
    contexts = [r.prompt_len + k for r in run["records"]
                for k, t in enumerate(r.token_times)
                if k >= 1 and tw0 <= t < tw1]
    nbytes = model.decode_read_bytes(run["config"], contexts)
    floor_s = nbytes / run["device"]["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / kernel_s
