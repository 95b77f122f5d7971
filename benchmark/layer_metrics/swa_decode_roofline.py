"""The window layers' decode kernel's share of its HBM roofline (percent):
bytes of K and V the traced window's decode steps HAD to read from the rings
(a row whose new token stands at position ``t`` attends ``min(t + 1,
window)`` positions in every window layer: the adapter's ``swa_read_bytes``,
summed over the tokens the records show decoded inside the traced seconds,
each at its own position), over the device time of the kernel
(``flash_decode`` at the adapter's ``swa_kernel_shape``:
``benchmark/swa_readers.py``) and the chip's HBM bandwidth.  The kernel
fetches a row's whole ring block whatever the row's context, so a batch of
short rows reads under 100% by that much; ~8 flops a byte: the bytes bound
it.  Nothing to read where the adapter counts no ring, or no such kernel
ran.  Source: device trace."""

from benchmark import swa_readers


def read(run):
    tr = run.get("trace")
    tw0, tw1 = run.get("trace_window") or (None, None)
    model = run["model"]
    if tr is None or tw0 is None or not tr.devices \
            or not hasattr(model, "swa_read_bytes"):
        return None
    kernel_s = sum(d for _, d in swa_readers.decode_ops(run))
    if kernel_s <= 0:
        return None
    # token k (k >= 1; token 0 is the prefill's) is decoded from the token
    # at position prompt_len + k - 1
    nbytes = sum(model.swa_read_bytes(run["config"], r.prompt_len + k - 1)
                 for r in run["records"]
                 for k, t in enumerate(r.token_times)
                 if k >= 1 and tw0 <= t < tw1)
    return 100.0 * nbytes / run["device"]["peaks"]["hbm_bytes_per_s"] \
        / kernel_s
