"""The one-token KDA update's share of its HBM roofline (percent): bytes of
recurrent state the traced window's decode steps had to read and write (each
row that decodes a token moves its float32 state of every KDA layer once in
and once out: the adapter's ``kda_step_bytes``), over the device time of the
instructions that touch the state inside the decode programs and the chip's
HBM bandwidth.  Told by shape (``benchmark/kda_readers.py``): a leaf
instruction of a ``jit_decode_block`` run whose text names the stacked state
store, ``f32[kda layers, rows, heads * head size, head size]`` (the adapter's
``kda_store_shape``), or one whole layer of it, as its result or as an
operand: the same work whether XLA's fusions or a kernel do it.  ~3 flops a
byte: the bytes bound it.  An update that reads the state more often than
once reads under 100% by that much.  Nothing to read where the adapter
counts no KDA state, or no such instruction ran.  Source: device trace."""

from benchmark import kda_readers


def read(run):
    tr = run.get("trace")
    tw0, tw1 = run.get("trace_window") or (None, None)
    model = run["model"]
    if tr is None or tw0 is None or not tr.devices \
            or not hasattr(model, "kda_step_bytes"):
        return None
    update_s = sum(d for _, d in kda_readers.state_ops(run, "decode"))
    if update_s <= 0:
        return None
    row_steps = sum(1 for r in run["records"]
                    for k, t in enumerate(r.token_times)
                    if k >= 1 and tw0 <= t < tw1)
    nbytes = row_steps * model.kda_step_bytes(run["config"], 1)
    return 100.0 * nbytes / run["device"]["peaks"]["hbm_bytes_per_s"] \
        / update_s
