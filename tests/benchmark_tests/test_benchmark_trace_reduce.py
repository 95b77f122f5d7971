"""trace_reduce.py and the device readers on a recorded trace: 0.87 s of
``mistral7b.chat_steady`` on one v5e chip (my chip run, PR 27, started as
``python3``, seed 2700000001), cut by ``cut_trace.py`` (beside this file) to the lines
the reduction reads; and on a small trace made here (``xplane_wire.py``)."""

import gzip
import os

import pytest

import xplane_wire as xw            # a helper beside this file
from benchmark import harness, readers, trace_reduce as tr
from benchmark.models import mistral

TRACE = os.path.join(harness.HERE, "testdata", "serve_v5e_slice.xplane.pb.gz")


@pytest.fixture(scope="module")
def trace():
    return tr.load(TRACE)


def test_parse_op():
    p = tr.parse_op("%copy.53 = bf16[16,1300,8,64,128]{4,2,3,1,0:T(8,128)(2,1)}"
                    " copy(bf16[16,1300,8,64,128]{4,3,2,1,0} %pool__v__.1)")
    assert p == {"name": "copy.53", "shape": "bf16[16,1300,8,64,128]",
                 "opcode": "copy"}
    p = tr.parse_op("%while.19 = (s32[]{:T(128)}, bf16[32,1,4096]{2,0,1}) "
                    "while((s32[], bf16[32,1,4096]) %tuple), body=%b")
    assert p["opcode"] == "while" and p["shape"] == "(tuple)"
    assert not tr.is_leaf("%while.19 = (s32[]) while(%x)")
    assert tr.parse_op("np.asarray(jax.Array)")["opcode"] == ""


def test_union():
    assert tr.union([(0, 2), (1, 3), (5, 6), (5.5, 5.8)]) == [(0, 3), (5, 6)]


def test_busy_and_window(trace):
    assert len(trace.devices) == 1
    assert trace.window_s == pytest.approx(0.8787, abs=0.001)
    busy = tr.busy_s(trace)
    assert 0.8 * trace.window_s < busy < trace.window_s
    assert busy == pytest.approx(0.7601, abs=0.001)


def test_host_line_is_found_by_its_spans_whatever_it_is_called(trace):
    """The run was started as ``python3`` and the profiler calls the serve
    thread's line so; it carries the program's ``batcher.*`` spans."""
    from jax.profiler import ProfileData
    with gzip.open(TRACE, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    host = next(p for p in data.planes if p.name == "/host:CPU")
    assert [ln.name for ln in host.lines] == ["python3"]
    assert len(trace.host) == 1012
    assert {n for n, _, _ in trace.host if n.startswith("batcher.")} == {
        "batcher." + k for k in ("pull", "admit", "prefill_sync", "prep",
                                 "dispatch", "readback", "retire", "emit")}


def test_breakdown_names_the_kernels_and_the_phases(trace):
    b = tr.breakdown(trace)
    ops = dict(map(tuple, b["device_ops"]))
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    assert max(ops, key=ops.get) == \
        "custom-call flash_decode_paged.7 bf16[32,8,4,128]"
    # since PR 25 no instruction's result is the pool or a layer of it
    assert not any("bf16[16,1300,8,64,128]" in k for k in ops)
    assert not any(k.startswith("while") for k in ops)
    gaps = dict(map(tuple, b["idle_gaps"]))
    assert max(gaps, key=gaps.get) == "batcher.readback"
    assert gaps["batcher.readback"] == pytest.approx(0.1003, abs=0.001)
    assert "batcher.prefill_sync" in gaps and "(no host span)" not in gaps


def test_module_runs_tell_decode_from_prefill_by_name(trace):
    runs = tr.module_runs(trace)
    decode = [r for r in runs if r["kind"] == "decode"]
    prefill = [r for r in runs if r["kind"] == "prefill"]
    # the slice's last run, a whole decode block, ends with the trace: out
    assert len(trace.devices[0].modules) == 40
    assert len(decode) == 35 and len(prefill) == 4 and len(runs) == 39
    assert all(r["name"].startswith("jit_decode_block(") for r in decode)
    # every prefill has a width: the program still calls its prompt so
    assert [r["width"] for r in prefill] == [512, 384, 768, 192]
    assert sum(0.0158 < r["dur"] < 0.0164 for r in decode) == 35


def _run(trace):
    cfg = {"hidden_size": 4096, "num_attention_heads": 32,
           "num_key_value_heads": 8, "head_dim": 128,
           "num_hidden_layers": 16, "intermediate_size": 14336,
           "vocab_size": 32768}
    return {"trace": trace, "config": cfg, "model": mistral, "records": [],
            "trace_window": (0.0, 3.0),
            "device": {"peaks": {"hbm_bytes_per_s": 819e9}},
            "counters": {"rows": 32, "n_pages": 1300, "page_size": 64}}


def test_device_readers(trace):
    run = _run(trace)
    assert readers.decode_block_ms_p50(run) == pytest.approx(16.04, abs=0.05)
    assert readers.prefill_p50_ms(run) == pytest.approx(41.19, abs=0.05)
    assert readers.pool_copy_share(run) == 0.0
    assert readers.attn_kernel_share(run) == pytest.approx(23.0, abs=0.5)
    # no decode step on record: zero bytes over a real kernel time
    assert readers.paged_decode_roofline(run) == 0.0


def test_roofline_from_known_bytes(trace):
    from benchmark.window import Served
    run = _run(trace)
    # one request that decoded 10 tokens over a 1000-token prompt
    r = Served(index=0, prompt_len=1000, max_new_tokens=11, due=0.0,
               token_times=[0.1 + 0.1 * k for k in range(11)],
               tokens=[0] * 11)
    run["records"] = [r]
    nbytes = sum(1000 + k for k in range(1, 11)) * 16 * 2 * 8 * 128 * 2
    kernel_s = 0.170096        # the paged kernel's time in this trace
    want = 100 * nbytes / 819e9 / kernel_s
    assert readers.paged_decode_roofline(run) == pytest.approx(want, rel=1e-3)


# -- a trace made here ------------------------------------------------------

MS = 10 ** 9       # picoseconds


def _synthetic(tmp_path, host_line="python3"):
    """One chip: a decode block whose layers are unrolled (no ``while``), a
    prefill 96 wide, a page copy and a copy the size of a pool leaf, and
    last, as in docqa_batch's traces, a prefill 160 wide that is still
    going when the trace stops: the profiler closes it and the one
    instruction under it there, at 1 ms; the device idles 4 ms between the
    first three programs.  The serve thread's line, called as the profiler
    would call it, carries two phases; another thread's line carries
    none."""
    ops = {1: "jit_decode_block(11)", 2: "jit_prefill(12)",
           3: "jit__copy_page(13)",
           10: "%fusion.1 = bf16[4,1,64]{2,1,0} fusion(bf16[4,1,64]{2,1,0} "
               "%get-tuple-element.1), kind=kLoop",
           11: "%flash_decode_paged.1 = bf16[4,2,2,16]{3,2,1,0} custom-call("
               "bf16[4,2,2,16]{3,2,1,0} %q), custom_call_target=\"tpu\"",
           12: "%fusion.9 = s32[96]{0:T(128)} fusion(s32[1,96]{1,0:T(1,128)} "
               "%prompt.1), kind=kLoop",
           13: "%copy.5 = bf16[2,80,2,16,16]{4,2,3,1,0} copy("
               "bf16[2,80,2,16,16]{4,3,2,1,0} %pool.1)",
           14: "%fusion.9 = s32[160]{0:T(256)} fusion(s32[1,160]{1,0:"
               "T(1,128)} %prompt.1), kind=kLoop"}
    device = xw.plane("/device:TPU:0", [
        xw.line("XLA Modules", [(1, 0, 6 * MS), (2, 10 * MS, 5 * MS),
                                (3, 19 * MS, 1 * MS), (2, 20 * MS, 1 * MS)],
                1),
        xw.line("XLA Ops", [(10, 0, 4 * MS), (11, 4 * MS, 2 * MS),
                            (12, 10 * MS, 5 * MS), (13, 19 * MS, 1 * MS),
                            (14, 20 * MS, 1 * MS)], 2)], ops)
    host = xw.plane("/host:CPU", [
        xw.line(host_line, [(1, 5 * MS, 6 * MS), (3, 6 * MS, 3 * MS),
                            (2, 14 * MS, 6 * MS)], 7),
        xw.line("pjrt-tpu-tasks/330", [(4, 0, 20 * MS)], 8)],
        {1: "batcher.readback", 2: "batcher.prefill_sync",
         3: "np.asarray(jax.Array)", 4: "H2D Dispatch"}, 1)
    path = os.path.join(tmp_path, "made.xplane.pb.gz")
    with gzip.open(path, "wb") as f:
        f.write(xw.space([device, host]))
    return tr.load(path)


@pytest.mark.parametrize("host_line", ["python3", "python", "serve/4242"])
def test_made_trace_host_line_and_idle_gaps(tmp_path, host_line):
    t = _synthetic(str(tmp_path), host_line)
    assert [n for n, _, _ in t.host] == [
        "batcher.readback", "np.asarray(jax.Array)", "batcher.prefill_sync"]
    assert t.window_s == pytest.approx(0.021)
    assert tr.busy_s(t) == pytest.approx(0.013)    # the cut run's time too
    assert dict(map(tuple, tr.idle_gaps(t))) == {
        "batcher.readback": pytest.approx(0.004),
        "batcher.prefill_sync": pytest.approx(0.004)}


def test_made_trace_programs_go_by_name_not_by_a_carried_shape(tmp_path):
    runs = tr.module_runs(_synthetic(str(tmp_path)))
    assert [(r["kind"], r["width"]) for r in runs] == [
        ("decode", None), ("prefill", 96), ("other", None)]
    assert [r["dur"] for r in runs] == pytest.approx([0.006, 0.005, 0.001])


def test_made_trace_a_run_cut_by_the_end_of_the_trace_is_no_run(tmp_path):
    """docqa_batch, PR 27: counted, the cut ``jit_prefill`` (610 ms of a
    run 6,208 wide) made ``prefill_p50_ms.docqa`` read 946.0 where the two
    whole prefills read 1142.9.  Here: 1 ms of a prefill 160 wide."""
    t = _synthetic(str(tmp_path))
    assert [n for n, _, _ in t.devices[0].modules][-1] == "jit_prefill(12)"
    assert len(t.devices[0].modules) == 4 and len(tr.module_runs(t)) == 3
    run = {"trace": t}
    assert readers.prefill_p50_ms(run) == pytest.approx(5.0)   # not 3.0
    # a trace of one run has no whole run
    one = tr.Trace([tr.Device("d", t.devices[0].modules[:1],
                              t.devices[0].ops[:2])], [], 0.0, 0.006)
    assert tr.module_runs(one) == []


def test_made_trace_readers_ask_the_adapter_for_shapes(tmp_path):
    from benchmark import tiny
    run = {"trace": _synthetic(str(tmp_path)), "config": tiny.config(),
           "model": mistral, "records": [], "trace_window": (0.0, 1.0),
           "device": {"peaks": {"hbm_bytes_per_s": 819e9}},
           "counters": {"rows": 4, "n_pages": 80, "page_size": 16}}
    assert mistral.pool_leaf_shapes(run["config"], run["counters"]) == [
        [2, 80, 2, 16, 16], [80, 2, 16, 16]]
    assert readers.pool_copy_share(run) == pytest.approx(100 / 13)
    assert readers.attn_kernel_share(run) == pytest.approx(100 * 2 / 13)
    assert readers.decode_block_ms_p50(run) == pytest.approx(6.0)
    assert readers.prefill_p50_ms(run) == pytest.approx(5.0)
    assert readers.paged_decode_roofline(run) == 0.0    # the kernel is found


def test_cut_trace_keeps_what_the_reduction_reads(tmp_path):
    import cut_trace
    names = {1: "jit_decode_block(1)", 2: "%fusion.1 = bf16[4]{0} fusion()",
             3: "%copy-start.1 = bf16[4]{0} copy-start()"}
    device = xw.plane("/device:TPU:0", [
        xw.line("XLA Modules", [(1, 0, 3 * MS), (1, 5 * MS, 3 * MS)], 1),
        xw.line("XLA Ops", [(2, 0, 3 * MS), (2, 5 * MS, 3 * MS)], 2),
        xw.line("Async XLA Ops", [(3, 0, MS)], 3)], names)
    host = xw.plane("/host:CPU", [
        xw.line("python3", [(1, 1 * MS, MS), (1, 6 * MS, MS)], 7),
        xw.line("pjrt-tpu-tasks/330", [(2, 0, 9 * MS)], 8)],
        {1: "batcher.readback", 2: "H2D Dispatch"}, 1)
    misc = xw.plane("#Chip0 Misc", [xw.line("x", [(1, 0, MS)])], {1: "y"}, 2)
    path = os.path.join(str(tmp_path), "cut.xplane.pb.gz")
    with gzip.open(path, "wb") as f:
        f.write(cut_trace.cut(xw.space([device, host, misc]), 0.004, 1.0))
    from jax.profiler import ProfileData
    with gzip.open(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    assert {p.name: [ln.name for ln in p.lines] for p in data.planes} == {
        "/device:TPU:0": ["XLA Modules", "XLA Ops"], "/host:CPU": ["python3"]}
    t = tr.load(path)
    assert [(n, round(s, 6)) for n, s, _ in t.devices[0].modules] == [
        ("jit_decode_block(1)", 0.005)]
    assert [(n, round(s, 6)) for n, s, _ in t.host] == [
        ("batcher.readback", 0.006)]


def test_wire_roundtrip():
    msg = [(1, 0, 0), (2, 0, -1), (3, 2, b"abc"), (4, 1, b"12345678"),
           (5, 5, b"1234"), (6, 0, 2 ** 40)]
    back = xw.decode(xw.encode(msg))
    assert back[1] == (2, 0, 2 ** 64 - 1)       # int64 -1, ten bytes
    assert back[:1] + back[2:] == msg[:1] + msg[2:]


def test_readers_return_nothing_without_a_trace():
    from benchmark.traffic_gen import Schedule
    run = {"trace": None, "records": [], "t0": 0.0, "t1": 1.0,
           "config": {}, "model": mistral, "counters": {"rows": 1},
           "schedule": Schedule("open_loop", 0.0, 0.0, [])}
    for fn in (readers.prefill_p50_ms, readers.decode_block_ms_p50,
               readers.pool_copy_share, readers.attn_kernel_share,
               readers.paged_decode_roofline, readers.gen_late_p99_ms):
        assert fn(run) is None
