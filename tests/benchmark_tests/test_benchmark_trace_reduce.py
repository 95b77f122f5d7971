"""trace_reduce.py and the device readers on a recorded trace: three seconds
of the 16-layer Mistral-width batcher on one v5e chip under a backlog (my
exploratory chip run, PR 23), cut down to the lines the reduction reads."""

import os

import pytest

from benchmark import harness, readers, trace_reduce as tr

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
    assert trace.window_s == pytest.approx(2.996, abs=0.01)
    busy = tr.busy_s(trace)
    assert 0.9 * trace.window_s < busy < trace.window_s
    assert busy == pytest.approx(2.861, abs=0.01)


def test_breakdown_names_the_pool_copies(trace):
    b = tr.breakdown(trace)
    ops = dict(map(tuple, b["device_ops"]))
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    assert "custom-call closed_call.12 bf16[32,8,4,128]" in ops
    assert sum(1 for k in ops if k.startswith("copy ")
               and "bf16[16,1300,8,64,128]" in k) >= 4
    assert not any(k.startswith("while") for k in ops)
    gaps = dict(map(tuple, b["idle_gaps"]))
    assert max(gaps, key=gaps.get) == "np.asarray(jax.Array)"


def test_module_runs_tell_decode_from_prefill(trace):
    runs = tr.module_runs(trace, d_model=4096, rows=32)
    decode = [r for r in runs if r["kind"] == "decode"]
    prefill = [r for r in runs if r["kind"] == "prefill"]
    assert len(decode) == 36 and len(prefill) == 10
    assert sorted({r["width"] for r in prefill}) == [64, 256, 512, 1024, 2048]
    # (a run cut by the trace's edge, or at another table width, may differ)
    assert sum(0.054 < r["dur"] < 0.058 for r in decode) >= 30


def _run(trace):
    cfg = {"hidden_size": 4096, "num_attention_heads": 32,
           "num_key_value_heads": 8, "head_dim": 128,
           "num_hidden_layers": 16, "intermediate_size": 14336,
           "vocab_size": 32768}
    return {"trace": trace, "config": cfg, "records": [],
            "trace_window": (0.0, 3.0),
            "device": {"peaks": {"hbm_bytes_per_s": 819e9}},
            "counters": {"rows": 32, "n_pages": 1300, "page_size": 64}}


def test_device_readers(trace):
    run = _run(trace)
    assert readers.decode_block_ms_p50(run) == pytest.approx(55.9, abs=0.2)
    assert readers.prefill_p50_ms(run) == pytest.approx(66.2, abs=0.2)
    assert readers.pool_copy_share(run) == pytest.approx(52.8, abs=0.5)
    assert readers.attn_kernel_share(run) == pytest.approx(15.8, abs=0.5)
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
    kernel_s = 0.432865        # the paged kernel's time in this trace
    want = 100 * nbytes / 819e9 / kernel_s
    assert readers.paged_decode_roofline(run) == pytest.approx(want, rel=1e-3)


def test_readers_return_nothing_without_a_trace():
    from benchmark.traffic_gen import Schedule
    run = {"trace": None, "records": [], "t0": 0.0, "t1": 1.0,
           "config": {}, "counters": {"rows": 1},
           "schedule": Schedule("open_loop", 0.0, 0.0, [])}
    for fn in (readers.prefill_p50_ms, readers.decode_block_ms_p50,
               readers.pool_copy_share, readers.attn_kernel_share,
               readers.paged_decode_roofline, readers.gen_late_p99_ms):
        assert fn(run) is None
