"""The serving driver: one ``ContinuousBatcher`` in this process, fed through
``submit()``/``serve()``, every token timestamped at ``Request.on_tokens``.

One process holds the chip.  Order of a run:

  set-up   JAX up, the chip looked for, the weights made on the device from
           ``--seed`` (the adapter's ``make_weights``), the batcher built
           from the adapter's ``program_config``, every shape this
           cell's traffic uses warmed (its padded prompt widths through
           ``run()``, the decode widths through ``warmup()``), then the
           unmeasured ramp: the generator (or the backlog) runs until the
           rows have turned over.  All of it is ``setup_s``.
  window   ``--seconds`` seconds; tokens are counted by timestamp.
  grace    a short fixed time for the first tokens of requests that were
           due late in the window, then the loop is abandoned: nothing
           waits for the backlog to drain.
  check    the batcher is freed, and the plain reference reads a seeded
           sample of the requests the run finished (``correct``).

The batcher's scheduling options are NOT the benchmark's: they are the
defaults of ``fleet/replica.py``'s own argument parser, read at run time, so
that a PR which changes what ``tfserve`` does by default is measured
without touching the benchmark.  Only ``rows``, ``max_len``, ``page_size``
and ``n_pages`` come from the configuration file.

What belongs to one model family (how the program is configured, the
weights, the plain reference, the shapes the readers look for) is the
configuration's adapter, ``models/<model>.py``, found by
``harness.load_model``; the readers get it as ``run["model"]``.
"""

from __future__ import annotations

import gc
import json
import os
import shutil
import threading
import time
from typing import Any, Callable, Dict, List

import numpy as np

from benchmark import harness, traffic_gen, window
from benchmark.window import Served

#: seconds of the window that a ``--trace 1`` run records with the profiler
TRACE_S = 4.0


class _AdmitTap:
    """Stands where the fleet replica attaches a request's trace: the
    batcher reports its per-request events here."""

    def __init__(self, rec: Served):
        self.rec = rec

    def event(self, component, name, **attrs):
        if name == "admit" and self.rec.admit is None:
            self.rec.admit = time.perf_counter()

    def span_between(self, *args, **kwargs):
        pass


def batcher_options() -> Dict[str, Any]:
    """What ``python -m tfmesos_tpu.fleet.replica`` would build with no
    scheduling flag given, mapped as ``build_batcher`` maps it."""
    from tfmesos_tpu.fleet.replica import build_parser
    a = build_parser().parse_args([])
    fused = bool(getattr(a, "fused_prefill", False))
    return {
        "prefill_bucket": a.prefill_bucket,
        "multi_step": a.multi_step,
        "prefix_cache_pages": a.prefix_cache_pages,
        "pipeline_depth": a.pipeline_depth,
        "prefill_chunk": a.prefill_bucket if fused else None,
        "fused_prefill": fused,
        "tokens_per_tick": getattr(a, "tokens_per_tick", None),
    }


def check_served(model, weights, config, records: List[Served], seed: int,
                 limits: Dict[str, float], n_sample: int,
                 control: bool = False, out: Callable = print
                 ) -> Dict[str, Any]:
    """``correct`` for a served model: on a sample of the finished
    requests, drawn from the seed and with the longest in it, the plain
    reference (the adapter's ``served_gaps``) reads every served token
    (teacher-forced) and reports how far its logit lies below the
    reference's best.  Greedy serving only.  The numbers compared come
    back under ``compared``, each beside its limit (``run.py`` prints
    them); a reading without a limit is printed here."""
    limits = {"length_mismatches": 0, "token_ids_out_of_range": 0, **limits}
    done = [r for r in records if r.done is not None and r.tokens]
    vocab = config["vocab_size"]
    readings: Dict[str, Any] = {
        "finished": len(done),
        "length_mismatches": sum(1 for r in done
                                 if len(r.tokens) != r.max_new_tokens),
        "token_ids_out_of_range": sum(1 for r in done if min(r.tokens) < 0
                                      or max(r.tokens) >= vocab)}
    if done:
        rng = np.random.default_rng([int(seed), 0xc0de])
        longest = max(done, key=lambda r: r.prompt_len + len(r.tokens))
        rest = [r for r in done if r is not longest]
        pick = [longest] + [rest[i] for i in rng.permutation(len(rest))
                            [:max(0, n_sample - 1)]]
        got = [model.served_gaps(weights, config, r.prompt, r.tokens,
                                  control=control) for r in pick]
        gap = np.concatenate([g["gap"] for g in got])
        readings.update(
            sampled_requests=len(pick), served_tokens=int(gap.size),
            longest_context=int(longest.prompt_len + len(longest.tokens)),
            max_gap=float(gap.max()), mean_gap=float(gap.mean()),
            off_best_share=float(np.mean(gap > 0)))
        if control:
            cg = np.concatenate([g["control_gap"] for g in got])
            readings.update(control_max_gap=float(cg.max()),
                            control_mean_gap=float(cg.mean()),
                            control_off_best_share=float(np.mean(cg > 0)))
    compared = {k: {"value": readings[k], "limit": lim}
                for k, lim in limits.items() if k in readings}
    ok = bool(done) and len(compared) == len(limits) and all(
        c["value"] <= c["limit"] for c in compared.values())
    for key in ("max_gap", "mean_gap", "off_best_share", "control_max_gap",
                "control_mean_gap", "control_off_best_share"):
        if key in readings and key not in limits:
            out(f"correct: {key} = {readings[key]:.6g} (no limit)")
    readings["correct"] = bool(ok)
    readings["compared"] = compared
    return readings


def _dump_records(cell: str, seed: int, trace: bool, records: List[Served],
                  t0: float, t1: float, e2e: Dict[str, float]) -> None:
    """What the client side saw, for a reader after the run (inside the
    checkout, git-ignored): one line per request, times relative to t0."""
    path = os.path.join(harness.ROOT, "benchmark_out", "runs")
    os.makedirs(path, exist_ok=True)

    def rel(t):
        return None if t is None else round(t - t0, 6)

    with open(os.path.join(path, f"{cell}.{seed}.{int(trace)}.json"),
              "w") as f:
        json.dump({"window_s": t1 - t0, "e2e": e2e, "requests": [
            {"i": r.index, "prompt": r.prompt_len, "new": r.max_new_tokens,
             "due": rel(r.due), "submit": rel(r.submit),
             "admit": rel(r.admit), "first": rel(r.first),
             "last": rel(r.token_times[-1] if r.token_times else None),
             "n": len(r.token_times), "done": rel(r.done)}
            for r in records if r.submit is not None]}, f)


def run_cell(spec: Dict[str, Any], cell: Dict[str, Any],
             config: Dict[str, Any], traffic: Dict[str, Any], *, seed: int,
             seconds: float, trace: bool, t_start: float,
             require_chip: bool = True, control: bool = False,
             program_int8: bool = False, schedule=None,
             out: Callable = print) -> Dict[str, Any]:
    """One run of one cell.  Returns the result line's fields (and, for
    the control runs and the tests, the ``check`` readings).

    ``control`` also reads the reference with int8 weights on the sampled
    requests.  ``program_int8`` serves from the program's own weight-only
    int8 path (the adapter's ``int8_program_weights``) while the reference
    keeps the weights as made: the control that has to come out not correct.
    ``schedule`` is for ``sweep.py`` (the cell's schedule at another
    rate).  A benchmark run sets none of the three."""
    import jax
    model = harness.load_model(config)      # fails before the chip is sought
    harness.enable_compile_cache()
    device = harness.accelerator(int(cell["chips"]), require_chip)
    from tfmesos_tpu.serving import ContinuousBatcher, Request

    dep = config["deployment"]
    cfg = model.program_config(config, int(dep["max_len"]))
    w = model.make_weights(config, seed, dtype=cfg.dtype)
    jax.block_until_ready(w)
    served_w = w
    if program_int8:
        # int8 weights, the bf16 ones and the pool do not fit together:
        # the reference's weights are made again after the batcher is freed
        served_w = jax.block_until_ready(model.int8_program_weights(cfg, w))
        del w
    opts = batcher_options()
    batcher = ContinuousBatcher(
        cfg, served_w, rows=int(dep["rows"]), max_len=int(dep["max_len"]),
        page_size=int(dep["page_size"]), n_pages=int(dep["n_pages"]), **opts)
    out(f"setup: device {device['kind']} x{device['count']}; batcher "
        f"options from fleet/replica.py: {opts}")

    sched = schedule or traffic_gen.make_schedule(
        traffic, seed, seconds, config["vocab_size"])
    bucket = batcher.prefill_bucket
    widths = sorted({-(-len(p.prompt) // bucket) * bucket
                     for p in sched.requests})
    t_w = time.perf_counter()
    batcher.warmup(decode=True, prefill=False)
    rng = np.random.default_rng([int(seed), 0x3a7])
    for _ in batcher.run(
            Request(prompt=rng.integers(0, config["vocab_size"], wd,
                                        dtype=np.int32), max_new_tokens=1)
            for wd in widths):
        pass
    out(f"setup: warmed {len(widths)} prompt widths {widths[0]}..{widths[-1]}"
        f" and the decode widths in {time.perf_counter() - t_w:.1f} s")

    records: List[Served] = []
    requests = []
    by_req: Dict[int, Served] = {}
    for p in sched.requests:
        rec = Served(index=p.index, prompt_len=int(len(p.prompt)),
                     max_new_tokens=p.max_new_tokens, due=p.due_s,
                     prompt=p.prompt)
        req = Request(prompt=p.prompt, max_new_tokens=p.max_new_tokens)

        def on_tokens(toks, off, rec=rec):
            now = time.perf_counter()
            rec.token_times.extend([now] * len(toks))
            rec.tokens.extend(toks)

        req.on_tokens = on_tokens
        req.trace = _AdmitTap(rec)
        records.append(rec)
        requests.append(req)
        by_req[id(req)] = rec

    stop = threading.Event()
    errors: List[BaseException] = []

    def consume():
        try:
            it = batcher.serve()
            for c in it:
                now = time.perf_counter()
                rec = by_req.get(id(getattr(c, "request", None)))
                toks = getattr(c, "tokens", None)
                if rec is not None and toks is not None:
                    missing = len(toks) - len(rec.tokens)
                    if missing > 0:     # the tail that never streamed
                        rec.token_times.extend([now] * missing)
                        rec.tokens.extend(int(t) for t in toks[-missing:])
                    rec.done = now
                if stop.is_set():
                    it.close()
                    break
        except BaseException as e:      # reported by the main thread
            errors.append(e)

    def generate(t_last: float):
        for rec, req in zip(records, requests):
            if rec.due >= t_last:
                break
            delay = rec.due - time.perf_counter()
            if delay > 0 and stop.wait(delay):
                break
            rec.submit = time.perf_counter()
            batcher.submit(req)

    gc.collect()
    gc.freeze()
    t_gen0 = time.perf_counter()
    t0 = t_gen0 + sched.ramp_s
    t1 = t0 + float(seconds)
    t_end = t1 + sched.grace_s
    for rec in records:
        rec.due += t_gen0
    threads = [threading.Thread(target=consume, name="serve", daemon=True)]
    if sched.kind == "backlog":
        for rec, req in zip(records, requests):
            rec.submit = time.perf_counter()
            batcher.submit(req)
    else:
        threads.append(threading.Thread(target=generate, args=(t1,),
                                        name="generator", daemon=True))
    for th in threads:
        th.start()

    def sleep_until(t):
        while not errors:
            left = t - time.perf_counter()
            if left <= 0:
                return
            time.sleep(min(left, 0.25))

    trace_dir = tw0 = tw1 = None
    sleep_until(t0)
    if trace:
        trace_dir = os.path.join(harness.ROOT, "benchmark_out", "trace",
                                 f"{cell['name']}.{seed}")
        popts = jax.profiler.ProfileOptions()
        popts.python_tracer_level = 0
        popts.host_tracer_level = 2
        sleep_until(t0 + 1.0)
        tw0 = time.perf_counter()
        jax.profiler.start_trace(trace_dir, profiler_options=popts)
        sleep_until(tw0 + TRACE_S)
        tw1 = time.perf_counter()
        jax.profiler.stop_trace()
    sleep_until(t_end)
    stop.set()
    batcher.close()
    threads[0].join(120.0)
    for th in threads[1:]:
        th.join(10.0)
    if errors:
        raise errors[0]
    if any(th.is_alive() for th in threads):
        raise RuntimeError("the serve loop did not stop")
    memory_peak = harness.memory_peak_bytes()

    # -- the window's arithmetic ------------------------------------------
    e2e: Dict[str, float] = {"setup_s": t0 - t_start}
    tt = window.ttft_samples(records, t0, t1, sched.grace_s)
    tp = window.tpot_samples(records, t0, t1)
    n_tok = window.tokens_in_window(records, t0, t1)
    touched = [r for r in records if r.token_times
               and r.token_times[0] < t1 and r.token_times[-1] >= t0]
    if sched.kind == "open_loop":
        attempted, failed = len(tt["values"]), int(tt["failed"])
        if tt["values"]:
            e2e["ttft_p90_ms"] = 1e3 * window.percentile(tt["values"], 90)
        if tp:
            e2e["tpot_p90_ms"] = 1e3 * window.percentile(tp, 90)
    else:
        attempted, failed = len(touched), 0
    e2e["tok_s"] = n_tok / float(seconds)
    out(f"samples: due_requests={len(tt['values'])} tpot_requests={len(tp)} "
        f"(requests with {window.TPOT_MIN_TOKENS} or more tokens in the "
        f"window) tokens_in_window={n_tok:.0f} requests_in_window={len(touched)}"
        f" finished={sum(1 for r in records if r.done is not None)}")
    if tt["values"]:        # for a reader; no end-to-end metric of a cell yet
        out("samples: ttft_ms p50=%.1f p90=%.1f (from the due time)" % tuple(
            1e3 * window.percentile(tt["values"], q) for q in (50, 90)))

    _dump_records(cell["name"], seed, trace, records, t0, t1, e2e)
    run: Dict[str, Any] = {
        "records": records, "t0": t0, "t1": t1, "seconds": float(seconds),
        "schedule": sched, "config": config, "model": model,
        "device": device, "trace": None, "trace_window": (tw0, tw1),
        "e2e": e2e,
        "counters": {"rows": int(dep["rows"]),
                     "n_pages": int(dep["n_pages"]),
                     "page_size": int(dep["page_size"])},
    }
    device_line = {"platform": device["platform"], "kind": device["kind"],
                   "count": device["count"],
                   "memory_peak_bytes": memory_peak}
    breakdown = None
    if trace:
        from benchmark import trace_reduce
        tr = trace_reduce.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)    # reduced: done with
        run["trace"] = tr
        device_line["busy_s"] = trace_reduce.busy_s(tr)
        device_line["window_s"] = tr.window_s
        breakdown = trace_reduce.breakdown(tr)

    # -- correct: the reference reads what the timed path served ----------
    del batcher, served_w
    gc.unfreeze()
    gc.collect()
    if program_int8:
        w = model.make_weights(config, seed, dtype=cfg.dtype)
    in_run = [r for r in records
              if r.done is not None and r.done >= t0 and r.done <= t_end]
    chk = config["correct"]
    t_chk = time.perf_counter()
    check = check_served(model, w, config, in_run, seed, chk["limits"],
                         int(chk["sample_requests"]), control=control,
                         out=out)
    failed += check["length_mismatches"] + check["token_ids_out_of_range"]
    out(f"correct: the reference read {check.get('served_tokens', 0)} served "
        f"tokens of {check.get('sampled_requests', 0)} requests in "
        f"{time.perf_counter() - t_chk:.1f} s (after the window, not set-up)")

    if trace:
        metrics = harness.per_layer(spec, cell["name"], run)
    else:
        # a metric split by cell (``<base>.<cell>``) is its base quantity
        metrics = {m["name"]: {"value": e2e[m["name"].split(".")[0]],
                               "unit": m["unit"]}
                   for m in harness.cell_metrics(spec, cell["name"],
                                                 "end_to_end")
                   if m["name"].split(".")[0] in e2e}
    return {"correct": check["correct"], "attempted": attempted,
            "failed": failed, "metrics": metrics, "device": device_line,
            "breakdown": breakdown, "check": check, "e2e": e2e,
            "records": records, "t0": t0, "t1": t1}
