#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the two paths users start from the shell — the ``tfrun`` trainer and
the ``tfserve`` fleet — through the scheduler and the local backend's own
chips, at the full width of the flagship the repo supports (vocab 8192,
d_model 512, 8 layers, 8 heads, d_ff 1408, bf16; weights random from a
seed), and checks what comes out.  Counts and times on earlier lines are
for the record, not metrics.  The last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and the exit code is 0 only if every phase passed.  With no chip on the
host it exits non-zero with a one-line reason and prints no result.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: replicas behind the
                                     # router, and a sharded trainer

One chip, in order (each phase's processes have exited before the next):

  kernels    a task that owns the chip builds the trainer's step and the
             batcher's decode step as the phases below do, lowers them for
             the device and counts their ``tpu_custom_call``s; the flash
             kernel must be in the train step and the paged-decode kernel
             in the decode step at a 600-token context
  train      ``tfrun -w 1 -s 0 -Gw 1 -- python examples/transformer_train.py
             --steps 20 --seq_len 2048 --batch_size 8`` through ``cli.main``;
             the losses at steps 10 and 20 are finite and do not rise
  serve      ``FleetServer(replicas=1, replica_chips=1, rows=8,
             max_len=1024)`` answers eight requests (prompts of 16..768
             tokens, two of them >= 600, 32 new tokens, one streamed) at the
             gateway; received == admitted == completed and the replica
             reported the platform it was launched for
  reference  a task that owns the chip runs the same prompts one at a time
             through ``transformer.generate`` and the greedy tokens are
             compared; a disagreement is accepted only where the two
             candidates' logits are within bf16 rounding of each other

This process is the scheduler and the gateway and never imports JAX: a
parent that has touched JAX holds the chip, and the task that needs it
would fail or hang.  Runs from a fresh checkout: ``tfmesos_tpu/native/*.so``
are git-ignored build outputs, and without them the pure-Python fallbacks
(``logpump.py``, ``TokenFileDataset``) are what runs.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import contextlib
import dataclasses
import json
import math
import os
import random
import re
import shlex
import signal
import sys
import tempfile
import time
import traceback
from typing import Any, Dict, List, Optional, Sequence

HERE = os.path.dirname(os.path.abspath(__file__))


class SmokeFailure(Exception):
    """A phase did not do what it must; the message is the one-line reason."""


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything the phases size themselves by — the chip run uses
    :data:`FULL`; ``tests/test_chip_smoke.py`` passes tiny ones."""

    tiny: bool
    steps: int = 20
    seq_len: int = 2048
    batch: int = 8
    rows: int = 8
    max_len: int = 1024
    page_size: int = 64
    prefill_bucket: int = 64
    vocab: int = 8192
    n_requests: int = 8
    prompt_len: Sequence[int] = (16, 768)
    #: at least two prompts this long, so the paged kernel's gate (a
    #: logical cache of 512 slots) opens; also the kernels phase's context
    long_prompt: int = 600
    new_tokens: int = 32
    phase_timeout: float = 420.0


FULL = Sizes(tiny=False)


def make_prompts(sizes: Sizes, seed: int) -> List[List[int]]:
    rng = random.Random(seed)
    lo, hi = sizes.prompt_len
    lens = [rng.randint(lo, hi) for _ in range(sizes.n_requests)]
    for i in range(2):      # the two shortest become the two long ones
        if sorted(lens)[-2] >= sizes.long_prompt:
            break
        lens[lens.index(min(lens))] = rng.randint(sizes.long_prompt, hi)
    return [[rng.randrange(sizes.vocab) for _ in range(n)] for n in lens]


# -- plumbing ---------------------------------------------------------------


@contextlib.contextmanager
def phase(name: str, timeout: float):
    """Run one phase: everything it and the processes it starts write to
    stdout/stderr is collected (children inherit the redirected
    descriptors) and replayed on stdout afterwards, prefixed; the phase is
    cut by an alarm at ``timeout``.  Yields a function returning the lines
    collected so far."""
    sys.stdout.flush()
    sys.stderr.flush()
    log = tempfile.TemporaryFile(mode="w+b")
    saved = os.dup(1), os.dup(2)
    os.dup2(log.fileno(), 1)
    os.dup2(log.fileno(), 2)

    def lines() -> List[str]:
        sys.stdout.flush()
        sys.stderr.flush()
        log.seek(0)
        return log.read().decode(errors="replace").splitlines()

    def on_alarm(signum, frame):
        raise SmokeFailure(f"{name}: no result after {timeout:.0f}s")

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(int(timeout))
    t0 = time.monotonic()
    try:
        yield lines
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)
        collected = lines()
        for fd, keep in zip((1, 2), saved):
            os.dup2(keep, fd)
            os.close(keep)
        log.close()
        for line in collected:
            # JAX's per-compile debug lines are on only to be counted
            # (compile_log); the counts are what goes on the record.
            if "DEBUG:" not in line:
                print(f"[{name}] {line}")
        print(f"[{name}] took {time.monotonic() - t0:.1f}s", flush=True)


def run_on_chips(name: str, chips: int, spec: Dict[str, Any],
                 timeout: float) -> Dict[str, Any]:
    """Run ``on_chip_<name>(spec)`` in a task of its own that owns
    ``chips`` chips (0: the CPU), launched through the scheduler and the
    local backend like any user's command; returns what it reported."""
    from tfmesos_tpu import Job, cluster

    with tempfile.TemporaryDirectory(prefix="chip_smoke-") as tmp:
        spec_path = os.path.join(tmp, "spec.json")
        out_path = os.path.join(tmp, "out.json")
        with open(spec_path, "w") as f:
            json.dump(spec, f)
        prog = f"import chip_smoke; chip_smoke.child_main({name!r})"
        cmd = " ".join(shlex.quote(a) for a in (
            sys.executable, "-c", prog, spec_path, out_path))
        with cluster(Job(name="worker", num=1, chips=chips, cmd=cmd),
                     quiet=True, start_timeout=timeout) as c:
            while not c.finished():
                time.sleep(0.1)
        if not os.path.exists(out_path):
            raise SmokeFailure(f"{name}: the task left no result")
        with open(out_path) as f:
            return json.load(f)


def child_main(name: str) -> None:
    """Entry of a chip-owning task (see :func:`run_on_chips`)."""
    spec_path, out_path = sys.argv[1:3]
    with open(spec_path) as f:
        spec = json.load(f)
    out = globals()[f"on_chip_{name}"](spec)
    with open(out_path + ".tmp", "w") as f:
        json.dump(out, f)
    os.replace(out_path + ".tmp", out_path)


def _model(spec: Dict[str, Any], max_len: int):
    """The model a replica serves (``fleet/replica.py:build_batcher``)."""
    from tfmesos_tpu.fleet import replica

    if spec["tiny"]:
        return replica.tiny_model(spec["seed"])
    return replica.flagship_model(spec["seed"], max_len=max_len)


# -- what runs in a task that owns the chip (JAX lives here) ----------------


def _join_runtime():
    """``runtime.initialize()`` (platform check, compile cache) and the
    device line; returns the task context and the first device."""
    import jax

    from tfmesos_tpu import runtime

    ctx = runtime.initialize()
    dev = jax.devices()[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind!r} "
          f"count={jax.device_count()}", flush=True)
    return ctx, dev


def on_chip_kernels(spec: Dict[str, Any]) -> Dict[str, Any]:
    """Build the train step and the decode step with the constructors and
    sizes the train and serve phases use, lower them for the device this
    task was given, and count the Pallas kernels in each."""
    import numpy as np
    import optax
    from jax.sharding import NamedSharding

    from tfmesos_tpu.models import transformer
    from tfmesos_tpu.parallel.sharding import batch_spec
    from tfmesos_tpu.serving import ContinuousBatcher, _PagedSide
    from tfmesos_tpu.train.trainer import make_train_step

    ctx, dev = _join_runtime()

    # The trainer's step, as examples/transformer_train.py builds it at
    # its defaults (the flagship config is the replica's, at seq_len).
    mesh = ctx.mesh(None)
    cfg, params = _model(spec, spec["seq_len"])
    opt = optax.adamw(3e-4, weight_decay=0.01)
    step = make_train_step(
        lambda p, b: transformer.loss_fn(cfg, p, b, mesh), opt, mesh=mesh,
        param_specs=transformer.partition_specs(cfg, mesh),
        batch_spec_tree=NamedSharding(mesh, batch_spec(mesh, extra_dims=1)))
    placed, opt_state = step.place(params, opt.init(params))
    tokens = np.zeros((spec["batch"], spec["seq_len"] + 1), np.int32)
    train = step.lower(placed, opt_state, {"tokens": tokens}).as_text()

    # The batcher's decode step at the table width a row holding
    # ``context`` tokens dispatches (fleet/replica.py:build_batcher).
    cfg, params = _model(spec, spec["max_len"])
    b = ContinuousBatcher(cfg, params, rows=spec["rows"],
                          max_len=spec["max_len"],
                          page_size=spec["page_size"],
                          prefill_bucket=spec["prefill_bucket"])
    width = _PagedSide.width_for(-(-spec["context"] // b.page_size),
                                 b.t_side.np_max)
    z = np.zeros((b.rows,), np.int32)
    table = np.full((b.rows, width), b.t_side.sink, np.int32)
    decode = b._decode.lower(b.params, b.pool, table, z, z, z, z).as_text()

    out = {"platform": dev.platform,
           "train_step": train.count("tpu_custom_call"),
           "decode_step": decode.count("tpu_custom_call"),
           "decode_width": width}
    print(f"tpu_custom_call count: train step (T={spec['seq_len']}, "
          f"B={spec['batch']}) {out['train_step']}; decode step "
          f"(context {spec['context']}, table width {width}) "
          f"{out['decode_step']}", flush=True)
    return out


def on_chip_reference(spec: Dict[str, Any]) -> Dict[str, Any]:
    """The repo's per-request path on the same prompts, one at a time;
    where its greedy tokens part from the fleet's, the logits of the two
    candidates at that step (teacher-forced on the agreed prefix)."""
    import jax.numpy as jnp
    import numpy as np

    from tfmesos_tpu.models import transformer

    _, dev = _join_runtime()
    cfg, params = _model(spec, spec["max_len"])
    results = []
    for prompt, fleet in zip(spec["prompts"], spec["fleet_tokens"]):
        prompt = np.asarray(prompt, np.int32)
        out = transformer.generate(cfg, params, jnp.asarray(prompt[None]),
                                   len(fleet))
        ref = [int(t) for t in np.asarray(out)[0, prompt.size:]]
        res: Dict[str, Any] = {"tokens": ref}
        if ref != fleet:
            i = next(j for j in range(len(fleet)) if ref[j] != fleet[j])
            ctx = np.concatenate([prompt, np.asarray(ref[:i], np.int32)])
            logits = np.asarray(transformer.forward(
                cfg, params, jnp.asarray(ctx[None]))[0, -1], np.float32)
            res.update(step=i, ref_token=ref[i], fleet_token=fleet[i],
                       ref_logit=float(logits[ref[i]]),
                       fleet_logit=float(logits[fleet[i]]),
                       top_logit=float(logits.max()))
        results.append(res)
    return {"platform": dev.platform, "results": results}


# -- phases (this process: scheduler, gateway, checks — no JAX) -------------


def phase_kernels(sizes: Sizes, chips: int, seed: int) -> Dict[str, Any]:
    spec = dict(dataclasses.asdict(sizes), seed=seed,
                context=sizes.long_prompt)
    with phase("kernels", sizes.phase_timeout):
        return run_on_chips("kernels", chips, spec, sizes.phase_timeout)


_LOSS = re.compile(r"^step (\d+): loss=(\S+) ")
_DEVICE = re.compile(r"^device: platform=(\S+) kind='([^']*)' count=(\d+)")


def phase_train(sizes: Sizes, chips: int, platform: str,
                mesh: Optional[str] = None,
                name: str = "train") -> Dict[str, Any]:
    """The trainer through ``tfrun``'s own ``main``; returns the device it
    printed and the losses by step."""
    from tfmesos_tpu import cli

    argv = ["-w", "1", "-s", "0", "-Gw", str(chips), "--",
            sys.executable, os.path.join(HERE, "examples",
                                         "transformer_train.py"),
            "--steps", str(sizes.steps), "--seq_len", str(sizes.seq_len),
            "--batch_size", str(sizes.batch)]
    if sizes.tiny:
        argv.append("--tiny")
    if mesh:
        argv += ["--mesh", mesh]
    with phase(name, sizes.phase_timeout) as lines:
        print("tfrun " + " ".join(argv), flush=True)
        rc = cli.main(argv)
        out = lines()
    if rc != 0:
        raise SmokeFailure(f"{name}: tfrun exited {rc}")
    device = next((m.groups() for m in map(_DEVICE.match, out) if m), None)
    if device is None:
        raise SmokeFailure(f"{name}: the trainer printed no device line")
    if device[0] != platform:
        raise SmokeFailure(f"{name}: the trainer ran on {device[0]!r}, "
                           f"not {platform!r}")
    losses = {int(m.group(1)): float(m.group(2))
              for m in map(_LOSS.match, out) if m}
    half, last = sizes.steps // 2, sizes.steps
    if half not in losses or last not in losses:
        raise SmokeFailure(f"{name}: no loss lines for steps {half} and "
                           f"{last} (got {sorted(losses)})")
    if not all(math.isfinite(losses[s]) for s in (half, last)):
        raise SmokeFailure(f"{name}: non-finite loss {losses}")
    if losses[last] > losses[half]:
        raise SmokeFailure(f"{name}: loss rose from {losses[half]} at step "
                           f"{half} to {losses[last]} at step {last}")
    print(f"{name}: device {device}, loss {losses[half]} at step {half}, "
          f"{losses[last]} at step {last}", flush=True)
    return {"device": {"platform": device[0], "kind": device[1],
                       "count": int(device[2])},
            "losses": losses, "compiles": compile_log(out)}


def compile_log(lines: Sequence[str]) -> Dict[str, List[str]]:
    """Which jitted programs a phase's processes found in the persistent
    compile cache and which they compiled afresh, by JAX's own log lines
    (``JAX_DEBUG_LOG_MODULES=jax._src.compiler``, set in :func:`main`)."""
    hit = re.compile(r"Persistent compilation cache hit for '([^']+)'")
    miss = re.compile(r"PERSISTENT COMPILATION CACHE MISS for '([^']+)'")
    out: Dict[str, List[str]] = {"hits": [], "misses": []}
    for line in lines:
        for key, pat in (("hits", hit), ("misses", miss)):
            m = pat.search(line)
            if m:
                out[key].append(m.group(1))
    return out


def phase_serve(sizes: Sizes, replicas: int, replica_chips: int,
                platform: str, seed: int, prompts: List[List[int]],
                name: str = "serve") -> Dict[str, Any]:
    """What ``tfserve --replicas N -Gr k`` builds, asked for completions
    at the gateway; returns each request's tokens and who served it."""
    from tfmesos_tpu.fleet import FleetServer

    with phase(name, sizes.phase_timeout) as lines:
        with FleetServer(replicas=replicas, replica_chips=replica_chips,
                         tiny=sizes.tiny, rows=sizes.rows, seed=seed,
                         max_len=sizes.max_len, page_size=sizes.page_size,
                         prefill_bucket=sizes.prefill_bucket,
                         request_timeout=sizes.phase_timeout,
                         start_timeout=sizes.phase_timeout) as fleet:
            client = fleet.client(timeout=sizes.phase_timeout)
            streamed: List[int] = []

            def ask(i: int) -> Dict[str, Any]:
                out = client.generate(
                    prompts[i], sizes.new_tokens, trace=True,
                    on_tokens=streamed.extend if i == 0 else None)
                spans = client.trace(trace_id=out["trace_id"])[0]["spans"]
                out["addr"] = next(
                    s["addr"] for s in spans
                    if s["component"] == "router" and s["name"] == "attempt"
                    and s.get("outcome") == "ok")
                return out

            with concurrent.futures.ThreadPoolExecutor(
                    max_workers=min(8, len(prompts))) as pool:
                outs = list(pool.map(ask, range(len(prompts))))
            time.sleep(3 * fleet.heartbeat_interval)
            snap = client.metrics()
            table = {r["addr"]: r for r in fleet.registry.snapshot()}
            client.close()
        log = lines()

    counters = snap["counters"]
    counts = [counters.get(k, 0) for k in ("received", "admitted",
                                           "completed")]
    print(f"{name}: received == admitted == completed: {counts} of "
          f"{len(prompts)} requests", flush=True)
    if counts != [len(prompts)] * 3:
        raise SmokeFailure(f"{name}: received/admitted/completed {counts} "
                           f"for {len(prompts)} requests")
    devices = snap["gauges"]["devices"]
    print(f"{name}: replicas reported {json.dumps(devices, sort_keys=True)}",
          flush=True)
    if len(devices) != replicas:
        raise SmokeFailure(f"{name}: {len(devices)} of {replicas} replicas "
                           f"reported a device")
    wrong = {n: d for n, d in devices.items() if d["platform"] != platform}
    if wrong:
        raise SmokeFailure(f"{name}: replicas not on {platform!r}: {wrong}")
    if streamed != outs[0]["tokens"]:
        raise SmokeFailure(f"{name}: streamed chunks {streamed} are not the "
                           f"completion {outs[0]['tokens']}")
    served = []
    for i, out in enumerate(outs):
        if len(out["tokens"]) != sizes.new_tokens:
            raise SmokeFailure(f"{name}: request {i} returned "
                               f"{len(out['tokens'])} tokens")
        rep = table[out["addr"]]
        served.append(rep["node"])
        print(f"{name}: request {i} prompt {len(prompts[i])} tokens, "
              f"ttft_ms={out['ttft_ms']} total_ms={out['total_ms']}"
              f"{' streamed' if i == 0 else ''} by {rep['node']} on host "
              f"chip {rep['device']['chips'] or 'none'}", flush=True)
    return {"tokens": [o["tokens"] for o in outs], "served": served,
            "devices": devices, "compiles": compile_log(log)}


def phase_reference(sizes: Sizes, chips: int, platform: str, seed: int,
                    prompts: List[List[int]],
                    fleet_tokens: List[List[int]]) -> float:
    """Greedy tokens of the fleet against ``transformer.generate``; returns
    the share of requests that agree token for token."""
    spec = dict(dataclasses.asdict(sizes), seed=seed, prompts=prompts,
                fleet_tokens=fleet_tokens)
    with phase("reference", sizes.phase_timeout):
        out = run_on_chips("reference", chips, spec, sizes.phase_timeout)
    if out["platform"] != platform:
        raise SmokeFailure(f"reference: ran on {out['platform']!r}")
    same = 0
    for i, res in enumerate(out["results"]):
        if res["tokens"] == fleet_tokens[i]:
            same += 1
            continue
        # One bf16 spacing at the logits' magnitude: candidates closer
        # than that are a tie the two paths' rounding may break either way.
        a, b = res["ref_logit"], res["fleet_logit"]
        spacing = 2.0 ** (math.floor(math.log2(max(abs(a), abs(b),
                                                   1e-30))) - 7)
        print(f"reference: request {i} parts at new token {res['step']}: "
              f"generate {res['ref_token']} (logit {a:.6f}) vs fleet "
              f"{res['fleet_token']} (logit {b:.6f}), gap {abs(a - b):.6f}, "
              f"bf16 spacing {spacing:.6f}, top logit "
              f"{res['top_logit']:.6f}", flush=True)
        if abs(a - b) > spacing:
            raise SmokeFailure(
                f"reference: request {i} diverges at new token "
                f"{res['step']} with a logit gap of {abs(a - b):.6f}, more "
                f"than bf16 rounding ({spacing:.6f}) explains")
    share = same / len(fleet_tokens)
    print(f"reference: {same} of {len(fleet_tokens)} requests agree token "
          f"for token with transformer.generate (share {share:.3f}); "
          f"{len(fleet_tokens) - same} part at a bf16 tie", flush=True)
    return share


def report_compiles(name: str, compiles: Dict[str, List[str]],
                    watch: Sequence[str]) -> None:
    """Earlier-line record of the compile cache: totals, and whether the
    named step programs were compiled afresh."""
    fresh = sorted({m for m in compiles["misses"] if m in watch})
    print(f"{name}: compile cache {len(compiles['hits'])} hits, "
          f"{len(compiles['misses'])} misses; fresh compiles of "
          f"{list(watch)}: {fresh or 'none'}", flush=True)


# -- the two runs -----------------------------------------------------------


def smoke_one_chip(sizes: Sizes, seed: int, chips: int = 1,
                   platform: str = "tpu") -> Dict[str, Any]:
    kernels = phase_kernels(sizes, chips, seed)
    if platform == "tpu" and not (kernels["train_step"]
                                  and kernels["decode_step"]):
        raise SmokeFailure(
            f"kernels: the flash kernel is missing from the train step or "
            f"the paged-decode kernel from the decode step: {kernels}")
    train = phase_train(sizes, chips, platform)
    report_compiles("train", train["compiles"], ["jit_sharded_step"])
    prompts = make_prompts(sizes, seed)
    serve = phase_serve(sizes, 1, chips, platform, seed, prompts)
    report_compiles("serve", serve["compiles"], ["jit_fn"])
    phase_reference(sizes, chips, platform, seed, prompts, serve["tokens"])
    return train["device"]


def smoke_four_chips(sizes: Sizes, seed: int, replicas: int = 4,
                     replica_chips: int = 1, trainer_chips: int = 4,
                     platform: str = "tpu") -> Dict[str, Any]:
    """One-chip replicas behind the router, then one trainer sharded over
    every chip against the same steps on one chip."""
    prompts = make_prompts(sizes, seed) * replicas
    serve = phase_serve(sizes, replicas, replica_chips, platform, seed,
                        prompts, name="serve4")
    # The JAX device id is 0 inside every one-chip task; the host chip the
    # backend gave each replica is what must differ.
    ids = sorted(d["chips"] for d in serve["devices"].values())
    if replica_chips and len(set(ids)) != replicas:
        raise SmokeFailure(f"serve4: replicas share chips: {ids}")
    idle = set(serve["devices"]) - set(serve["served"])
    if idle:
        raise SmokeFailure(f"serve4: replicas {sorted(idle)} served nothing")
    for i in range(sizes.n_requests):
        copies = {tuple(serve["tokens"][i + k * sizes.n_requests])
                  for k in range(replicas)}
        if len(copies) != 1:
            raise SmokeFailure(f"serve4: prompt {i} got {len(copies)} "
                               f"different completions across replicas")
    print(f"serve4: {len(prompts)} of {len(prompts)} completed on host "
          f"chips {ids}, every replica served, tokens independent of the "
          f"replica", flush=True)

    sharded = phase_train(sizes, trainer_chips, platform,
                          mesh="fsdp=2,tp=2", name="train4")
    single = phase_train(sizes, replica_chips, platform, name="train1")
    for step, loss in sorted(sharded["losses"].items()):
        ref = single["losses"][step]
        # bf16 params and activations: two reduction orders agree to a
        # few roundings of a loss this size, not to the last digit.
        if abs(loss - ref) > 2.0 ** -6 * abs(ref):
            raise SmokeFailure(f"train4: loss {loss} at step {step} on "
                               f"fsdp=2,tp=2 vs {ref} on one device")
    print(f"train4: fsdp=2,tp=2 losses {sharded['losses']} match the "
          f"one-device losses {single['losses']}", flush=True)
    return sharded["device"]


def main(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="1: kernels, train, serve, reference on one chip; "
                        "4: four replicas behind the router and a sharded "
                        "trainer, and no other phase")
    p.add_argument("--seed", type=int, default=0,
                   help="seed of the weights and the prompts")
    args = p.parse_args(argv)
    try:
        from tfmesos_tpu.backends.local import host_chip_nodes
    except ImportError as e:
        print(f"chip_smoke: not in a checkout of the repo: {e}",
              file=sys.stderr)
        return 2
    nodes = host_chip_nodes()
    if len(nodes) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); this host "
              f"exposes {len(nodes)} ({', '.join(nodes) or 'no device nodes'})",
              file=sys.stderr)
        return 2
    # Every process started below logs its persistent-cache hits and
    # misses by program name (read back by compile_log).
    os.environ["JAX_DEBUG_LOG_MODULES"] = "jax._src.compiler"
    try:
        run = smoke_one_chip if args.chips == 1 else smoke_four_chips
        device = run(FULL, args.seed)
    except Exception as e:  # noqa: BLE001 - the script's one boundary
        if not isinstance(e, SmokeFailure):
            traceback.print_exc()
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 1
    if device["platform"] != "tpu" or device["count"] != args.chips:
        print(f"chip_smoke: FAILED: ran on {device}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
