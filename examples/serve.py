"""Offline batch serving on the flagship transformer.

The reference stops at training jobs; this is the inference-side workload
shape: read prompts (JSONL, one ``{"tokens": [...]}`` per line), serve
them in ragged mixed-length batches (right-padded per batch, per-row
positions — docs/SERVING.md), and write continuations back as JSONL.
Runs standalone or as a Mode-B task under the scheduler:

    tfrun -w 1 -s 0 -- python examples/serve.py --tiny --out /tmp/out.jsonl

Without ``--input``, a seeded synthetic workload (mixed prompt lengths)
stands in — this container has no egress, and untrained weights produce
token soup anyway; the point is the serving mechanics and throughput.
"""

import argparse
import json
import sys
import time

SPEC_N_DRAFT = 4    # draft tokens per speculative round (--speculative)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--input", type=str, default=None,
                   help="JSONL of {\"tokens\": [...]} prompts; synthetic "
                        "when absent")
    p.add_argument("--out", type=str, default=None,
                   help="output JSONL path (default stdout)")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--n-prompts", type=int, default=24, dest="n_prompts",
                   help="synthetic workload size (ignored with --input)")
    p.add_argument("--new-tokens", type=int, default=32, dest="new_tokens")
    p.add_argument("--stop-token", type=int, default=None, dest="stop_token")
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--int8", action="store_true")
    p.add_argument("--int8-kv", action="store_true", dest="int8_kv")
    p.add_argument("--int8-draft-kv", action="store_true",
                   dest="int8_draft_kv",
                   help="store the speculative draft's page pool int8 "
                        "(with --continuous --speculative)")
    p.add_argument("--paged", action="store_true",
                   help="serve from a paged KV cache: one shared page "
                        "pool, per-batch page allocation/recycling "
                        "(docs/SERVING.md)")
    p.add_argument("--continuous", action="store_true",
                   help="continuous batching: admit prompts into a "
                        "RUNNING paged decode as rows free up "
                        "(serving.ContinuousBatcher; --batch sets the "
                        "concurrent-row count)")
    p.add_argument("--speculative", action="store_true",
                   help="speculative continuous batching (with "
                        f"--continuous): a half-size draft proposes "
                        f"{SPEC_N_DRAFT} tokens per tick, the target "
                        "verifies them in one ragged chunk — greedy "
                        "outputs identical to target-only serving; "
                        "sampling is rejection-corrected to the "
                        "target's exact distribution")
    p.add_argument("--prefix-cache", type=int, default=0,
                   metavar="PAGES", dest="prefix_cache",
                   help="cross-request prefix cache budget in pool pages "
                        "per shard (with --continuous; 0 disables): "
                        "prompts sharing page-aligned leading chunks "
                        "prefill only their uncached tails")
    p.add_argument("--prefill-chunk", type=int, default=None,
                   dest="prefill_chunk",
                   help="chunked prefill (with --continuous): write "
                        "prompts in chunks of this many tokens, "
                        "interleaved with decode steps — bounds the "
                        "stall a long prompt imposes on decoding rows")
    p.add_argument("--multi-step", type=int, default=1, dest="multi_step",
                   help="decode K tokens per dispatch in continuous mode "
                        "(one host sync per [rows, K] block; stops act "
                        "at block granularity, token streams identical)")
    p.add_argument("--pipeline-depth", type=int, default=0,
                   choices=(0, 1), dest="pipeline_depth",
                   help="pipelined device-resident decode (with "
                        "--continuous): 1 feeds each block from the "
                        "previous block's on-device tokens/positions/"
                        "steps and syncs one block behind — token "
                        "streams identical to 0 (the synchronous "
                        "default)")
    p.add_argument("--warmup", action="store_true",
                   help="compile every jitted serving entry point "
                        "before the stream starts (with --continuous; "
                        "ContinuousBatcher.warmup) — first-request "
                        "latency no longer pays the compiles")
    p.add_argument("--mesh", type=str, default=None,
                   help="multi-chip continuous serving (with "
                        "--continuous): comma-separated mesh axes, e.g. "
                        "dp=2,tp=2 — pool pages shard over dp, heads "
                        "over tp; --batch rows must divide over dp")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--tiny", action="store_true")
    args = p.parse_args()
    if args.mesh is not None and not args.continuous:
        p.error("--mesh is a continuous-batching feature; add --continuous")
    if args.int8_draft_kv and not args.speculative:
        p.error("--int8-draft-kv needs --continuous --speculative")
    if args.multi_step != 1 and not args.continuous:
        p.error("--multi-step is a continuous-batching feature; "
                "add --continuous")
    if args.pipeline_depth and not args.continuous:
        p.error("--pipeline-depth is a continuous-batching feature; "
                "add --continuous")
    # --multi-step composes with --speculative (R fused speculative
    # rounds per dispatch); --pipeline-depth with --speculative serves
    # synchronously and records pipeline_bypass_reason — see
    # serving.BYPASS_ALLOWLIST.
    if args.warmup and not args.continuous:
        p.error("--warmup is a continuous-batching feature; "
                "add --continuous")
    if args.paged and args.continuous:
        p.error("--paged and --continuous are distinct serving modes: "
                "--continuous already serves from a paged pool (pick one)")
    if args.prefill_chunk is not None and not args.continuous:
        p.error("--prefill-chunk is a continuous-batching feature; "
                "add --continuous")
    if args.speculative:
        if not args.continuous:
            p.error("--speculative here is a continuous-batching "
                    "feature; add --continuous (offline speculative "
                    "serving lives in examples/generate.py)")

    import jax
    import jax.numpy as jnp
    import numpy as np
    from tfmesos_tpu import runtime
    from tfmesos_tpu.models import transformer

    runtime.initialize()
    if args.tiny:
        cfg = transformer.TransformerConfig(
            vocab_size=256, d_model=64, n_layers=2, n_heads=4, d_ff=128,
            max_seq_len=256, dtype=jnp.float32)
    else:
        cfg = transformer.TransformerConfig(
            vocab_size=8192, d_model=512, n_layers=8, n_heads=8, d_ff=1408,
            max_seq_len=4096, dtype=jnp.bfloat16)
    params = transformer.init_params(cfg, jax.random.PRNGKey(args.seed))
    if args.int8:
        params = jax.jit(
            lambda p_: transformer.quantize_params(cfg, p_))(params)

    if args.input:
        with open(args.input) as f:
            prompts = [json.loads(line)["tokens"] for line in f if line.strip()]
    else:
        rng = np.random.RandomState(args.seed)
        prompts = [rng.randint(0, cfg.vocab_size,
                               size=rng.randint(4, 33)).tolist()
                   for _ in range(args.n_prompts)]
    if not prompts:
        print("serve: empty workload", file=sys.stderr)
        return 1
    if any(len(t) == 0 for t in prompts):
        print("serve: empty prompt rows are not servable (there is no "
              "position to continue from)", file=sys.stderr)
        return 1
    limit = cfg.max_seq_len - args.new_tokens
    if any(len(t) > limit for t in prompts):
        print(f"serve: a prompt exceeds max_seq_len - new_tokens "
              f"({limit})", file=sys.stderr)
        return 1

    # One jitted servant per (padded_len) bucket: pad each batch to its
    # longest prompt rounded up to a multiple of 8, so a handful of
    # compiled shapes serves the whole stream.
    @jax.jit
    def run(params, batch, lens, cache=None):
        return transformer.generate(
            cfg, params, batch, args.new_tokens, prompt_lens=lens,
            rng=jax.random.PRNGKey(args.seed + 1),
            temperature=args.temperature, quantized_cache=args.int8_kv,
            stop_token=args.stop_token, cache=cache)

    if args.continuous:
        from tfmesos_tpu.serving import ContinuousBatcher, Request

        # Continuous mode has its own (tighter) length bound: prompts pad
        # to the prefill bucket, and speculative rounds overshoot by
        # n_draft on both the cache depth and the write high-water mark.
        nd = SPEC_N_DRAFT if args.speculative else 0
        bucket = args.prefill_chunk or 64
        # -1 in spec mode: the draft's backfill step writes one past the
        # proposals (ContinuousBatcher's depth check).
        ml = cfg.max_seq_len - (nd + 1 if nd else 0)
        # A pipelined stop surfaces one block late, so admission
        # reserves one more cache position.  (Speculative decoding
        # bypasses --pipeline-depth explicitly.)
        ov = int(bool(args.pipeline_depth) and not args.speculative
                 and args.stop_token is not None)
        climit = min((ml - nd - ov) // bucket * bucket,
                     ml - nd - ov - args.new_tokens + 1)
        if any(len(t) > climit for t in prompts):
            print(f"serve: a prompt exceeds the continuous-serving limit "
                  f"({climit} tokens at new-tokens={args.new_tokens}"
                  f"{', speculative' if args.speculative else ''})",
                  file=sys.stderr)
            return 1
        reqs = [Request(prompt=np.asarray(t, np.int32),
                        max_new_tokens=args.new_tokens,
                        stop_token=args.stop_token) for t in prompts]
        draft_cfg = draft_params = None
        if args.speculative:
            draft_cfg = transformer.TransformerConfig(
                vocab_size=cfg.vocab_size, d_model=cfg.d_model // 2,
                n_layers=max(1, cfg.n_layers // 2), n_heads=cfg.n_heads,
                d_ff=cfg.d_ff // 2, max_seq_len=cfg.max_seq_len,
                dtype=cfg.dtype)
            draft_params = transformer.init_params(
                draft_cfg, jax.random.PRNGKey(args.seed + 4))
        mesh = None
        if args.mesh is not None:
            from tfmesos_tpu.cli import parse_mesh
            from tfmesos_tpu.parallel.mesh import build_mesh
            mesh = build_mesh(parse_mesh(args.mesh))
        batcher = ContinuousBatcher(
            cfg, params, rows=args.batch, page_size=64, max_len=ml,
            temperature=args.temperature,
            rng=jax.random.PRNGKey(args.seed + 1),
            quantized_cache=args.int8_kv,
            prefill_chunk=args.prefill_chunk,
            draft_cfg=draft_cfg, draft_params=draft_params,
            n_draft=SPEC_N_DRAFT, mesh=mesh,
            draft_quantized_cache=args.int8_draft_kv,
            multi_step=args.multi_step,
            prefix_cache_pages=args.prefix_cache,
            pipeline_depth=args.pipeline_depth)
        if args.warmup:
            info = batcher.warmup()
            print(f"warmed {len(info['compiled'])} entry points in "
                  f"{info['seconds']:.1f}s", file=sys.stderr)
        sink = open(args.out, "w") if args.out else sys.stdout
        served = 0
        t0 = time.perf_counter()
        for c in batcher.run(reqs):
            sink.write(json.dumps({"rid": c.rid,
                                   "prompt_len": int(c.request.prompt.size),
                                   "tokens": c.tokens}) + "\n")
            served += 1
        dt = time.perf_counter() - t0
        if sink is not sys.stdout:
            sink.close()
        rate = batcher.acceptance_rate
        spec_note = ("" if rate is None
                     else f", draft acceptance {rate:.0%}")
        pst = batcher.prefix_cache_stats()
        pfx_note = ("" if pst is None else
                    f", prefix cache {pst['hits']}/{pst['hits'] + pst['misses']} hits "
                    f"({pst['hit_tokens']} tokens reused)")
        print(f"served {served} prompts continuously in {dt:.2f}s "
              f"(peak pages {batcher.peak_pages_used}/{batcher.n_pages}"
              f"{spec_note}{pfx_note})", file=sys.stderr)
        return 0

    alloc = pool = None
    if args.paged:
        # Pool sized for one batch at max shape — including the bucket
        # padding (prompts pad up to a multiple of 8, so the written
        # region can exceed limit+new_tokens by up to 7); pages recycle
        # between batches (a long-lived server would grow rows
        # incrementally).  --int8-kv composes: the pool stores int8 pages.
        page = 64
        max_width = -(-limit // 8) * 8
        per_row = -(-(max_width + args.new_tokens) // page)
        alloc = transformer.PageAllocator(args.batch * per_row, page)
        pool = transformer.init_paged_cache(cfg, args.batch * per_row,
                                            page_size=page,
                                            quantized=args.int8_kv)

    sink = open(args.out, "w") if args.out else sys.stdout
    served = 0
    t0 = time.perf_counter()
    for lo in range(0, len(prompts), args.batch):
        chunk = prompts[lo:lo + args.batch]
        lens = np.array([len(t) for t in chunk], np.int32)
        width = int(-(-max(lens) // 8) * 8)
        padded = np.zeros((len(chunk), width), np.int32)
        for i, t in enumerate(chunk):
            padded[i, :len(t)] = t
        if alloc is not None:
            # Pages must back the PADDED prompt region (prefill writes
            # the whole chunk) plus the continuation.
            for i in range(len(chunk)):
                alloc.ensure(i, width + args.new_tokens)
            cache = dict(pool, pages=alloc.table(range(len(chunk))))
            out = np.asarray(run(params, jnp.asarray(padded),
                                 jnp.asarray(lens), cache))
            for i in range(len(chunk)):
                alloc.release(i)
        else:
            out = np.asarray(run(params, jnp.asarray(padded),
                                 jnp.asarray(lens)))
        for i, t in enumerate(chunk):
            row = out[i, lens[i]:lens[i] + args.new_tokens].tolist()
            if args.stop_token is not None and args.stop_token in row:
                row = row[:row.index(args.stop_token) + 1]
            sink.write(json.dumps({"prompt_len": int(lens[i]),
                                   "tokens": row}) + "\n")
        served += len(chunk)
    dt = time.perf_counter() - t0
    if sink is not sys.stdout:
        sink.close()
    print(f"served {served} prompts ({served * args.new_tokens} tokens) "
          f"in {dt:.2f}s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
