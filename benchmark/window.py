"""Window arithmetic: from per-token timestamps to the end-to-end metrics.

Pure functions over :class:`Served` records, so that the definitions can be
tested without a chip.  Every time is a ``time.perf_counter()`` reading of
the one process that drives the batcher.

- A token belongs to the window if its timestamp lies in ``[t0, t1)``,
  whichever request it belongs to: nothing waits for completions.
- ``tok_s`` counts each generated token when it is emitted and a request's
  prompt tokens over the time its prefill took (admit event to first token).
- TTFT runs from when the request was DUE (open loop) to its first token
  at the client callback, over the requests due in the window; one with no
  first token by ``t1 + grace`` is failed, and enters the percentile with
  the time it had waited by then (a lower bound).
- TPOT is per request: (last - first token time) / (tokens - 1) over the
  tokens it emitted inside the window, for requests with at least
  ``TPOT_MIN_TOKENS`` there.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import numpy as np

TPOT_MIN_TOKENS = 32


@dataclasses.dataclass
class Served:
    """What the client side saw of one request."""
    index: int
    prompt_len: int
    max_new_tokens: int
    due: float                          # absolute; backlog: generator start
    submit: Optional[float] = None      # when submit() was called
    admit: Optional[float] = None       # the batcher's admit event
    token_times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    done: Optional[float] = None        # when the Completion was yielded
    prompt: Optional[np.ndarray] = None

    @property
    def first(self) -> Optional[float]:
        return self.token_times[0] if self.token_times else None


def percentile(values: Sequence[float], q: float) -> float:
    """Linear interpolation between order statistics (numpy's default)."""
    v = np.sort(np.asarray(values, dtype=np.float64))
    if v.size == 0:
        raise ValueError("percentile of nothing")
    pos = (v.size - 1) * q / 100.0
    lo = int(np.floor(pos))
    hi = min(lo + 1, v.size - 1)
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))


def tokens_in_window(reqs: Sequence[Served], t0: float, t1: float) -> float:
    """Delivered tokens by timestamp.  A generated token counts when it is
    emitted.  A prompt's tokens count while the chip works on them: spread
    evenly from the batcher's admit event to the first token, so that the
    part of a prefill inside the window counts and the rest does not (a
    request with no admit event counts whole at its first token).  Counted
    in one lump, a 2-8 k-token prompt that lands a millisecond either side
    of the window's edge moved a 51 s window's rate by 1-2%."""
    n = 0.0
    for r in reqs:
        if not r.token_times:
            continue
        ts = np.asarray(r.token_times)
        n += int(np.count_nonzero((ts >= t0) & (ts < t1)))
        first = float(ts[0])
        if r.admit is None or r.admit >= first:
            if t0 <= first < t1:
                n += r.prompt_len
        else:
            inside = min(first, t1) - max(r.admit, t0)
            if inside > 0:
                n += r.prompt_len * inside / (first - r.admit)
    return n


def ttft_samples(reqs: Sequence[Served], t0: float, t1: float,
                 grace: float) -> Dict[str, object]:
    """TTFT (seconds) of the requests due in ``[t0, t1)``."""
    vals, failed = [], 0
    for r in reqs:
        if not (t0 <= r.due < t1):
            continue
        first = r.first
        if first is None or first > t1 + grace:
            failed += 1
            vals.append(t1 + grace - r.due)
        else:
            vals.append(first - r.due)
    return {"values": vals, "failed": failed}


def tpot_samples(reqs: Sequence[Served], t0: float, t1: float) -> List[float]:
    """Per-request time per output token (seconds) inside the window."""
    out = []
    for r in reqs:
        ts = np.asarray(r.token_times)
        ts = ts[(ts >= t0) & (ts < t1)]
        if ts.size >= TPOT_MIN_TOKENS:
            out.append(float((ts[-1] - ts[0]) / (ts.size - 1)))
    return out


def live_rows_mean(reqs: Sequence[Served], t0: float, t1: float) -> float:
    """Time-average number of requests between their first and last token
    inside the window: the rows the decode blocks carried."""
    total = 0.0
    for r in reqs:
        if not r.token_times:
            continue
        a = max(r.token_times[0], t0)
        b = min(r.token_times[-1], t1)
        if b > a:
            total += b - a
    return total / (t1 - t0)


def decode_read_bytes(reqs: Sequence[Served], t0: float, t1: float,
                      bytes_per_context_token: int) -> int:
    """Bytes of cached keys and values that the decode steps inside
    ``[t0, t1)`` had to read: token ``k`` (0-based, k >= 1; token 0 comes
    from prefill) of a request is one decode step over a context of
    ``prompt_len + k`` positions."""
    total = 0
    for r in reqs:
        for k, t in enumerate(r.token_times):
            if k >= 1 and t0 <= t < t1:
                total += (r.prompt_len + k) * bytes_per_context_token
    return total


def live_tokens_mean(reqs: Sequence[Served], t0: float, t1: float) -> float:
    """Time-average number of pool slots that hold a live request's
    context inside the window: a request's prompt from the batcher's admit
    event (its first token where there is no event), each served token
    from its timestamp, both until the request's last token (the window's
    end for one still running).  Tokens, not pages: a request's last page
    is part empty, at most ``page_size - 1`` slots more per request."""
    total = 0.0
    for r in reqs:
        start = r.admit if r.admit is not None else r.first
        if start is None:
            continue
        finished = len(r.token_times) >= r.max_new_tokens
        end = min(r.token_times[-1], t1) if finished else t1
        held = end - max(start, t0)
        if held <= 0:
            continue
        total += r.prompt_len * held
        ts = np.asarray(r.token_times, dtype=np.float64)
        total += float(np.clip(end - np.maximum(ts, t0), 0.0, None).sum())
    return total / (t1 - t0)
