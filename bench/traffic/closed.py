"""Traffic kind ``closed``: a batch of long sessions whose contexts are
built in set-up and decoded in the window.

Contexts sit at the quantiles (i + 0.5) / N of the mix's clipped normal
length distribution (``context_tokens``). Each session's budget is what
it could emit if every decode step ran at the bandwidth bound, (step
weights + the batch's live KV) / ``bandwidth_bytes_per_s``, over the
run's seconds plus ``margin_s``: so no session ends inside the window
however fast the engine gets. N is the largest number of sessions whose
contexts and budgets fit the pool.

Set-up submits every session, runs the prefill until each has its first
token, and warms every decode table width the sessions can grow into.
The window ticks the engine until the seconds are up, then collects the
horizon in flight, so the tokens counted are the work done in the time
measured.
"""
from __future__ import annotations

import math

import generator
import work


def session_budget(mix: dict, m: dict, contexts: list[int],
                   run_seconds: float) -> int:
    b = mix["budget"]
    step_bytes = (work.step_weight_bytes(m)
                  + sum(contexts) * work.kv_bytes_per_token(m))
    steps_per_s = float(b["bandwidth_bytes_per_s"]) / step_bytes
    return math.ceil((run_seconds + b["margin_s"]) * steps_per_s)


def plan(mix: dict, m: dict, engine: dict,
         run_seconds: float) -> tuple[list[int], int]:
    """(contexts, budget) of the largest batch that fits the pool."""
    page, K = engine["page_size"], engine["decode_horizon"]
    for n in range(engine["n_slots"], 0, -1):
        ctxs = generator.quantile_lengths(mix["context_tokens"], n)
        budget = session_budget(mix, m, ctxs, run_seconds)
        pages = sum(-(-(c + budget + K + 1) // page) for c in ctxs)
        if pages <= engine["n_pages"] and \
                max(ctxs) + budget + K + 1 <= engine["max_context"]:
            return ctxs, budget
    raise SystemExit("bench: not one session of this mix fits the pool")


def requests(mix: dict, m: dict, engine: dict, run_seconds: float,
             seed: int) -> list[generator.Request]:
    ctxs, budget = plan(mix, m, engine, run_seconds)
    rng = generator.token_rng(seed)
    return [generator.Request(i, generator.token_ids(rng, m["V"], c), budget)
            for i, c in enumerate(ctxs)]


def setup(run, reqs) -> None:
    for r in reqs:
        run.submit(r)
    run.tick_until(lambda: all(run.n_out(r) for r in reqs))
    run.quiesce()
    run.warm_decode(max(len(r.prompt) + r.max_new_tokens for r in reqs))


def drive(run, reqs, seconds: float) -> None:
    while run.elapsed() < seconds:
        run.tick()
    run.quiesce()
