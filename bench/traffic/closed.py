"""Traffic kind ``closed``: a batch of long sessions whose contexts are
built in set-up and decoded in the window.

Contexts sit at the quantiles (i + 0.5) / N of the mix's clipped normal
length distribution (``context_tokens``). Each session's budget is what
it could emit if every decode step ran at the bandwidth bound, (step
weights + the batch's live KV) / ``bandwidth_bytes_per_s``, over the
run's seconds plus ``margin_s``: so no session ends inside the window
however fast the engine gets. N is the largest number of sessions whose
contexts and budgets fit the pool. The step's bytes and a session's pages
are the architecture module's (``step_bytes``, ``session_pages``).

Set-up submits every session, runs the prefill until each has its first
token, and warms every decode table width the sessions can grow into.
The window ticks the engine until the seconds are up, then collects the
horizon in flight, so the tokens counted are the work done in the time
measured.
"""
from __future__ import annotations

import math

import generator


def session_budget(mix: dict, arch, m: dict, contexts: list[int],
                   run_seconds: float) -> int:
    b = mix["budget"]
    steps_per_s = (float(b["bandwidth_bytes_per_s"])
                   / arch.step_bytes(m, contexts))
    return math.ceil((run_seconds + b["margin_s"]) * steps_per_s)


def plan(mix: dict, arch, m: dict, engine: dict,
         run_seconds: float) -> tuple[list[int], int]:
    """(contexts, budget) of the largest batch that fits the pool."""
    K = engine["decode_horizon"]
    for n in range(engine["n_slots"], 0, -1):
        ctxs = generator.quantile_lengths(mix["context_tokens"], n)
        budget = session_budget(mix, arch, m, ctxs, run_seconds)
        pages = sum(arch.session_pages(m, engine, c + budget + K + 1)
                    for c in ctxs)
        if pages <= engine["n_pages"] and \
                max(ctxs) + budget + K + 1 <= engine["max_context"]:
            return ctxs, budget
    raise SystemExit("bench: not one session of this mix fits the pool")


def requests(mix: dict, arch, m: dict, engine: dict, run_seconds: float,
             seed: int) -> list[generator.Request]:
    ctxs, budget = plan(mix, arch, m, engine, run_seconds)
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
