"""Prefill strategies for the serving engine.

Three ways to get an admitted prompt into the paged pool / recurrent state:

* ``slot`` — the seed path: one batch-1 ``MDL.prefill`` per admitted
  request, recurrent/enc-dec states merged into the engine state. Works for
  every architecture family; pays one dispatch (and one compile per prompt
  length) per request. Kept as the recompute-everything reference.
* ``batched`` — length-bucketed batched prefill: all requests admitted in a
  tick are grouped into padded-length buckets and each bucket runs under ONE
  jitted call (``last_idx`` picks each request's true last position,
  ``valid_len`` masks pad writes AND stops each row's recurrent carry at its
  true last token). All families: attention stacks carry only the shared
  pool; recurrent/enc-dec families ride their per-slot state rows through
  the call (gathered from / scattered back to the engine state).
* ``chunked`` — DCS-style interleave: prompts are cut into fixed-size
  chunks and ONE batched ``MDL.prefill_chunk`` call per engine tick covers
  every prefilling slot (vector ``ctx_start`` — each row at its own chunk
  cursor), between decode steps. Recurrent state is the explicit carry: a
  chunk resumes exactly where the previous chunk's returned state left off,
  so decode latency for running requests stays bounded by the chunk, not
  the longest admitted prompt, for attention AND recurrent-hybrid families
  alike — the scheduling overlap the paper's DCS gets by pipelining data
  movement with compute.

``make_prefiller`` picks the implementation; only runtimes whose prefill
branches bypass ``valid_len`` masking (ring pools, sharded pool writers)
still degrade to ``slot``.

Fused-horizon interaction: each prefiller exposes ``max_horizon`` — the cap
it imposes on the engine's fused decode horizon this tick. Slot/batched
prefill never cap (``None``); chunked prefill caps to 1 while chunks are
streaming, so running requests decode exactly one step between consecutive
chunks and the DCS interleave granularity (and TTFT of the prefilling
request) is independent of ``decode_horizon``.

Resume depths (``batched``/``chunked``): prefix-cache hits
(``req.cached_len > 0``, attention stacks) and preemption snapshots of the
recurrent carry (``engine._take_snapshot``, recurrent/enc-dec families)
both mean prefill covers only the *suffix* beyond the resume depth.
``chunked`` starts its chunk cursor there; ``batched`` groups resumes into
suffix-length buckets and passes the per-request depths as a vector
``ctx_start``, so one jitted call covers mixed resume depths. A snapshot
whose depth already covers the whole reconstructable context (the common
decode-preemption case) restores without any model call at all.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as MDL


def _make_batched_fn(cfg, rt):
    encdec = cfg.family == "encdec"

    def prefill_batch(params, state, tokens, bt, last_idx, valid_len):
        frames = (jnp.zeros((tokens.shape[0], cfg.enc_seq, cfg.d_model),
                            cfg.dtype) if encdec else None)
        return MDL.prefill(cfg, params, state, tokens, bt,
                           last_idx=last_idx, valid_len=valid_len,
                           frames=frames, rt=rt)
    return jax.jit(prefill_batch)


def _make_chunk_fn(cfg, rt):
    def prefill_chunk(params, state, tokens, bt, ctx_start, last_idx,
                      valid_len):
        return MDL.prefill_chunk(cfg, params, state, tokens, bt, ctx_start,
                                 last_idx=last_idx, valid_len=valid_len,
                                 rt=rt)
    return jax.jit(prefill_chunk)


def _suffix_bucket(n: int, cap: int) -> int:
    b = 8
    while b < n and b < cap:
        b *= 2
    return b if b >= n else -(-n // cap) * cap


def decode_table_bucket(live_pages: int, width: int) -> int:
    """Decode block-table width the engine dispatches for a live-page
    high-water mark: the prefill pow2 bucket with a 16-page floor, capped
    at the full table width. Shared by serving/engine.py (production) and
    benchmarks/kernel_bench.py (so the bench measures production widths)."""
    return min(width, _suffix_bucket(max(16, live_pages), width))


def _group_tables(eng, slots, span: int) -> np.ndarray:
    """Stacked Va2Pa rows for a prefill group, sliced to the pages the
    group's context actually spans (pow2-bucketed so the jit cache stays
    small) — the chunk path gathers every block-table slot per layer, so
    dispatching the max_context width would gather ~the whole pool."""
    bts = np.stack([eng.batcher.block_table_row(slot) for slot in slots])
    need = -(-span // eng.ecfg.page_size) + 1
    return bts[:, :min(_suffix_bucket(need, need), bts.shape[1])]


def prefill_suffix(eng, fn, grp) -> None:
    """One jitted ``prefill_chunk`` call covering a group of resumed
    requests: suffixes padded to a shared bucket length, per-request resume
    depths as the ``ctx_start`` vector, recurrent carries riding along as
    the group's state rows. ``grp``: [(slot, req, seq, emit, start)] with
    equal bucket sizes; ``fn`` is a ``_make_chunk_fn`` jit."""
    cap = max(8, eng.ecfg.max_prefill)
    blen = max(_suffix_bucket(len(seq) - start, cap)
               for _, _, seq, _, start in grp)
    toks = np.zeros((len(grp), blen), np.int32)
    starts = np.zeros((len(grp),), np.int32)
    lens = np.zeros((len(grp),), np.int32)
    for i, (_, _, seq, _, start) in enumerate(grp):
        suf = seq[start:]
        toks[i, :len(suf)] = suf
        starts[i] = start
        lens[i] = len(suf)
    slots = [slot for slot, *_ in grp]
    bts = _group_tables(eng, slots,
                        max(len(seq) for _, _, seq, _, _ in grp))
    with eng.timing.compiling(fn):
        logits, gstate = fn(
            eng.params, eng._group_prefill_state(slots), jnp.asarray(toks),
            jnp.asarray(bts), jnp.asarray(starts), jnp.asarray(lens - 1),
            jnp.asarray(lens))
    eng._merge_group_state(slots, gstate)
    emits = [emit for _, _, _, emit, _ in grp]
    first = eng._first_tokens(logits, emits)     # one batched sample call
    for i, (slot, req, _, emit, _) in enumerate(grp):
        req.generated = 1
        eng._emit_first(slot, req, int(first[i]), emit)


class SlotPrefiller:
    """Per-request whole-prompt prefill (seed semantics) — the recompute
    reference path: preemption snapshots are never consumed here, and only
    prefix-cache hits take the batch-1 suffix shortcut."""
    name = "slot"
    max_horizon = None                 # never caps the fused decode horizon

    def __init__(self, engine):
        self.eng = engine
        self._suffix_fn = _make_chunk_fn(engine.cfg, engine.rt) \
            if engine.chunkable else None

    @property
    def busy(self) -> bool:
        return False

    def run(self, admitted, active):
        for slot, req in admitted:
            if req.cached_len > 0:
                seq, emit = self.eng._prompt_seq(req)
                prefill_suffix(self.eng, self._suffix_fn,
                               [(slot, req, seq, emit, req.cached_len)])
            else:
                self._prefill_slot(slot, req)
        return active

    def _prefill_slot(self, slot: int, req) -> None:
        """Run the prompt through the model into this slot's pages.

        The functional prefill writes whole-batch; for slot-wise admission we
        run a batch-1 prefill and merge its cache rows into the engine state.
        """
        eng = self.eng
        req.generated = 1              # prefill emits the first token
        prompt, emit = eng._prompt_seq(req)
        bt = eng.batcher.block_table_row(slot)
        state1 = MDL.init_decode_state(eng.cfg, eng.pool_spec, 1)
        # share the pool so pages written land in the engine pool
        if "pool" in eng.state:
            state1["pool"] = eng.state["pool"]
        logits, state1 = MDL.prefill(
            eng.cfg, eng.params, state1, jnp.asarray(prompt[None]),
            jnp.asarray(bt[None]), rt=eng.rt,
            frames=(jnp.zeros((1, eng.cfg.enc_seq, eng.cfg.d_model),
                              eng.cfg.dtype)
                    if eng.cfg.family == "encdec" else None))
        if "pool" in eng.state:
            eng.state["pool"] = state1["pool"]
        for key in MDL.RSTATE_KEYS:
            if key in eng.state:
                def put(dst, src):
                    return dst.at[:, slot].set(src[:, 0])
                eng.state[key] = jax.tree.map(put, eng.state[key],
                                              state1[key])
        eng._emit_first(slot, req,
                        int(eng._first_tokens(np.asarray(logits)[:1],
                                              [emit])[0]), emit)


class BatchedPrefiller:
    """Length-bucketed batched prefill: every bucket is one jitted call.
    Resumed requests (prefix-cache hits / preemption snapshots) go through
    suffix-length buckets instead (vector ``ctx_start`` — one call per
    bucket, mixed resume depths); snapshot-covered requests restore with no
    model call at all."""
    name = "batched"
    max_horizon = None

    def __init__(self, engine):
        self.eng = engine
        self._fn = _make_batched_fn(engine.cfg, engine.rt)
        self._suffix_fn = _make_chunk_fn(engine.cfg, engine.rt) \
            if engine.chunkable else None

    @property
    def busy(self) -> bool:
        return False

    def _bucket(self, n: int) -> int:
        return _suffix_bucket(n, max(8, self.eng.ecfg.max_prefill))

    def run(self, admitted, active):
        eng = self.eng
        if not admitted:
            return active
        groups: dict[int, list] = {}
        fresh: dict[int, bool] = {}
        sgroups: dict[int, list] = {}
        starts, _ = eng._begin_prefill_group(admitted)
        for slot, req in admitted:
            seq, emit = eng._prompt_seq(req)
            start = starts[slot]
            if start >= len(seq):      # snapshot covers everything: restored
                req.generated = 1
                eng._emit_first(slot, req, None, emit=False)
                continue
            if start > 0:
                sgroups.setdefault(
                    self._bucket(len(seq) - start), []).append(
                        (slot, req, seq, emit, start))
                continue
            groups.setdefault(self._bucket(len(seq)), []).append(
                (slot, req, seq))
            fresh[slot] = emit
        for blen in sorted(sgroups):
            prefill_suffix(eng, self._suffix_fn, sgroups[blen])
        for blen in sorted(groups):
            grp = groups[blen]
            toks = np.zeros((len(grp), blen), np.int32)
            lens = np.zeros((len(grp),), np.int32)
            for i, (_, _, seq) in enumerate(grp):
                toks[i, :len(seq)] = seq
                lens[i] = len(seq)
            slots = [slot for slot, _, _ in grp]
            bts = np.stack([eng.batcher.block_table_row(slot)
                            for slot in slots])
            with eng.timing.compiling(self._fn):
                logits, gstate = self._fn(
                    eng.params, eng._group_prefill_state(slots),
                    jnp.asarray(toks), jnp.asarray(bts),
                    jnp.asarray(lens - 1), jnp.asarray(lens))
            eng._merge_group_state(slots, gstate)
            first = eng._first_tokens(logits, [fresh[s] for s in slots])
            for i, (slot, req, _) in enumerate(grp):
                req.generated = 1
                eng._emit_first(slot, req, int(first[i]), fresh[slot])
        return active


class ChunkedPrefiller:
    """Fixed-size chunk per prefilling slot per tick, interleaved with
    decode — ONE batched ``prefill_chunk`` call covers every streaming slot
    (vector chunk cursors), with each slot's recurrent carry gathered from
    and scattered back to the engine state rows. Slots finishing their last
    chunk join this tick's decode batch (same (generated, ctx) trajectory
    as the seed's admission-tick decode, so greedy outputs are
    token-identical)."""
    name = "chunked"

    def __init__(self, engine):
        self.eng = engine
        self._fn = _make_chunk_fn(engine.cfg, engine.rt)
        self._pos: dict[int, int] = {}      # slot -> next ctx_start

    @property
    def busy(self) -> bool:
        return bool(self._pos)

    @property
    def max_horizon(self):
        """One decode step per tick while chunks stream (DCS granularity);
        uncapped once every prompt is through."""
        return 1 if self._pos else None

    def run(self, admitted, active):
        eng = self.eng
        # resumes (prefix-cache hit / preemption snapshot) start the chunk
        # cursor at the covered depth
        starts, restored = eng._begin_prefill_group(admitted)
        self._pos.update(starts)
        fresh_cross = [s for s, _ in admitted
                       if eng.cfg.family == "encdec" and s not in restored]
        if fresh_cross:
            # enc-dec decoder chunks attend over carried cross-KV rows:
            # materialize them in ONE batched encoder pass per tick
            # (snapshot-restored slots brought their own rows back)
            eng._init_cross_rows(fresh_cross)
        if not self._pos:
            return active
        C = max(1, eng.ecfg.prefill_chunk)
        completed = []
        grp = []                            # (slot, req, prompt, emit, valid)
        for slot in sorted(self._pos):
            req = eng.batcher.slots[slot]
            if req is None or req.prefill_done:
                # slot freed or preempted out from under a mid-prefill
                # request; its re-admission re-registers from chunk 0
                del self._pos[slot]
                continue
            prompt, emit = eng._prompt_seq(req)
            if self._pos[slot] >= len(prompt):
                # snapshot covered the whole context: restored, no chunks.
                # kv_written is set BEFORE the growth-page grab: the
                # restored pages/state genuinely hold the context, so a
                # mark_prefill_done MemoryError re-snapshots instead of
                # silently degrading the next resume to full recompute
                del self._pos[slot]
                req.generated = 1
                req.kv_written = True
                if eng.batcher.mark_prefill_done(slot):
                    eng._emit_first(slot, req, None, emit=False)
                    completed.append(slot)
                continue
            grp.append((slot, req, prompt, emit,
                        min(C, len(prompt) - self._pos[slot])))
        if grp:
            toks = np.zeros((len(grp), C), np.int32)
            starts = np.zeros((len(grp),), np.int32)
            lens = np.zeros((len(grp),), np.int32)
            for i, (slot, _, prompt, _, valid) in enumerate(grp):
                start = self._pos[slot]
                toks[i, :valid] = prompt[start:start + valid]
                starts[i] = start
                lens[i] = valid
            slots = [slot for slot, *_ in grp]
            # attention reads nothing past the processed context, so the
            # table slice tracks the deepest cursor, not the full prompts
            bts = _group_tables(eng, slots, int((starts + lens).max()))
            with eng.timing.compiling(self._fn):
                logits, gstate = self._fn(
                    eng.params, eng._group_prefill_state(slots),
                    jnp.asarray(toks), jnp.asarray(bts), jnp.asarray(starts),
                    jnp.asarray(lens - 1), jnp.asarray(lens))
            eng._merge_group_state(slots, gstate)
            fin = [(i, slot, req, emit)
                   for i, (slot, req, prompt, emit, valid) in enumerate(grp)
                   if starts[i] + valid >= len(prompt)]
            first = (eng._first_tokens(np.asarray(logits)[[i for i, *_ in
                                                           fin]],
                                       [e for *_, e in fin]) if fin else [])
            for j, (i, slot, req, emit) in enumerate(fin):
                del self._pos[slot]
                req.generated = 1
                # every chunk is through the model: the pages/state hold
                # the full context, so a finish-line preemption may
                # snapshot it (resume restores instead of re-chunking)
                req.kv_written = True
                if eng.batcher.mark_prefill_done(slot):
                    eng._emit_first(slot, req, int(first[j]), emit)
                    completed.append(slot)
                # else: pool exhausted at the finish line — the batcher
                # preempted and requeued the bare prompt, WITH a snapshot
                # when the family carries one
            for i, (slot, _, _, _, valid) in enumerate(grp):
                if slot in self._pos:
                    self._pos[slot] += valid
        return sorted(set(active) | set(completed)) if completed else active


def make_prefiller(mode: str, engine):
    """'slot' | 'batched' | 'chunked'. Every model family supports every
    mode (state-carrying chunk/batch prefill covers recurrent and enc-dec
    stacks); only runtimes whose prefill branches bypass ``valid_len``
    masking (ring pools, sharded pool writers) degrade to 'slot'."""
    if mode == "batched" and engine.batchable:
        return BatchedPrefiller(engine)
    if mode == "chunked" and engine.chunkable:
        return ChunkedPrefiller(engine)
    assert mode in ("slot", "batched", "chunked"), mode
    return SlotPrefiller(engine)
