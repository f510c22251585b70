"""Serving engines (DESIGN.md §4).

Two surfaces:

* :func:`generate` — one-shot batched prefill + decode for LM-family
  bundles.  Kept as the static-batching baseline (and the parity oracle
  for the slot engine), with honest accounting: ``ServeStats`` reports
  *live* (pre-eos) decode tokens with the prefill-sampled token
  attributed to prefill, ``done`` is seeded from that first sampled
  token (a batch that immediately emits eos decodes zero steps), and the
  host checks termination every ``sync_every`` steps instead of forcing
  a device→host round-trip per token.

* :class:`SlotEngine` — the continuous-batching engine.  A host-side
  request queue feeds a fixed pool of ``n_slots`` decode slots; one
  donated ``jit`` step (``lax.scan`` over ``sync_every`` micro-steps)
  advances *all* slots with per-slot KV/state caches in the carry — the
  engine-carry discipline of ``train/engine.py``.  Admit/evict happens
  between scans by writing a freshly prefilled request into a freed
  slot; prompts are right-padded to bucketed lengths (pad positions get
  position id -1, invalid under every attention mask rule) so prefill
  compiles once per bucket and the decode executable never retraces —
  the ``subset_epoch_plan`` pad/gate trick transferred to serving: dead
  slots still run the step but their state is selected back bit-exactly
  (like ``optim.gate_step``).

The slot engine serves two families behind one loop: decoder LMs
(per-slot KV cache, eos termination) and the paper's RNN-T CRDNN
(per-slot encoder buffer + prediction-network state; one *joint step*
per scan micro-step, blank advances the frame cursor — streaming greedy
transducer search, token-for-token equal to
:func:`rnnt_greedy_reference`).
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


# ===========================================================================
# One-shot generate (static batching)
# ===========================================================================

@dataclasses.dataclass
class ServeStats:
    """Timing/throughput for one :func:`generate` call.

    ``decode_tokens`` counts only *live* tokens — sampled for an example
    that had not already emitted eos — so padded post-eos eos tokens
    never inflate tok/s.  The token sampled from the prefill logits is
    attributed to prefill (``prefill_tokens``), not to the decode phase.
    """

    prefill_s: float
    decode_s: float
    prompt_tokens: int        # prompt tokens processed by prefill (B * Sp)
    prefill_tokens: int       # tokens sampled from prefill logits (B)
    decode_tokens: int        # live (pre-eos) tokens emitted by decode steps
    decode_steps: int         # decode dispatches actually executed

    @property
    def tokens_per_s(self) -> float:
        """Decode-phase throughput over live decode tokens only."""
        return self.decode_tokens / max(self.decode_s, 1e-9)

    @property
    def prefill_tokens_per_s(self) -> float:
        return (self.prompt_tokens + self.prefill_tokens) \
            / max(self.prefill_s, 1e-9)


def sample_token(logits, key, temperature: float = 0.0):
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature).astype(jnp.int32)


def generate(
    bundle,
    params,
    prompts: jnp.ndarray,           # (B, S_prompt) int32
    max_new_tokens: int,
    *,
    temperature: float = 0.0,
    eos_id: Optional[int] = None,
    key=None,
    extra_inputs: Optional[Dict] = None,
    sync_every: int = 8,
):
    """Greedy/temperature batched generation.  Returns ``(tokens
    (B, T_new), stats)``.

    Termination is checked on the host every ``sync_every`` steps (the
    ``done`` mask stays on device in between), so up to
    ``sync_every - 1`` trailing all-eos columns may be returned after
    every example has finished — token values are unchanged vs a
    per-step check because finished examples are pinned to ``eos_id``
    (tests/test_serve_engine.py asserts exact equality).
    """
    if bundle.cfg.family == "rnnt":
        raise ValueError(
            "generate() is the LM one-shot path; RNN-T uses streaming "
            "greedy transducer search — SlotEngine or "
            "rnnt_greedy_reference")
    key = jax.random.PRNGKey(0) if key is None else key
    B, Sp = prompts.shape
    batch = dict(extra_inputs or {}, tokens=prompts)

    t0 = time.time()
    # jit on bundle.prefill itself (not a fresh lambda) so repeated
    # generate() calls hit the cached lowering instead of recompiling
    prefill = jax.jit(bundle.prefill, static_argnames=("cache_len",))
    logits, cache = prefill(params, batch, cache_len=Sp + max_new_tokens)
    logits.block_until_ready()
    t_prefill = time.time() - t0

    decode = jax.jit(bundle.decode)
    out = []
    tok = sample_token(logits, key, temperature)
    out.append(tok)
    # the token sampled from the *prefill* logits can already be eos:
    # seed `done` from it instead of assuming a live batch
    done = (tok == eos_id) if eos_id is not None else None
    n_live = jnp.zeros((), jnp.int32)
    steps = 0
    t0 = time.time()
    for i in range(max_new_tokens - 1):
        # one device->host sync per `sync_every` steps, not per token
        if done is not None and i % sync_every == 0 and bool(done.all()):  # repro: noqa[host-sync-loop] -- the amortized early-exit probe; rate is capped by sync_every
            break
        key = jax.random.fold_in(key, i)
        logits, cache = decode(params, cache, tok)
        tok = sample_token(logits, key, temperature)
        if done is not None:
            n_live = n_live + jnp.sum(~done)        # live *before* this step
            done = done | (tok == eos_id)
            tok = jnp.where(done, eos_id, tok)
        else:
            n_live = n_live + B
        out.append(tok)
        steps += 1
    jax.block_until_ready(out[-1])
    t_decode = time.time() - t0
    tokens = jnp.stack(out, axis=1)
    stats = ServeStats(t_prefill, t_decode,
                       prompt_tokens=B * Sp, prefill_tokens=B,
                       decode_tokens=int(n_live), decode_steps=steps)
    return tokens, stats


# ===========================================================================
# Continuous batching: requests, completions, slot engine
# ===========================================================================

@dataclasses.dataclass
class Request:
    """One serving request.  ``inputs`` holds host arrays: ``tokens``
    (Lp,) int32 for LM families, ``feats`` (T, F) float32 for RNN-T.
    ``arrival_s`` is the offered-load arrival time relative to
    ``SlotEngine.run``'s start (0 = already queued).  ``deadline_s``
    (seconds after arrival, None = no deadline) bounds total latency: a
    request still queued or still decoding past its deadline is evicted
    with ``status="expired"`` instead of holding a slot forever."""

    uid: int
    inputs: Dict[str, np.ndarray]
    max_new_tokens: int
    arrival_s: float = 0.0
    deadline_s: Optional[float] = None


@dataclasses.dataclass
class Completion:
    """Terminal record for one request.  ``status`` is ``"ok"`` (decoded
    to eos/budget), ``"rejected"`` (host queue overflow — backpressure,
    the request never held a slot) or ``"expired"`` (deadline passed in
    queue or mid-decode; ``tokens`` holds whatever was emitted)."""

    uid: int
    tokens: List[int]
    arrival_s: float
    admit_s: float
    done_s: float
    status: str = "ok"

    @property
    def latency_s(self) -> float:
        """Queue wait + decode: arrival to completion."""
        return self.done_s - self.arrival_s


def _select_slots(mask, new, old):
    """Leafwise per-slot select: slots where ``mask`` is False keep their
    old state bit-exactly (the serving twin of ``optim.gate_step``)."""
    def sel(n, o):
        m = mask.reshape(mask.shape + (1,) * (n.ndim - 1))
        return jnp.where(m, n, o)
    return jax.tree.map(sel, new, old)


class SlotEngine:
    """Continuous-batching serving engine over a ``ModelBundle``.

    Slot lifecycle (DESIGN.md §4):

    1. **admit** — a pending request is prefilled (prompt right-padded to
       its length bucket) and written into a free slot of the donated
       state: per-slot cache pool, last-token vector, live mask, output
       buffer and budget.  One compiled admit executable per bucket.
    2. **decode** — one donated ``jit(lax.scan)`` advances every slot
       ``sync_every`` micro-steps; non-live slots are selected back
       bit-exactly.  The host syncs once per scan (live flags + counts),
       never per token.
    3. **evict** — slots whose request finished (eos / frame cursor
       exhausted / budget) are read out and freed; the next pending
       request is admitted into the freed slot without recompiling.

    Families: decoder LMs (``tokens`` prompts, per-slot KV caches, eos
    termination) and RNN-T (``feats`` prompts; the slot cache is the
    encoder buffer + prediction-net state, a micro-step is one joint
    step, blanks advance the frame cursor and are never emitted).
    """

    def __init__(self, bundle, params, *,
                 n_slots: int = 8,
                 max_new_tokens: int = 32,
                 max_prompt_len: int = 64,
                 temperature: float = 0.0,
                 eos_id: Optional[int] = None,
                 sync_every: int = 4,
                 max_symbols: int = 8,
                 bucket_min: int = 8,
                 seed: int = 0,
                 max_queue: Optional[int] = None,
                 clock=time.time):
        cfg = bundle.cfg
        if cfg.family in ("vlm", "encdec"):
            raise ValueError(f"SlotEngine serves LM and RNN-T families, "
                             f"not {cfg.family!r}")
        self.bundle = bundle
        self.params = params
        self.cfg = cfg
        self.n_slots = int(n_slots)
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.eos_id = eos_id
        self.sync_every = int(sync_every)
        self.max_symbols = int(max_symbols)
        self.bucket_min = int(bucket_min)
        self.is_rnnt = cfg.family == "rnnt"
        self._key = jax.random.PRNGKey(seed)
        # bounded host queue: None = unbounded (legacy); a bound turns
        # overflow into an immediate backpressure rejection instead of
        # unbounded host memory growth under offered overload
        self.max_queue = None if max_queue is None else int(max_queue)
        # injectable for deterministic deadline tests; must be monotonic
        self._clock = clock
        self.n_decode_dispatches = 0
        self.n_admits = 0
        self.n_rejected = 0
        self.n_expired = 0

        if self.is_rnnt:
            red = cfg.rnnt.time_reduction
            # feats buckets must stay multiples of the conv reduction so
            # encoder frame counts are exact per bucket
            self.bucket_min = max(self.bucket_min, red)
            self.max_prompt_len = self._bucket_of(int(max_prompt_len))
            self.cache_capacity = self.max_prompt_len // red
        else:
            # ring (sliding-window) caches evict oldest-first by buffer
            # order: bucket padding would push real keys out of a full
            # window, so windowed archs use exact-length prompts (one
            # prefill trace per distinct length — still correct)
            self.exact_lengths = bool(
                cfg.window and "local" in cfg.layer_kinds())
            self.max_prompt_len = (int(max_prompt_len) if self.exact_lengths
                                   else self._bucket_of(int(max_prompt_len)))
            self.cache_capacity = self.max_prompt_len + self.max_new_tokens

        # -- slot-state pool (the donated carry) ------------------------
        if self.is_rnnt:
            cache1 = bundle.init_cache(1, self.cache_capacity,
                                       max_symbols=self.max_symbols)
        else:
            cache1 = bundle.init_cache(1, self.cache_capacity)
        n = self.n_slots
        pool = jax.tree.map(
            lambda l: jnp.broadcast_to(l, (n,) + l.shape).copy(), cache1)
        fill = int(eos_id) if eos_id is not None else 0
        self._state = {
            "cache": pool,
            "tok": jnp.zeros((n,), jnp.int32),
            "live": jnp.zeros((n,), bool),
            "n_out": jnp.zeros((n,), jnp.int32),
            "budget": jnp.ones((n,), jnp.int32),
            "out": jnp.full((n, self.max_new_tokens), fill, jnp.int32),
        }
        self._fill = fill

        self._admit_jit = jax.jit(self._admit_fn, donate_argnums=(1,))
        self._decode_jit = jax.jit(self._decode_fn, donate_argnums=(1,))
        # deadline eviction: clear live flags for a host-computed kill
        # mask — the slot becomes a dead-slot no-op on the next scan,
        # exactly like a finished request (no retrace, no state rebuild)
        self._expire_jit = jax.jit(
            lambda state, kill: dict(state, live=state["live"] & ~kill),
            donate_argnums=(0,))

    # -- buckets --------------------------------------------------------
    def _bucket_of(self, length: int) -> int:
        """Smallest power-of-two bucket >= length (>= bucket_min)."""
        b = self.bucket_min
        while b < length:
            b *= 2
        return b

    def bucket_for(self, request: Request) -> int:
        key = "feats" if self.is_rnnt else "tokens"
        L = int(np.shape(request.inputs[key])[0])
        if L > self.max_prompt_len:
            raise ValueError(f"request {request.uid}: prompt length {L} "
                             f"exceeds max_prompt_len={self.max_prompt_len}")
        if not self.is_rnnt and self.exact_lengths:
            return L
        return self._bucket_of(L)

    # -- family hooks ---------------------------------------------------
    def _prefill_one(self, params, inputs, length):
        """B=1 prefill of one bucketed request -> (logits (1,V), cache)."""
        if self.is_rnnt:
            logits, cache = self.bundle.prefill(
                params,
                {"feats": inputs["feats"][None],
                 "feat_lens": length[None]},
                max_symbols=self.max_symbols)
            pad = self.cache_capacity - cache["enc"].shape[1]
            if pad:
                cache = dict(cache, enc=jnp.pad(
                    cache["enc"], ((0, 0), (0, pad), (0, 0))))
            return logits, cache
        return self.bundle.prefill(
            params, {"tokens": inputs["tokens"][None]},
            cache_len=self.cache_capacity, prompt_lens=length[None])

    def _emit_and_done(self, tok, cache):
        """Per-slot emission mask + termination mask for sampled ``tok``
        given the *post-step* cache (leaves carry the pool's (n, 1, ...)
        layout; scalars arrive as (n,))."""
        if self.is_rnnt:
            from repro.models.rnnt import BLANK_ID
            t = cache["t"].reshape(-1)
            t_len = cache["t_len"].reshape(-1)
            exhausted = t >= t_len
            return (tok != BLANK_ID) & ~exhausted, exhausted
        emit = jnp.ones(tok.shape, bool)
        done = (tok == self.eos_id) if self.eos_id is not None \
            else jnp.zeros(tok.shape, bool)
        return emit, done

    # -- jitted executables ---------------------------------------------
    def _admit_fn(self, params, state, slot, inputs, length, budget, key):
        logits, cache1 = self._prefill_one(params, inputs, length)
        tok0 = sample_token(logits, key, self.temperature)[0]
        if self.is_rnnt:
            from repro.models.rnnt import BLANK_ID
            emit0 = tok0 != BLANK_ID
            done0 = jnp.zeros((), bool)       # frame 0 is always valid
        else:
            emit0 = jnp.ones((), bool)
            done0 = (tok0 == self.eos_id) if self.eos_id is not None \
                else jnp.zeros((), bool)
        cache = jax.tree.map(lambda pool, leaf: pool.at[slot].set(leaf),
                             state["cache"], cache1)
        n_out0 = emit0.astype(jnp.int32)
        out_row = jnp.full((self.max_new_tokens,), self._fill, jnp.int32)
        out_row = out_row.at[0].set(jnp.where(emit0, tok0, self._fill))
        live0 = ~done0 & (n_out0 < budget)
        return {
            "cache": cache,
            "tok": state["tok"].at[slot].set(tok0),
            "live": state["live"].at[slot].set(live0),
            "n_out": state["n_out"].at[slot].set(n_out0),
            "budget": state["budget"].at[slot].set(budget),
            "out": state["out"].at[slot].set(out_row),
        }

    def _decode_fn(self, params, state, key):
        n = self.n_slots

        def one(cache, tok):
            logits, cache = self.bundle.decode(params, cache, tok[None])
            return logits[0], cache

        def micro_step(st, k):
            live = st["live"]
            logits, new_cache = jax.vmap(one)(st["cache"], st["tok"])
            tok = sample_token(logits, jax.random.fold_in(key, k),
                               self.temperature)
            emit, done_now = self._emit_and_done(tok, new_cache)
            emit = emit & live
            idx = jnp.clip(st["n_out"], 0, self.max_new_tokens - 1)
            rows = jnp.arange(n)
            cur = st["out"][rows, idx]
            out = st["out"].at[rows, idx].set(jnp.where(emit, tok, cur))
            n_out = st["n_out"] + emit.astype(jnp.int32)
            finished = live & (done_now | (n_out >= st["budget"]))
            # dead slots are bit-exact no-ops: state selected back leafwise
            return {
                "cache": _select_slots(live, new_cache, st["cache"]),
                "tok": jnp.where(live, tok, st["tok"]),
                "live": live & ~finished,
                "n_out": n_out,
                "budget": st["budget"],
                "out": out,
            }, None

        state, _ = jax.lax.scan(micro_step, state,
                                jnp.arange(self.sync_every))
        return state

    # -- host-side admit/evict loop --------------------------------------
    def _pad_inputs(self, request: Request, bucket: int):
        if self.is_rnnt:
            feats = np.asarray(request.inputs["feats"], np.float32)
            L = feats.shape[0]
            padded = np.zeros((bucket,) + feats.shape[1:], np.float32)
            padded[:L] = feats
            return {"feats": jnp.asarray(padded)}, L
        toks = np.asarray(request.inputs["tokens"], np.int32)
        L = toks.shape[0]
        padded = np.zeros((bucket,), np.int32)
        padded[:L] = toks
        return {"tokens": jnp.asarray(padded)}, L

    def _admit(self, slot: int, request: Request):
        bucket = self.bucket_for(request)
        inputs, L = self._pad_inputs(request, bucket)
        budget = min(int(request.max_new_tokens), self.max_new_tokens)
        self._key, sub = jax.random.split(self._key)
        self._state = self._admit_jit(
            self.params, self._state, jnp.asarray(slot, jnp.int32),
            inputs, jnp.asarray(L, jnp.int32),
            jnp.asarray(budget, jnp.int32), sub)
        self.n_admits += 1

    def _expired(self, req: Request, now: float) -> bool:
        return (req.deadline_s is not None
                and now > req.arrival_s + req.deadline_s)

    def run(self, requests: Sequence[Request]) -> List[Completion]:
        """Serve ``requests`` (offered load via ``arrival_s``) to
        completion.  Admission, decoding and eviction interleave: freed
        slots are refilled between decode scans without recompiling.
        Arrivals land in a host queue bounded by ``max_queue`` (overflow
        -> ``status="rejected"`` backpressure); a request whose
        ``deadline_s`` passes while queued is dropped without ever
        taking a slot, and one that expires mid-decode is evicted as a
        dead-slot no-op with its partial tokens (``status="expired"``)."""
        clock = self._clock
        schedule = collections.deque(
            sorted(requests, key=lambda r: (r.arrival_s, r.uid)))
        queue: "collections.deque[Request]" = collections.deque()
        active: Dict[int, Tuple[Request, float]] = {}
        free = list(range(self.n_slots))
        completions: List[Completion] = []
        t0 = clock()
        while schedule or queue or active:
            now = clock() - t0
            # arrivals -> bounded host queue; overflow is rejected NOW
            # (backpressure) instead of growing the queue without bound
            while schedule and schedule[0].arrival_s <= now:
                req = schedule.popleft()
                if (self.max_queue is not None
                        and len(queue) >= self.max_queue):
                    self.n_rejected += 1
                    completions.append(Completion(
                        uid=req.uid, tokens=[], arrival_s=req.arrival_s,
                        admit_s=float("nan"), done_s=clock() - t0,
                        status="rejected"))
                    continue
                queue.append(req)
            # queued requests whose deadline passed never take a slot
            now = clock() - t0
            kept: "collections.deque[Request]" = collections.deque()
            for req in queue:
                if self._expired(req, now):
                    self.n_expired += 1
                    completions.append(Completion(
                        uid=req.uid, tokens=[], arrival_s=req.arrival_s,
                        admit_s=float("nan"), done_s=clock() - t0,
                        status="expired"))
                else:
                    kept.append(req)
            queue = kept
            while free and queue:
                req = queue.popleft()
                slot = free.pop()
                self._admit(slot, req)
                active[slot] = (req, clock() - t0)
            if not active:
                if schedule:
                    # idle: nothing decoding, next arrival in the future
                    time.sleep(min(max(schedule[0].arrival_s - now, 0.0),
                                   0.005))
                continue
            self._key, sub = jax.random.split(self._key)
            self._state = self._decode_jit(self.params, self._state, sub)
            self.n_decode_dispatches += 1
            # ONE host sync per scan: live flags + emission counts
            live = np.asarray(self._state["live"])    # repro: noqa[host-sync-loop] -- the documented once-per-scan sync point (DESIGN §4)
            n_out = np.asarray(self._state["n_out"])  # repro: noqa[host-sync-loop] -- fetched alongside live, same single sync point
            # deadline sweep over active slots: expired ones are killed
            # on device (live cleared) and read out below like finished
            now = clock() - t0
            kill = np.zeros(self.n_slots, bool)
            for slot, (req, _) in active.items():
                if live[slot] and self._expired(req, now):
                    kill[slot] = True
            if kill.any():
                self._state = self._expire_jit(self._state,
                                               jnp.asarray(kill))
                live = live & ~kill
            finished = [s for s in list(active) if not live[s]]
            if finished:
                # one fetch of the whole out pool for the sweep — indexing
                # `out[slot]` per finished slot would dispatch a device
                # gather + blocking D2H transfer for every eviction
                out_pool = np.asarray(self._state["out"])  # repro: noqa[host-sync-loop] -- single pool fetch, only on sweeps that evict
            for slot in finished:
                req, admit_s = active.pop(slot)
                toks = out_pool[slot][: int(n_out[slot])]
                if kill[slot]:
                    self.n_expired += 1
                completions.append(Completion(
                    uid=req.uid, tokens=[int(t) for t in toks],
                    arrival_s=req.arrival_s, admit_s=admit_s,
                    done_s=clock() - t0,
                    status="expired" if kill[slot] else "ok"))
                free.append(slot)
        return completions


# ===========================================================================
# RNN-T greedy decode: non-streaming reference
# ===========================================================================

def rnnt_greedy_reference(bundle, params, feats, feat_lens,
                          max_symbols: int = 8) -> List[List[int]]:
    """Greedy transducer search as the textbook host loop (Graves 2012):
    for each frame, emit argmax symbols until blank (or ``max_symbols``
    emissions), then advance.  The oracle the streaming SlotEngine path
    must match token-for-token (tests/test_serve_engine.py)."""
    from repro.models import rnnt as rnnt_mod
    cfg = bundle.cfg
    enc = rnnt_mod.encode(params, cfg, jnp.asarray(feats),
                          jnp.asarray(feat_lens))
    t_lens = np.minimum(np.asarray(rnnt_mod.encoder_lengths(
        cfg, jnp.asarray(feat_lens))), enc.shape[1])
    results: List[List[int]] = []
    for b in range(enc.shape[0]):
        g, h = rnnt_mod.pred_start(params, cfg, 1, dtype=enc.dtype)
        toks: List[int] = []
        for t in range(int(t_lens[b])):
            for _ in range(max_symbols):
                logits = rnnt_mod.joint_step(params, enc[b: b + 1, t], g)
                k = int(jnp.argmax(logits[0]))  # repro: noqa[host-sync-loop] -- textbook host-loop oracle; per-symbol sync is its definition
                if k == rnnt_mod.BLANK_ID:
                    break
                toks.append(k)
                g, h = rnnt_mod.pred_step(
                    params, cfg, jnp.asarray([k], jnp.int32), h)
        results.append(toks)
    return results
