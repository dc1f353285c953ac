"""Continuous-batching serving engine: a slotted request pool.

The static serving loop (``ServeSetup.make_generate``) advances one batch
shape in lockstep: every row prefills together and decodes until the LAST
row finishes, so under skewed request lengths short requests pin their slot
while a straggler drains.  This engine keeps a pool of ``slots`` rows where
each slot carries its own absolute position, its own remaining-token budget
and an active mask:

* **admit** — queued requests are prefilled slot-locally at their EXACT
  prompt length (see the ragged-prompt rule in docs/serving.md) and their
  decode state (LLN ``(s, z)`` + diag tail, or the softmax KV block) is
  scattered into the freed pool rows (``PoolSetup.admit_fn``), while the
  other rows keep decoding from where they are — admission is mid-segment
  from the pool's point of view.  Same-length queued prompts admit as ONE
  batched prefill when that is exact (softmax / fixed alpha/beta; dynamic
  moment matching pools prompt-batch statistics, so those configs prefill
  per request);
* **decode** — ``segment`` steps run as ONE jitted ``lax.scan`` with the
  pooled cache carry donated (``PoolSetup.segment_fn``), so steady-state
  throughput matches the static scanned loop;
* **evict** — a row whose budget hits zero drops out of the active mask
  *inside* the scan (masked rows provably advance nothing: KV writes, LLN
  state, tails and positions are all ``where``-guarded on the mask), and
  its slot is handed back to the queue at the next segment boundary.

Why this is cheap for LLN attention: the per-request decode state is
O(d^2) — a (H, D, Dv) matrix, a (H, D) vector and a diag tail block —
independent of how long the request's history is, so admitting a request
into a slot moves a few hundred KB instead of re-paging a full softmax KV
cache.  (Softmax caches work too; they just move O(max_len) bytes.)

The engine is deliberately host-driven between segments (admission needs a
queue, which jit cannot own); everything per-token is inside the scan.

Robustness layer (``docs/serving.md`` "Failure handling" has the lifecycle
diagram; ``tests/test_robustness.py`` proves each path end to end):

* **lifecycle guards** — admission validates every request (rid, prompt
  shape/vocab, budget vs. pool capacity) and rejects with typed
  :class:`AdmissionError`/:class:`QueueFullError` reasons instead of
  crashing mid-scan; per-request ``deadline_s`` budgets are enforced at
  segment boundaries; every request terminates with an explicit status
  (``done | timeout | rejected | failed | retried``) in
  :class:`BatchingStats`;
* **state-health sentinel** — ``segment_fn`` returns a per-row
  ``unhealthy`` flag (``core/health.py``, fused into the decode dispatch).
  A flagged row is QUARANTINED: its segment tokens are discarded (its
  committed prefix stays clean), its slot is evicted, and the request is
  re-queued with exponential backoff.  On re-admission the row is rebuilt
  exactly — re-prefill the original prompt (bitwise-identical calibration)
  then replay the already-emitted tokens through
  ``PoolSetup.replay_fn`` (the partial-commit contract) — so one poisoned
  row costs one slot re-prefill, never the pool;
* **streaming concentration telemetry** — ``segment_fn`` also returns the
  per-row concentration instruments
  (``core/metrics.py:streaming_concentration_tree``: log key mass, its
  per-token drift, log-variance, temperature proxy) computed from the
  carried O(d^2) LLN state inside the same jit; the last segment's
  summary lands in ``BatchingStats.telemetry``, and with
  ``HealthConfig.check_drift`` a drifting row is quarantined through the
  sentinel path above;
* **snapshot/restore** — with a ``snapshot_mgr``
  (``checkpoint/manager.py:CheckpointManager``), the full serving carry
  (pooled caches + tok/pos/remaining/active + the loop PRNG key) plus the
  host metadata (queue, per-row request map, outputs, statuses) is saved
  atomically every ``snapshot_every`` segments; ``run(resume=True)``
  resumes every in-flight request mid-stream after a crash
  (``launch/serve.py --restore``);
* **fault injection** — ``run(fault_plan=...)`` applies a deterministic
  ``launch/faults.py:FaultPlan`` (NaN poison / drop / delay / kill) at
  segment boundaries;
* **straggler watchdog** — each segment's wall clock feeds a
  ``distributed/straggler.py:StepWatchdog`` EWMA; anomalies surface as
  ``StragglerReport`` entries in the final stats.

Tracing: the loop opens ``jax.profiler.TraceAnnotation`` host spans at
segment and admission boundaries only (never per row or per token), so a
profiler trace puts each host gap between device programs on the device's
clock.  A span costs about a microsecond when no profiler runs.

* ``batcher.admit`` — :meth:`ContinuousBatcher._admit_all`; inside it
  ``batcher.prefill`` (args ``prompt_len``, ``rows``: the prefill
  dispatch), ``batcher.first_token`` (the wait for the first tokens),
  ``batcher.scatter`` (``admit_fn`` and the ``tok``/``pos``/``remaining``/
  ``active`` updates) and ``batcher.resume`` (a quarantined row rebuilt);
* ``batcher.segment`` (args ``segment``, ``active_rows``): the key split
  and the ``segment_fn`` dispatch;
* ``batcher.sync``: the host reads of the segment's tokens and flags;
* ``batcher.harvest`` (args ``emitted``: row-steps emitted, ``slot_steps``:
  slots x steps): outputs, quarantine and finishes, eviction excluded;
* ``batcher.evict``: :meth:`ContinuousBatcher._free_rows`;
* ``batcher.deadlines``: the deadline sweep;
* ``batcher.snapshot``: a pool snapshot.

``BatchingStats.times`` stamps each accepted request on
``time.perf_counter()``: ``enqueued``, ``admitted`` (its prefill
dispatched), ``first_token`` (its first token on the host) and
``finished`` (its terminal status set).
"""
from __future__ import annotations

import dataclasses
import json
import time
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.checkpoint.checkpointer import restore as _restore_tree
from repro.distributed.straggler import StepWatchdog
from repro.launch.faults import FaultPlan, SimulatedCrash, poison_rows
from repro.launch.steps import PoolSetup, make_pool_setup


class RequestError(ValueError):
    """Base class for typed request-lifecycle failures."""


class AdmissionError(RequestError):
    """Request failed admission validation (bad rid/prompt/budget)."""


class QueueFullError(RequestError):
    """Admission queue is at ``queue_cap``; request rejected, not queued."""


#: Every request ends in exactly one of these (``BatchingStats.statuses``).
REQUEST_STATUSES = ("done", "timeout", "rejected", "failed", "retried")


@dataclasses.dataclass
class Request:
    """One generation request: ``prompt`` (plen,) int32 token ids and the
    number of tokens to generate (``gen_len`` >= 1; the first generated
    token comes from the prefill's last-position logits).  ``deadline_s``
    is an optional wall-clock budget measured from enqueue and enforced at
    segment boundaries; ``max_tokens`` optionally caps the stored output
    buffer below ``gen_len`` (the effective budget is the min of the
    two)."""
    rid: int
    prompt: np.ndarray
    gen_len: int
    deadline_s: Optional[float] = None
    max_tokens: Optional[int] = None

    @property
    def budget(self) -> int:
        """Effective generation budget: ``min(gen_len, max_tokens)``."""
        if self.max_tokens is None:
            return self.gen_len
        return min(self.gen_len, self.max_tokens)


@dataclasses.dataclass
class BatchingStats:
    """Engine run summary.  ``outputs`` maps rid -> generated tokens
    (length == the request's budget for completed requests; partial for
    timeouts/failures).  ``completed_tokens`` counts tokens of requests
    that finished (status ``done``/``retried`` — the goodput numerator);
    ``decode_steps`` counts scan steps actually dispatched (segments *
    segment length).  ``statuses`` maps every rid to its terminal status
    (one of :data:`REQUEST_STATUSES`); ``reject_reasons`` carries the
    typed-error message for rejected/failed rids.  ``telemetry`` is the
    LAST segment's streaming-concentration summary over live rows
    (``conc_drift_max``/``log_mass_mean``/``log_mass_var_mean``/
    ``tau_hat_mean``) — empty for softmax pools or ``telemetry=False``
    setups.  ``times`` maps each accepted rid to its ``time.perf_counter()``
    stamps: ``enqueued``, ``admitted``, ``first_token``, ``finished`` (a
    stamp is absent where the request never reached that point)."""
    outputs: dict
    completed_tokens: int
    decode_steps: int
    segments: int
    admitted: int
    wall_s: float
    statuses: dict = dataclasses.field(default_factory=dict)
    reject_reasons: dict = dataclasses.field(default_factory=dict)
    recoveries: int = 0
    retries: int = 0
    timeouts: int = 0
    rejected: int = 0
    failed: int = 0
    health_events: list = dataclasses.field(default_factory=list)
    stragglers: list = dataclasses.field(default_factory=list)
    segment_ewma_s: float = 0.0
    snapshots: int = 0
    restored_step: Optional[int] = None
    telemetry: dict = dataclasses.field(default_factory=dict)
    times: dict = dataclasses.field(default_factory=dict)
    # Speculative pools (``PoolSetup.spec_k >= 1``): acceptance-aware
    # goodput.  ``verify_iters`` counts draft+verify iterations that
    # emitted anything; ``drafted_tokens`` = spec_k * verify_iters;
    # ``accepted_tokens`` counts accepted DRAFT tokens (the bonus/resample
    # token each iteration emits is excluded — acceptance_rate is the
    # draft hit rate); ``goodput_tokens_per_iter`` = emitted tokens per
    # verify iteration, in [1, spec_k + 1].
    spec_k: int = 0
    drafted_tokens: int = 0
    accepted_tokens: int = 0
    acceptance_rate: float = 0.0
    verify_iters: int = 0
    goodput_tokens_per_iter: float = 0.0


def synthetic_traffic(n_requests: int, vocab: int, prompt_lens,
                      gen_lens, seed: int = 0) -> list[Request]:
    """Mixed-length synthetic traffic: prompts/gen budgets drawn round-robin
    from the given length menus (deterministic — benchmarks and parity
    tests need identical request streams across engines)."""
    rng = np.random.RandomState(seed)
    reqs = []
    for i in range(n_requests):
        plen = int(prompt_lens[i % len(prompt_lens)])
        glen = int(gen_lens[i % len(gen_lens)])
        prompt = rng.randint(0, vocab, size=(plen,)).astype(np.int32)
        reqs.append(Request(rid=i, prompt=prompt, gen_len=glen))
    return reqs


@dataclasses.dataclass
class _Tracked:
    """Host-side lifecycle record for one accepted request."""
    req: Request
    deadline_at: Optional[float] = None   # absolute time.monotonic() bound
    retries: int = 0
    eligible_seg: int = 0                 # backoff: earliest admit boundary


@dataclasses.dataclass
class _RunState:
    """Everything one :meth:`ContinuousBatcher.run` mutates — bundled so
    the snapshot/restore path serializes ONE object's fields."""
    caches: object = None
    tok: object = None
    pos: object = None
    remaining: object = None
    active: object = None
    key: object = None
    slot_rid: np.ndarray = None
    queue: deque = dataclasses.field(default_factory=deque)
    tracked: dict = dataclasses.field(default_factory=dict)
    outputs: dict = dataclasses.field(default_factory=dict)
    statuses: dict = dataclasses.field(default_factory=dict)
    reject_reasons: dict = dataclasses.field(default_factory=dict)
    health_events: list = dataclasses.field(default_factory=list)
    segments: int = 0
    decode_steps: int = 0
    admitted: int = 0
    recoveries: int = 0
    rejected: int = 0
    snapshots: int = 0
    restored_step: Optional[int] = None
    telemetry: dict = dataclasses.field(default_factory=dict)
    emitted_tokens: int = 0
    verify_iters: int = 0
    accepted_tokens: int = 0
    drafted_tokens: int = 0


class ContinuousBatcher:
    """Drives a ``PoolSetup`` over a queue of :class:`Request`s.

    Typical use (see ``launch/serve.py --continuous`` for the CLI form)::

        setup = make_pool_setup(cfg, mesh, slots=4, max_len=256, segment=8)
        eng = ContinuousBatcher(setup, params)
        stats = eng.run(synthetic_traffic(...))

    ``queue_cap`` bounds the admission queue (excess requests reject with
    status ``rejected`` instead of growing host memory without bound);
    ``max_retries`` bounds quarantine-recovery attempts per request;
    ``snapshot_mgr``/``snapshot_every`` enable pool snapshots (see the
    module docstring).
    """

    def __init__(self, setup: PoolSetup, params, *, queue_cap: int = 1024,
                 max_retries: int = 2, snapshot_mgr=None,
                 snapshot_every: int = 0):
        self.setup = setup
        self.params = params
        self.key = jax.random.PRNGKey(0)
        self.queue_cap = queue_cap
        self.max_retries = max_retries
        self.snapshot_mgr = snapshot_mgr
        self.snapshot_every = snapshot_every
        self._times: dict = {}          # BatchingStats.times of the run
        # Grouped admission (one batched prefill for several same-length
        # queued prompts) is exact whenever prefill is per-row
        # independent: softmax has no calibration, fixed alpha/beta skips
        # moment matching, and per-row calibration
        # (``lln_per_row_calib``, the make_pool_setup default) measures
        # each row's statistics alone — so dynamic moment matching can
        # now use batched slot prefill too.  Only a pool explicitly built
        # with batch-pooled calibration must admit one request at a time.
        cfg = setup.cfg
        self.group_admits = (cfg.attn_impl == "softmax"
                             or cfg.lln_fixed_ab != 0
                             or getattr(cfg, "lln_per_row_calib", False))

    # ------------------------------------------------------------------
    # Validation (the typed-rejection path).
    # ------------------------------------------------------------------

    def check_request(self, req: Request) -> None:
        """Raise :class:`AdmissionError` if the request can never be
        served by this pool (bad rid, malformed prompt, out-of-vocab
        tokens, budget exceeding pool capacity)."""
        s = self.setup
        if req.rid < 0:
            raise AdmissionError(
                f"request rid must be >= 0 (-1 marks a free slot), "
                f"got {req.rid}")
        p = np.asarray(req.prompt)
        if p.ndim != 1 or p.shape[0] < 1:
            raise AdmissionError(
                f"request {req.rid}: prompt must be a non-empty 1-D "
                f"token array, got shape {p.shape}")
        if not np.issubdtype(p.dtype, np.integer):
            raise AdmissionError(
                f"request {req.rid}: prompt dtype {p.dtype} is not "
                "integer token ids")
        vocab = int(getattr(s.cfg, "vocab", 0) or 0)
        if vocab and (int(p.min()) < 0 or int(p.max()) >= vocab):
            raise AdmissionError(
                f"request {req.rid}: token ids outside [0, {vocab})")
        if req.gen_len < 1:
            raise AdmissionError(
                f"request {req.rid}: gen_len must be >= 1, "
                f"got {req.gen_len}")
        if req.max_tokens is not None and req.max_tokens < 1:
            raise AdmissionError(
                f"request {req.rid}: max_tokens must be >= 1, "
                f"got {req.max_tokens}")
        if req.deadline_s is not None and req.deadline_s <= 0:
            raise AdmissionError(
                f"request {req.rid}: deadline_s must be > 0, "
                f"got {req.deadline_s}")
        # Speculative pools reserve ``spec_k`` rows of cache slack: a
        # row's final iteration may commit up to spec_k tokens past its
        # budget, and every score pass needs room for the whole
        # (spec_k + 1)-token chunk before the partial commit rolls the
        # unaccepted suffix back.
        slack = getattr(s, "spec_k", 0)
        if p.shape[0] + req.budget + slack > s.max_len:
            raise AdmissionError(
                f"request {req.rid}: prompt {p.shape[0]} + gen "
                f"{req.budget}" + (f" + spec slack {slack}" if slack else "")
                + f" exceeds max_len {s.max_len}")

    def _enqueue(self, st: _RunState, req: Request) -> bool:
        try:
            self.check_request(req)
            if req.rid in st.tracked or req.rid in st.outputs:
                raise AdmissionError(f"duplicate request rid {req.rid}")
            if len(st.queue) >= self.queue_cap:
                raise QueueFullError(
                    f"request {req.rid}: admission queue at cap "
                    f"{self.queue_cap}")
        except RequestError as e:
            st.rejected += 1
            rid = req.rid
            if rid >= 0 and rid not in st.tracked and rid not in st.outputs:
                st.outputs[rid] = []
                st.statuses[rid] = "rejected"
                st.reject_reasons[rid] = str(e)
            return False
        deadline = (time.monotonic() + req.deadline_s
                    if req.deadline_s is not None else None)
        tr = _Tracked(req=req, deadline_at=deadline)
        st.tracked[req.rid] = tr
        st.outputs[req.rid] = []
        st.queue.append(tr)
        self._stamp(req.rid, "enqueued")
        return True

    def _stamp(self, rid: int, event: str) -> None:
        """Stamp ``event`` of request ``rid`` once: a row rebuilt after
        quarantine keeps its first admission and first token."""
        self._times.setdefault(rid, {}).setdefault(event,
                                                    time.perf_counter())

    # ------------------------------------------------------------------
    # Admission (fresh groups + quarantine-recovery resumes).
    # ------------------------------------------------------------------

    def _admit_all(self, st: _RunState) -> None:
        with TraceAnnotation("batcher.admit"):
            free = list(np.nonzero(st.slot_rid < 0)[0])
            while free:
                idx = next((i for i, tr in enumerate(st.queue)
                            if tr.eligible_seg <= st.segments), None)
                if idx is None:
                    break
                tr = st.queue[idx]
                del st.queue[idx]
                if st.outputs[tr.req.rid]:
                    # Re-queued by quarantine recovery: the request already
                    # holds committed tokens — rebuild its row mid-stream.
                    with TraceAnnotation("batcher.resume"):
                        self._admit_resume(st, tr, int(free.pop(0)))
                    continue
                group = [tr]
                plen = tr.req.prompt.shape[0]
                # Group only CONSECUTIVE eligible fresh same-length prompts
                # (keeps admission order close to FCFS).
                while (self.group_admits and idx < len(st.queue)
                       and len(group) < len(free)):
                    nxt = st.queue[idx]
                    if (nxt.eligible_seg > st.segments
                            or st.outputs[nxt.req.rid]
                            or nxt.req.prompt.shape[0] != plen):
                        break
                    group.append(nxt)
                    del st.queue[idx]
                self._admit_group(st, group, free)

    def _admit_group(self, st: _RunState, group: list, free: list) -> None:
        s = self.setup
        plen = group[0].req.prompt.shape[0]
        for tr in group:
            self._stamp(tr.req.rid, "admitted")
        with TraceAnnotation("batcher.prefill", prompt_len=int(plen),
                             rows=len(group)):
            pf = s.prefill_fn(plen, len(group))
            prompts = jnp.asarray(np.stack([t.req.prompt for t in group]))
            logits, slot_caches = pf(self.params, prompts)
        with TraceAnnotation("batcher.first_token"):
            last = logits[:, -1] if logits.ndim == 3 else logits
            tok0 = np.asarray(jnp.argmax(last, -1).astype(jnp.int32))
        live, live_slots, live_rem = [], [], []
        for j, tr in enumerate(group):
            rid = tr.req.rid
            st.outputs[rid].append(int(tok0[j]))
            st.admitted += 1
            self._stamp(rid, "first_token")
            if tr.req.budget <= 1:          # done at prefill; slot free
                st.statuses[rid] = "done"
                self._stamp(rid, "finished")
                del st.tracked[rid]
                continue
            slot = int(free.pop(0))
            live.append(j)
            live_slots.append(slot)
            live_rem.append(tr.req.budget - 1)
            st.slot_rid[slot] = rid
        if not live:
            return
        with TraceAnnotation("batcher.scatter"):
            if len(live) != len(group):          # drop prefill-only rows
                sel = jnp.asarray(live)
                # Leaves whose rank matches the pooled leaf carry the
                # admit-group axis at position 1; lower-rank leaves
                # (len/pos/alpha/beta) are shared across the group.
                slot_caches = jax.tree_util.tree_map(
                    lambda sl, pl: sl[:, sel] if sl.ndim == pl.ndim
                    else sl, slot_caches, st.caches)
            slots_dev = jnp.asarray(live_slots, jnp.int32)
            st.caches = s.admit_fn(st.caches, slot_caches, slots_dev)
            st.tok = st.tok.at[slots_dev].set(jnp.asarray(tok0[live]))
            st.pos = st.pos.at[slots_dev].set(
                jnp.full((len(live),), plen, jnp.int32))
            st.remaining = st.remaining.at[slots_dev].set(
                jnp.asarray(live_rem, jnp.int32))
            st.active = st.active.at[slots_dev].set(True)

    def _admit_resume(self, st: _RunState, tr: _Tracked, slot: int) -> None:
        """Rebuild a quarantined request's row from its committed tokens:
        re-prefill the ORIGINAL prompt solo (bitwise-identical per-row
        calibration), then replay the emitted tokens minus the last one
        through ``replay_fn`` (partial-commit: every other row's
        ``commit_len`` is 0, so the rest of the pool is untouched).  The
        replayed trajectory IS the original decode trajectory, so the
        rebuilt state is exact under every calibration mode."""
        s = self.setup
        req = tr.req
        emitted = st.outputs[req.rid]
        plen = req.prompt.shape[0]
        n = len(emitted)
        pf = s.prefill_fn(plen, 1)
        _, slot_caches = pf(self.params, jnp.asarray(req.prompt)[None, :])
        slot_dev = jnp.asarray([slot], jnp.int32)
        st.caches = s.admit_fn(st.caches, slot_caches, slot_dev)
        replay = emitted[:-1]
        r_chunk = s.replay_chunk
        for off in range(0, len(replay), r_chunk):
            piece = replay[off:off + r_chunk]
            chunk = np.zeros((s.slots, r_chunk), np.int32)
            chunk[slot, :len(piece)] = piece
            commit = np.zeros((s.slots,), np.int32)
            commit[slot] = len(piece)
            pos_r = st.pos.at[slot].set(plen + off)
            st.caches = s.replay_fn(self.params, st.caches,
                                    jnp.asarray(chunk), pos_r,
                                    jnp.asarray(commit))
        st.tok = st.tok.at[slot].set(int(emitted[-1]))
        st.pos = st.pos.at[slot].set(plen + n - 1)
        left = req.budget - n
        st.remaining = st.remaining.at[slot].set(left)
        st.active = st.active.at[slot].set(left > 0)
        st.slot_rid[slot] = req.rid
        st.recoveries += 1

    # ------------------------------------------------------------------
    # Segment-boundary bookkeeping: harvest, quarantine, deadlines, drops.
    # ------------------------------------------------------------------

    def _free_rows(self, st: _RunState, rows: list) -> None:
        """Deactivate + evict the given pool rows (device side)."""
        s = self.setup
        if not rows:
            return
        with TraceAnnotation("batcher.evict"):
            sel = jnp.asarray(rows, jnp.int32)
            st.active = st.active.at[sel].set(False)
            st.remaining = st.remaining.at[sel].set(0)
            if s.evict_fn is not None:
                mask = np.zeros((s.slots,), np.bool_)
                mask[rows] = True
                st.caches = s.evict_fn(st.caches, jnp.asarray(mask))

    def _quarantine(self, st: _RunState, idx: int) -> None:
        """Sentinel fired on row ``idx``: discard the segment's tokens
        (the committed prefix stays clean), evict the row, and re-queue
        the request with exponential backoff — or fail it once retries
        are exhausted.  A poisoned FREE slot just resets."""
        rid = int(st.slot_rid[idx])
        st.health_events.append(
            {"segment": st.segments - 1, "slot": idx, "rid": rid})
        if rid < 0:
            return
        st.slot_rid[idx] = -1
        tr = st.tracked[rid]
        tr.retries += 1
        if tr.retries > self.max_retries:
            st.statuses[rid] = "failed"
            self._stamp(rid, "finished")
            st.reject_reasons[rid] = (
                f"unhealthy state; {self.max_retries} retries exhausted")
            del st.tracked[rid]
        else:
            tr.eligible_seg = st.segments + (1 << (tr.retries - 1))
            st.queue.append(tr)

    def _harvest(self, st: _RunState, toks_h, emitted_h, active_h,
                 unhealthy_h) -> None:
        """``toks_h``: (S, B, E) token panel, ``emitted_h``: (S, B) int
        per-step emission counts (E = 1 / counts in {0, 1} for plain
        pools; E = spec_k + 1 for speculative pools).  A speculative row
        may emit up to spec_k + 1 tokens in its budget-expiry step, so the
        harvest caps the FLATTENED per-row stream at ``Request.budget`` —
        overshoot tokens are committed on-device (the cache slack
        ``check_request`` reserved) but never surface in ``outputs``."""
        s = self.setup
        freed: list = []
        with TraceAnnotation("batcher.harvest",
                             emitted=int(emitted_h.sum()),
                             slot_steps=int(emitted_h.shape[0]
                                            * emitted_h.shape[1])):
            for idx in range(s.slots):
                if unhealthy_h[idx]:
                    self._quarantine(st, idx)
                    freed.append(idx)
                    continue
                rid = int(st.slot_rid[idx])
                if rid < 0:
                    continue
                tr = st.tracked[rid]
                out = st.outputs[rid]
                room = tr.req.budget - len(out)   # hard buffer bound
                for step in np.nonzero(emitted_h[:, idx])[0]:
                    if room <= 0:
                        break
                    take = toks_h[step, idx,
                                  :int(emitted_h[step, idx])][:room]
                    out.extend(int(t) for t in take)
                    room -= len(take)
                if not active_h[idx]:             # evict: budget exhausted
                    st.statuses[rid] = "retried" if tr.retries else "done"
                    self._stamp(rid, "finished")
                    st.slot_rid[idx] = -1
                    del st.tracked[rid]
                    freed.append(idx)
        self._free_rows(st, freed)

    def _sweep_deadlines(self, st: _RunState) -> None:
        now = time.monotonic()
        expired_rows = []
        for idx in range(self.setup.slots):
            rid = int(st.slot_rid[idx])
            if rid < 0:
                continue
            tr = st.tracked[rid]
            if tr.deadline_at is not None and now >= tr.deadline_at:
                st.statuses[rid] = "timeout"   # partial output kept
                self._stamp(rid, "finished")
                st.slot_rid[idx] = -1
                del st.tracked[rid]
                expired_rows.append(idx)
        self._free_rows(st, expired_rows)
        for tr in [t for t in st.queue
                   if t.deadline_at is not None
                   and now >= t.deadline_at]:
            st.queue.remove(tr)
            st.statuses[tr.req.rid] = "timeout"
            self._stamp(tr.req.rid, "finished")
            del st.tracked[tr.req.rid]

    def _drop(self, st: _RunState, rid: int) -> None:
        """Client-cancel (``drop`` fault): terminate ``rid`` wherever it
        is — queued or slot-resident — with status ``failed``."""
        if rid in st.tracked:
            tr = st.tracked[rid]
            if tr in st.queue:
                st.queue.remove(tr)
            st.statuses[rid] = "failed"
            st.reject_reasons[rid] = "dropped by client"
            self._stamp(rid, "finished")
            del st.tracked[rid]
        rows = [i for i in range(self.setup.slots)
                if int(st.slot_rid[i]) == rid]
        for i in rows:
            st.slot_rid[i] = -1
        self._free_rows(st, rows)

    def _fire_faults(self, st: _RunState, plan: Optional[FaultPlan],
                     fired: set, kinds: tuple) -> None:
        if plan is None:
            return
        for i, ev in enumerate(plan.events):
            if i in fired or ev.kind not in kinds \
                    or ev.segment > st.segments:
                continue
            fired.add(i)
            if ev.kind == "kill":
                raise SimulatedCrash(st.segments)
            if ev.kind == "drop":
                self._drop(st, ev.rid)
            elif ev.kind == "delay":
                time.sleep(ev.seconds)
            elif ev.kind == "nan":
                row = plan.pick_row(ev, self.setup.slots,
                                    active=st.slot_rid >= 0)
                st.caches = poison_rows(st.caches, [row])

    # ------------------------------------------------------------------
    # Snapshot / restore.
    # ------------------------------------------------------------------

    @staticmethod
    def _ser_tracked(tr: _Tracked, now: float) -> dict:
        return {"rid": tr.req.rid,
                "prompt": np.asarray(tr.req.prompt).tolist(),
                "gen_len": tr.req.gen_len,
                "max_tokens": tr.req.max_tokens,
                "deadline_left": (tr.deadline_at - now
                                  if tr.deadline_at is not None else None),
                "retries": tr.retries,
                "eligible_seg": tr.eligible_seg}

    @staticmethod
    def _deser_tracked(entry: dict, now: float) -> _Tracked:
        req = Request(rid=int(entry["rid"]),
                      prompt=np.asarray(entry["prompt"], np.int32),
                      gen_len=int(entry["gen_len"]),
                      max_tokens=entry.get("max_tokens"))
        left = entry.get("deadline_left")
        return _Tracked(req=req,
                        deadline_at=(now + left if left is not None
                                     else None),
                        retries=int(entry.get("retries", 0)),
                        eligible_seg=int(entry.get("eligible_seg", 0)))

    def _snapshot(self, st: _RunState) -> None:
        """Atomic pool snapshot: device carry through the checkpointer
        (CRC-verified shards) + the host metadata as a JSON sidecar in the
        SAME committed step dir — restore sees both or neither."""
        now = time.monotonic()
        tree = {"caches": st.caches, "tok": st.tok, "pos": st.pos,
                "remaining": st.remaining, "active": st.active,
                "key": st.key}
        queued_rids = [tr.req.rid for tr in st.queue]
        meta = {
            "slot_rid": [int(r) for r in st.slot_rid],
            "segments": st.segments, "decode_steps": st.decode_steps,
            "admitted": st.admitted, "recoveries": st.recoveries,
            "rejected": st.rejected, "snapshots": st.snapshots,
            "emitted_tokens": st.emitted_tokens,
            "verify_iters": st.verify_iters,
            "accepted_tokens": st.accepted_tokens,
            "drafted_tokens": st.drafted_tokens,
            "queue": [self._ser_tracked(tr, now) for tr in st.queue],
            "resident": [self._ser_tracked(tr, now)
                         for rid, tr in st.tracked.items()
                         if rid not in queued_rids],
            "outputs": {str(r): list(t) for r, t in st.outputs.items()},
            "statuses": {str(r): v for r, v in st.statuses.items()},
            "reject_reasons": {str(r): v
                               for r, v in st.reject_reasons.items()},
            "health_events": st.health_events,
        }
        self.snapshot_mgr.save_now(st.segments, tree,
                                   extra={"batcher.json": json.dumps(meta)})
        st.snapshots += 1

    def _restore(self, st: _RunState) -> None:
        if self.snapshot_mgr is None:
            raise RuntimeError("resume=True requires a snapshot_mgr")
        step = self.snapshot_mgr.latest_step()
        if step is None:
            raise RuntimeError(
                f"resume=True but no restorable snapshot in "
                f"{self.snapshot_mgr.directory}")
        s = self.setup
        template = {"caches": s.cache_init(),
                    "tok": jnp.zeros((s.slots,), jnp.int32),
                    "pos": jnp.zeros((s.slots,), jnp.int32),
                    "remaining": jnp.zeros((s.slots,), jnp.int32),
                    "active": jnp.zeros((s.slots,), jnp.bool_),
                    "key": jax.random.PRNGKey(0)}
        tree = _restore_tree(self.snapshot_mgr.directory, step, template)
        meta = json.loads(
            self.snapshot_mgr.read_extra(step, "batcher.json"))
        st.caches, st.tok, st.pos = tree["caches"], tree["tok"], tree["pos"]
        st.remaining, st.active = tree["remaining"], tree["active"]
        st.key = tree["key"]
        st.slot_rid = np.asarray(meta["slot_rid"], np.int64)
        st.segments = int(meta["segments"])
        st.decode_steps = int(meta["decode_steps"])
        st.admitted = int(meta["admitted"])
        st.recoveries = int(meta["recoveries"])
        st.rejected = int(meta["rejected"])
        st.snapshots = int(meta["snapshots"])
        st.emitted_tokens = int(meta.get("emitted_tokens", 0))
        st.verify_iters = int(meta.get("verify_iters", 0))
        st.accepted_tokens = int(meta.get("accepted_tokens", 0))
        st.drafted_tokens = int(meta.get("drafted_tokens", 0))
        st.health_events = list(meta["health_events"])
        st.outputs = {int(r): list(t) for r, t in meta["outputs"].items()}
        st.statuses = {int(r): v for r, v in meta["statuses"].items()}
        st.reject_reasons = {int(r): v
                             for r, v in meta["reject_reasons"].items()}
        now = time.monotonic()
        for entry in meta["queue"]:
            tr = self._deser_tracked(entry, now)
            st.tracked[tr.req.rid] = tr
            st.queue.append(tr)
        for entry in meta["resident"]:
            tr = self._deser_tracked(entry, now)
            st.tracked[tr.req.rid] = tr
        st.restored_step = step

    # ------------------------------------------------------------------
    # The serving loop.
    # ------------------------------------------------------------------

    def warmup(self, prompt_lens) -> None:
        """Compile every (prompt length, admit-group size) prefill, the
        admit scatters and the segment scan so a timed :meth:`run` measures
        steady state, not compiles."""
        s = self.setup
        plens = list(dict.fromkeys(int(p) for p in prompt_lens))
        sizes = range(1, s.slots + 1) if self.group_admits else (1,)
        pooled = s.cache_init()
        for p in plens:
            for k in sizes:       # mid-stream admits form every group size
                _, sc = s.prefill_fn(p, k)(self.params,
                                           jnp.zeros((k, p), jnp.int32))
                pooled = s.admit_fn(pooled, sc,
                                    jnp.arange(k, dtype=jnp.int32))
        del pooled
        # One tiny end-to-end pass for the segment scan + harvest path;
        # generation budgets are clamped to the pool's max_len.  Snapshots
        # are disabled for the warmup run — it is not real traffic.
        slack = getattr(s, "spec_k", 0)
        dummy = [Request(rid=i, prompt=np.zeros((p,), np.int32),
                         gen_len=max(1, min(s.segment + 1,
                                            s.max_len - p - slack)))
                 for i, p in enumerate(plens)]
        every, self.snapshot_every = self.snapshot_every, 0
        try:
            self.run(dummy)
        finally:
            self.snapshot_every = every

    def run(self, requests, key: Optional[jax.Array] = None,
            fault_plan: Optional[FaultPlan] = None,
            resume: bool = False) -> BatchingStats:
        """Serve ``requests`` to completion.  ``fault_plan`` injects
        scripted failures at segment boundaries; ``resume=True`` first
        restores the pool from the latest snapshot (a ``kill`` fault /
        crash mid-run) and finishes every in-flight request, then serves
        ``requests`` on top (pass ``[]`` to just drain)."""
        s = self.setup
        st = _RunState()
        self._times = {}
        if resume:
            self._restore(st)
        else:
            st.caches = s.cache_init()
            st.tok = jnp.zeros((s.slots,), jnp.int32)
            st.pos = jnp.zeros((s.slots,), jnp.int32)
            st.remaining = jnp.zeros((s.slots,), jnp.int32)
            st.active = jnp.zeros((s.slots,), jnp.bool_)
            st.slot_rid = np.full((s.slots,), -1, np.int64)
            if key is None:   # advance so repeated runs sample fresh streams
                self.key, key = jax.random.split(self.key)
            st.key = key
        for r in requests:
            self._enqueue(st, r)

        wd = StepWatchdog()
        fired: set = set()
        last_metrics = None     # device telemetry of the last live segment
        t0 = time.perf_counter()
        while st.queue or (st.slot_rid >= 0).any():
            # Kills/drops fire at the boundary, before admission — a
            # restore replays the admissions deterministically.
            self._fire_faults(st, fault_plan, fired, ("kill", "drop"))
            self._admit_all(st)
            if (st.slot_rid < 0).all():
                if st.queue:
                    # Every queued request is backoff-deferred: advance
                    # the boundary clock so eligibility can arrive.
                    st.segments += 1
                    continue
                break                         # all admits finished early

            # --- one scanned decode segment -----------------------------
            wd.start()
            self._fire_faults(st, fault_plan, fired, ("delay", "nan"))
            with TraceAnnotation("batcher.segment", segment=st.segments,
                                 active_rows=int((st.slot_rid >= 0).sum())):
                st.key, seg_key = jax.random.split(st.key)
                (st.caches, st.tok, st.pos, st.remaining, st.active,
                 toks, emitted, unhealthy, metrics) = s.segment_fn(
                    self.params, st.caches, st.tok, st.pos, st.remaining,
                    st.active, seg_key)
            # Host syncs land inside the watchdog window so the EWMA sees
            # the real segment wall clock, not async-dispatch latency.
            # Normalize the two segment shapes to one panel: plain pools
            # emit (S, B) tokens with bool masks -> (S, B, 1) + {0, 1}
            # counts; speculative pools emit (S, B, k+1) + int counts.
            with TraceAnnotation("batcher.sync"):
                toks_h = np.asarray(toks)
                emitted_h = np.asarray(emitted).astype(np.int64)
                if toks_h.ndim == 2:
                    toks_h = toks_h[..., None]
                active_h = np.asarray(st.active)
                unhealthy_h = np.asarray(unhealthy)
                wd.stop(st.segments)
                st.segments += 1
                st.decode_steps += s.segment
                st.emitted_tokens += int(emitted_h.sum())
                spec_k = getattr(s, "spec_k", 0)
                if spec_k:
                    iters = int((emitted_h > 0).sum())
                    st.verify_iters += iters
                    st.drafted_tokens += spec_k * iters
                    st.accepted_tokens += int(
                        np.maximum(emitted_h - 1, 0).sum())
                live = emitted_h.any(axis=0)      # rows that decoded here
                if metrics is not None and live.any():
                    last_metrics = (metrics, live)

            # --- harvest / quarantine / deadlines / snapshot ------------
            self._harvest(st, toks_h, emitted_h, active_h, unhealthy_h)
            with TraceAnnotation("batcher.deadlines"):
                self._sweep_deadlines(st)
            if (self.snapshot_mgr is not None and self.snapshot_every
                    and st.segments % self.snapshot_every == 0):
                with TraceAnnotation("batcher.snapshot"):
                    self._snapshot(st)
        wall = time.perf_counter() - t0
        if last_metrics is not None:
            # Only the last live segment's telemetry is reported, so it is
            # fetched once, after the loop, not after every segment.
            metrics, live = last_metrics
            m = {k: np.asarray(v)[live] for k, v in metrics.items()}
            st.telemetry = {
                "conc_drift_max": float(np.max(np.abs(m["conc_drift"]))),
                "log_mass_mean": float(np.mean(m["log_mass"])),
                "log_mass_var_mean": float(np.mean(m["log_mass_var"])),
                "tau_hat_mean": float(np.mean(m["tau_hat"]))}

        outputs = {rid: np.asarray(t, np.int32)
                   for rid, t in st.outputs.items()}
        done = sum(len(outputs[rid]) for rid, v in st.statuses.items()
                   if v in ("done", "retried"))
        by = {k: sum(1 for v in st.statuses.values() if v == k)
              for k in REQUEST_STATUSES}
        return BatchingStats(
            outputs=outputs, completed_tokens=done,
            decode_steps=st.decode_steps, segments=st.segments,
            admitted=st.admitted, wall_s=wall,
            statuses=dict(st.statuses),
            reject_reasons=dict(st.reject_reasons),
            recoveries=st.recoveries, retries=by["retried"],
            timeouts=by["timeout"], rejected=st.rejected,
            failed=by["failed"],
            health_events=list(st.health_events),
            stragglers=list(wd.anomalies),
            segment_ewma_s=wd.ewma or 0.0,
            snapshots=st.snapshots, restored_step=st.restored_step,
            telemetry=dict(st.telemetry),
            times=self._times,
            spec_k=getattr(s, "spec_k", 0),
            drafted_tokens=st.drafted_tokens,
            accepted_tokens=st.accepted_tokens,
            acceptance_rate=(st.accepted_tokens / st.drafted_tokens
                             if st.drafted_tokens else 0.0),
            verify_iters=st.verify_iters,
            goodput_tokens_per_iter=(st.emitted_tokens / st.verify_iters
                                     if st.verify_iters else 0.0))


__all__ = ["Request", "BatchingStats", "ContinuousBatcher",
           "RequestError", "AdmissionError", "QueueFullError",
           "REQUEST_STATUSES", "synthetic_traffic", "make_pool_setup",
           "PoolSetup"]
