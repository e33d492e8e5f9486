"""Ragged continuous-batching decode engine.

The integrated runtime's "task inference" rounds (paper §IV) are throughput
bound: a round's profit is booked per served request, so requests must keep
the accelerator full under realistic edge traffic — heterogeneous prompt
lengths and token budgets from many tenants — not just equal-shaped waves.
This engine is the serving layer between a request queue and the fused
ragged-wave primitives in :mod:`repro.models.model`.

**Ragged wave lifecycle** (one ``run()`` drain):

1. **Pack** — free slots are filled from the queue FIFO, with NO length
   bucketing: one wave freely mixes prompt lengths, token budgets, and
   (against an AdapterBank) tenant domains. Prompts are right-padded to
   the pack's max length (bucketed to the next power of two so the jit
   cache stays O(log max_len)).
2. **Prefill** — one jitted dispatch builds every packed row's decode
   state with per-row cache positions (``model._wave_prefill_fn``). The
   cache capacity is sized once per drain to the largest
   ``prompt + budget`` in the queue.
3. **Decode segments** — generation runs as a sequence of jitted
   ``lax.scan`` segments (``model._segment_fn``). Each segment's length is
   the power-of-two floor of the smallest remaining budget among live
   rows, so segments are never longer than the next retirement and the
   set of compiled segment shapes is {1, 2, 4, ...} — the jit cache stops
   growing no matter how budgets mix.
4. **Retire + refill IN-WAVE** — a row that exhausts its budget retires
   inside the scan (per-row active mask: cache writes dropped, position
   frozen). At the next segment boundary the freed slot is re-prefilled
   from the queue (``model._refill_fn`` merges fresh cache rows into the
   live wave state) — true continuous batching: the wave never drains to
   a boundary just to admit new work.
5. **Account** — ``EngineStats.tokens`` counts served (budget) tokens;
   ``EngineStats.padded_tokens`` counts wasted slot-steps (retired or
   empty slots riding along in a segment), so ``utilization`` is the real
   accelerator efficiency, not just the served-token rate.

Every drain is token-for-token identical to serving each request alone:
per-row cache positions + sentinel masking keep rows independent in
attention, and the recurrent families freeze padded state
identity-exactly (see ``stack_seq(lengths=...)``).

Modality-conditioned requests (vision/audio extras) carry their extras row
with the request (``submit(..., extras={...})``); refills rebuild the wave
extras so each slot stays bound to its own conditioning. Every request in
one drain must agree on the extras keys (or carry none).

**Multi-tenant serving**: constructed with an
:class:`~repro.core.adapter_bank.AdapterBank`, requests gain a ``domain``
field and one wave freely mixes domains — each row's bank slot id rides
the wave as per-row ``adapter_ids`` into the batched multi-LoRA kernels.
``bank.stacked`` is re-read at every prefill/refill/segment dispatch, so
an ``AdapterBank.publish`` between drains (or between segments) is served
by the very next dispatch.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import telemetry
from repro.core.paged import BlockAllocator, PagedSpec
from repro.core.telemetry import Histogram, Telemetry
from repro.models import model as M
from repro.models.transformer import groups_for, paged_subs
from repro.sharding.rules import init_from_spec


def _pow2ceil(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def _pow2floor(n: int) -> int:
    return 1 << (int(n).bit_length() - 1)


def _uids(packed) -> list[int]:
    """The uids of packed ``(slot, request)`` pairs, for a span's args."""
    return [req.uid for _, req in packed]


@dataclasses.dataclass
class Request:
    uid: int
    tokens: np.ndarray                 # (S,) int32 prompt
    max_new_tokens: int
    extras: Optional[dict] = None      # per-request modality rows (no batch dim)
    domain: Optional[str] = None       # multi-tenant: AdapterBank slot owner
    deadline_s: Optional[float] = None  # monotonic budget from submit time
    # deadline / latency anchor: time.perf_counter() at submit. MONOTONIC
    # by contract — a wall-clock step (NTP slew, manual set) must never
    # spuriously retire a request as timed_out or corrupt its latency
    t_submit: float = 0.0
    # lifecycle start (perf_counter): when the request was due — its
    # arrival time under serve_trace, else its submit time
    t_due: float = 0.0
    speculative: bool = True           # opt this row out of spec drafting
                                       # (it then decodes plainly THROUGH
                                       # the verify pass — mixed waves)
    t_submit_wall: float = 0.0         # informational ONLY (never compared)
    sla: Optional[str] = None          # service class label: per-class
                                       # TTFT/queue histograms + deadline-
                                       # miss counters (EngineStats.sla_stats)


@dataclasses.dataclass
class Slot:
    """One fixed batch slot; live fields track the resident request."""
    uid: int = -1
    prompt_len: int = 0
    target: int = 0                    # requested new tokens
    active: bool = False

    def assign(self, req: Request) -> None:
        self.uid, self.prompt_len = req.uid, len(req.tokens)
        self.target = req.max_new_tokens
        self.active = True

    def recycle(self) -> None:
        self.uid, self.prompt_len, self.target = -1, 0, 0
        self.active = False


@dataclasses.dataclass
class Completion:
    uid: int
    tokens: np.ndarray                 # (max_new_tokens,) generated tokens
    latency_s: float                   # submit -> retirement (monotonic)
    wave: int                          # prefill wave that admitted the row
    timed_out: bool = False            # retired at its deadline (partial tokens)
    queue_s: float = 0.0               # submit -> wave admission (queue wait)
    ttft_s: Optional[float] = None     # submit -> first token host-visible
                                       # (None: retired before any token)
    tok_s: float = 0.0                 # tokens / (admission -> retirement)


@dataclasses.dataclass
class EngineStats:
    requests: int = 0
    waves: int = 0                     # prefill/refill dispatches
    segments: int = 0                  # jitted decode-scan dispatches
    tokens: int = 0                    # served (budgeted) tokens
    padded_tokens: int = 0             # wasted slot-steps (retired/empty rows)
    timed_out: int = 0                 # requests retired at their deadline
    wall_s: float = 0.0
    drafted: int = 0                   # drafter-proposed tokens (spec serving)
    accepted: int = 0                  # proposals the verify pass committed
    # per-request latency distributions, summarized from log-bucketed
    # histograms (core/telemetry.py::Histogram.summary: count/mean/p50/
    # p95/p99) — always recorded (a handful of perf_counter reads per
    # dispatch), independent of whether global telemetry is enabled; the
    # queue wait is telemetry's ``engine.queue_s``
    ttft_hist: Optional[dict] = None       # time-to-first-token (s)
    tok_latency_hist: Optional[dict] = None  # per-token decode latency (s)
    # SLA classes (submit(sla=...)): per-class latency distributions +
    # deadline misses — {cls: {ttft_hist, queue_hist, deadline_miss,
    # requests}}. None when no request carried a class label.
    sla_stats: Optional[dict] = None
    # paged serving (DecodeEngine(paged=PagedSpec(...))):
    pool_block_size: int = 0           # tokens per pool block (0 = dense)
    pool_peak_blocks: int = 0          # max simultaneously-referenced blocks
    pool_blocks_alloc: int = 0         # private blocks allocated this drain
    cache_tokens: int = 0              # prompt+budget tokens placed in NEW
                                       # blocks (shared prefixes counted once)
    prefix_hits: int = 0               # admissions that matched a cached prefix
    prefix_hit_tokens: int = 0         # prompt tokens served from shared blocks

    @property
    def pool_occupancy(self) -> float:
        """Paged: useful tokens per allocated pool-block token. Blocks are
        sized per request (ceil over block_size), so this dominates the
        dense-slab utilization sum(len+gen)/(N*cap) — the slab pads every
        row to the drain-wide pow2 cap."""
        denom = self.pool_blocks_alloc * self.pool_block_size
        return self.cache_tokens / denom if denom else 0.0

    @property
    def tok_per_s(self) -> float:
        return self.tokens / self.wall_s if self.wall_s > 0 else 0.0

    @property
    def acceptance_rate(self) -> float:
        """Committed fraction of drafted tokens (speculative serving)."""
        return self.accepted / self.drafted if self.drafted else 0.0

    @property
    def utilization(self) -> float:
        """Served fraction of executed decode slot-steps (1.0 = no waste;
        same convention as RoundCost.utilization)."""
        total = self.tokens + self.padded_tokens
        return self.tokens / total if total else 1.0


class DecodeEngine:
    """Packs queued requests into fixed slots and serves them ragged."""

    def __init__(self, cfg, *, slots: int = 8, greedy: bool = True,
                 seed: int = 0, bank=None, mesh=None, spec=None,
                 tel: Optional[Telemetry] = None,
                 paged: Optional[PagedSpec] = None):
        self.cfg = cfg
        self.slots = slots
        self.greedy = greedy
        self.bank = bank                   # Optional[AdapterBank]: multi-tenant
        # telemetry: spans/counters go to `tel` if given, else to the
        # module singleton resolved at CALL time (so telemetry.enable()
        # after construction still instruments this engine). Per-request
        # latency histograms in EngineStats are recorded regardless.
        self.tel = tel
        # speculative serving: with a core.spec_decode.SpecDecoder, decode
        # segments run draft->verify chunks (k proposals + ONE batched
        # verify pass) instead of plain per-token scans. Greedy-only:
        # acceptance is exact-match against the target argmax, which is
        # what makes spec drains token-identical to plain ones. Rows
        # submitted with speculative=False decode plainly THROUGH the
        # verify pass (commit=1/chunk), so one wave freely mixes both.
        self.spec = spec
        if spec is not None:
            if not greedy:
                raise ValueError(
                    "speculative serving is greedy-only (sampled residual "
                    "acceptance is a recorded follow-up)")
            spec.validate_target(cfg)
            if mesh is not None:
                self.spec = spec.place(mesh)
        # mesh-native waves: every fused dispatch (wave prefill / in-wave
        # refill / decode segment) traces under rules.serving_rules(), so
        # the wave batch shards over `data` and head/FF dims over `model`.
        # Params must already live on the mesh (model.place_params /
        # AdapterBank(mesh=...)); drains stay token-identical to unsharded
        # serving (see tests/test_mesh_sharding.py).
        self.mesh = mesh
        # paged serving: the per-slot dense cache slab is replaced by a
        # device block pool + per-row block tables (models/attention.py)
        # and this HOST-side refcounted allocator (core/paged.py). The
        # pool and allocator persist ACROSS drains — freed blocks keep
        # their prefix hash on the LRU free list, so a later drain's
        # matching prompt revives them without re-prefilling.
        self.paged = paged
        self._pool: Optional[dict] = None
        self._alloc: Optional[BlockAllocator] = None
        self._psubs: list[tuple[str, str]] = []
        self._slot_blocks: list[Optional[dict]] = [None] * slots
        self._arrivals: deque = deque()    # serve_trace timed admissions
        self._trace_t0 = 0.0
        if paged is not None:
            if spec is not None:
                raise ValueError(
                    "paged serving composes with plain decode only "
                    "(speculative verify reads the dense slot layout; "
                    "paged verify is a recorded follow-up)")
            if cfg.family in ("audio", "vlm"):
                raise ValueError(
                    f"paged serving does not support the {cfg.family} "
                    "family (modality prefixes address the dense slab)")
            self._psubs = paged_subs(cfg)
            if paged.share_prefix:
                n_subs = sum(len(kinds) for _, kinds, _ in groups_for(cfg))
                if len(self._psubs) != n_subs or not self._psubs:
                    raise ValueError(
                        "share_prefix requires a fully paged stack (every "
                        "sub-layer full-window attention/moe): suffix-only "
                        "prefill has no partial-stack path")
            self._alloc = BlockAllocator(paged.n_blocks, paged.block_size)
        self.slot_table = [Slot() for _ in range(slots)]
        self._queue: deque[Request] = deque()
        self._uid = 0
        self._key = jax.random.PRNGKey(seed)

    # -- queue --------------------------------------------------------------
    def submit(self, tokens, max_new_tokens: int = 8,
               extras: Optional[dict] = None,
               domain: Optional[str] = None,
               deadline_s: Optional[float] = None,
               speculative: bool = True,
               sla: Optional[str] = None) -> int:
        """Enqueue one request; returns its uid. ``extras`` is one modality
        row per key (e.g. ``{"vision_embeds": (n_vis, d)}`` — no batch dim);
        it stays bound to this request across wave packing. ``domain`` names
        this request's adapter slot in the engine's AdapterBank (multi-tenant
        serving); it too stays bound across packing. ``deadline_s`` is a
        wall-clock budget from NOW: a row still live past it is retired
        mid-wave as a ``timed_out`` completion with its partial tokens.
        ``speculative=False`` opts this row out of drafting on a spec
        engine (it decodes plainly through the verify pass; ignored on
        plain engines). ``sla`` labels this request's service class:
        TTFT/queue-wait land in per-class histograms and a deadline
        retirement books a per-class miss (``EngineStats.sla_stats``,
        ``engine.deadline_miss.<cls>`` counters).

        Malformed requests fail HERE with ``ValueError`` — an empty or
        non-1-D prompt, a non-positive token budget, or an unknown domain
        would otherwise surface as a shape error (or a silent stall) deep
        inside a traced wave."""
        tokens = np.asarray(tokens, np.int32)
        if tokens.ndim != 1 or tokens.size == 0:
            raise ValueError(
                f"submit: prompt must be a non-empty 1-D token row, got "
                f"shape {tokens.shape}")
        if int(max_new_tokens) < 1:
            raise ValueError(
                f"submit: max_new_tokens must be >= 1, got {max_new_tokens}")
        if deadline_s is not None and deadline_s < 0:
            raise ValueError(
                f"submit: deadline_s must be >= 0, got {deadline_s}")
        if self.paged is not None:
            need = -(-(tokens.size + int(max_new_tokens))
                     // self.paged.block_size)
            if need > self.paged.n_blocks:
                raise ValueError(
                    f"submit: request needs {need} pool blocks but the "
                    f"pool only has {self.paged.n_blocks} — it could "
                    "never be admitted")
        if domain is not None:
            if self.bank is None:
                raise ValueError("submit(domain=...) requires an engine "
                                 "constructed with an AdapterBank")
            if domain not in self.bank.domains:  # fail fast on unknown domains
                raise ValueError(
                    f"domain {domain!r} has no adapter slot "
                    f"(known: {list(self.bank.domains)})")
        # enforce the all-or-none tenancy invariant at the door (rejecting
        # the offending request, not poisoning the queue): a mixed drain
        # would otherwise surface as a shape error deep inside the
        # projection kernels (stacked adapter leaves served without
        # adapter_ids).
        if self._queue and (domain is None) != (self._queue[0].domain is None):
            raise ValueError("all requests in a drain must carry a domain "
                             "or none (mixing tenant-addressed and "
                             "merged-param requests is ambiguous)")
        uid = self._uid
        self._uid += 1
        now = time.perf_counter()
        self._queue.append(Request(uid, tokens, int(max_new_tokens), extras,
                                   domain, deadline_s, now, now,
                                   bool(speculative),
                                   time.time(), sla))    # tracelint: ignore[R3] t_submit_wall is informational
        self._telemetry().count("engine.submitted")
        return uid

    def _telemetry(self) -> Telemetry:
        return self.tel if self.tel is not None else telemetry.get()

    def pending(self) -> int:
        return len(self._queue)

    # -- packing ------------------------------------------------------------
    def _fill_slots(self) -> list[tuple[int, Request]]:
        """Assign queued requests to free slots FIFO (no length bucketing).
        Returns [(slot_index, request)] for the rows to (re-)prefill.

        Paged admission is block-gated: the head request's pool blocks
        (shared prefix refs + private blocks for prompt tail and budget)
        must all be reservable NOW, else packing stops head-of-line — a
        later retirement frees blocks and the next segment boundary
        retries. FIFO order is preserved either way."""
        packed: list[tuple[int, Request]] = []
        for i, slot in enumerate(self.slot_table):
            if slot.active or not self._queue:
                continue
            if self.paged is not None:
                plan = self._plan_blocks(self._queue[0])
                if plan is None:
                    break                     # pool full: wait for a retire
                self._slot_blocks[i] = plan
            req = self._queue.popleft()
            slot.assign(req)
            packed.append((i, req))
        return packed

    def _plan_blocks(self, req: Request) -> Optional[dict]:
        """Reserve one request's pool blocks, or None if they don't fit.

        ``shared`` are prefix-cache hits (acquired, never written:
        copy-on-write by construction); ``owned`` are freshly allocated
        private blocks covering the prompt tail + decode budget. The
        match is capped at (len-1)//bs blocks so every row keeps at
        least one private suffix token — the suffix pass needs a token
        to produce the row's first logits from."""
        ps, alloc = self.paged, self._alloc
        total = -(-(len(req.tokens) + req.max_new_tokens) // ps.block_size)
        shared: list[int] = []
        if ps.share_prefix:
            ids, _ = alloc.match_prefix(req.tokens)
            shared = ids[:min(len(ids), (len(req.tokens) - 1)
                              // ps.block_size)]
        need = total - len(shared)
        # reviving a dead (rc==0) shared block consumes a free-list slot
        # too, so feasibility is checked BEFORE touching refcounts
        revive = sum(1 for b in shared if alloc.refcount[b] == 0)
        if need + revive > alloc.free_blocks:
            return None
        for b in shared:
            alloc.acquire(b)
        owned = alloc.alloc(need) if need else []
        # publish full-prefill rows' prompt blocks AT PLAN TIME so a
        # same-wave sibling already matches them (its suffix dispatch
        # consumes the prefill's output pool — device data dependence
        # orders the commit before any shared read). HIT rows stay
        # private: their suffix K/V is chunk-pass math, not bitwise
        # dense-prefill state.
        if ps.share_prefix and not shared:
            alloc.register(req.tokens, owned)
        return {"owned": owned, "shared": shared}

    def _ensure_pool(self) -> None:
        """Materialize the persistent device block pool (zeros) lazily —
        one (L, n_blocks, bs, Hkv, D) k/v pair per eligible sub-layer,
        shared by every drain this engine ever runs."""
        if self._pool is not None:
            return
        ps = self.paged
        spec = M.cache_spec(self.cfg, 1, ps.block_size,
                            paged=(ps.n_blocks, ps.block_size))
        pool: dict = {}
        for g, s in self._psubs:
            sub = spec[g][s]
            pool.setdefault(g, {})[s] = init_from_spec(
                jax.random.PRNGKey(0), {"k": sub["k"], "v": sub["v"]})
        self._pool = pool

    def _admit_due(self) -> None:
        """serve_trace: submit every arrival whose timestamp has passed. The
        request keeps its due time as its lifecycle start; how late this
        sweep took it in is ``engine.admit_lag_s``."""
        tel = self._telemetry()
        while self._arrivals and \
                time.perf_counter() - self._trace_t0 >= self._arrivals[0][0]:
            t, tokens, gen, kw = self._arrivals.popleft()
            self.submit(tokens, gen, **kw)
            req = self._queue[-1]              # the request just submitted
            req.t_due = self._trace_t0 + t
            tel.observe("engine.admit_lag_s", req.t_submit - req.t_due)

    def _check_extras(self) -> frozenset:
        """Validate the all-or-none extras-keys invariant across the drain."""
        keys = {k for r in self._queue if r.extras for k in r.extras}
        if keys and any(r.extras is None or set(r.extras) != keys
                        for r in self._queue):
            raise ValueError("all requests in a drain must carry the same "
                             f"extras keys ({sorted(keys)}) or none")
        return frozenset(keys)

    def _wave_params(self, params, tenant: bool):
        """Per-dispatch params: re-read the bank so publishes are fresh."""
        return params if not tenant else \
            {**params, "adapters": self.bank.stacked}

    # -- serving ------------------------------------------------------------
    # tracelint: hot
    def run(self, params) -> tuple[list[Completion], EngineStats]:
        """Drain the queue as ONE ragged continuous-batching wave.

        Returns (completions, stats). See the module docstring for the
        wave lifecycle; the drain is token-for-token identical to serving
        every request alone."""
        stats = EngineStats()
        out: list[Completion] = []
        if not self._queue and not self._arrivals:
            return out, stats
        tel = self._telemetry()
        # drain-local latency histograms: always on (a few clock reads per
        # DISPATCH, never per token), summarized into EngineStats at exit
        h_ttft, h_tok = Histogram(), Histogram()
        # per-SLA-class distributions (submit(sla=...)): lazily created
        # {cls: {"ttft": Histogram, "queue": Histogram, "miss": n, "n": n}}
        sla_acc: dict[str, dict] = {}
        t_all = time.perf_counter()
        extras_keys = self._check_extras()
        tenant = bool(self._queue) and self._queue[0].domain is not None
        # cache capacity: one size per drain keeps every refill shape-stable
        # (timed arrivals not yet submitted count too — they join THIS drain)
        cap = _pow2ceil(max(
            [len(r.tokens) + r.max_new_tokens for r in self._queue]
            + [e[1].size + e[2] for e in self._arrivals]))
        bs_ = nb_ = maxb = 0
        if self.paged is not None:
            bs_, nb_ = self.paged.block_size, self.paged.n_blocks
            cap = max(cap, bs_)            # pow2 cap >= pow2 bs divides evenly
            maxb = cap // bs_
            self._ensure_pool()
            stats.pool_block_size = bs_
        B = self.slots
        slot_req: list[Optional[Request]] = [None] * B
        slot_wave = [0] * B
        bufs: list[list[np.ndarray]] = [[] for _ in range(B)]
        remaining = np.zeros(B, np.int64)
        tok = caches = pos = None
        dtok = dcaches = dpos = None       # drafter wave state (spec serving)
        spec_rows = np.ones(B, bool)       # per-slot speculative opt-in
        ids = None                         # device (B,) adapter slot ids
        cur_extras: list[Optional[dict]] = [None] * B
        cur_dom: list[Optional[str]] = [None] * B
        # per-slot request lifecycle anchors (all monotonic):
        # submit (on the Request) -> admit (wave packing) -> first token
        # host-visible (first segment sync serving the row) -> retire
        t_admit = [0.0] * B
        t_first: list[Optional[float]] = [None] * B

        def retire(i: int, now: float, *, timed_out: bool = False) -> None:
            """Complete slot i's request: latency fields + trace span."""
            req = slot_req[i]
            toks_i = (np.concatenate(bufs[i]) if bufs[i]
                      else np.zeros(0, np.int32))
            ttft = t_first[i] - req.t_submit if t_first[i] is not None \
                else None
            decode_dt = now - t_admit[i]
            out.append(Completion(
                req.uid, toks_i, now - req.t_submit, slot_wave[i],
                timed_out=timed_out, queue_s=t_admit[i] - req.t_submit,
                ttft_s=ttft,
                tok_s=len(toks_i) / decode_dt if decode_dt > 0 else 0.0))
            stats.requests += 1
            if timed_out:
                stats.timed_out += 1
                tel.count("engine.timed_out")
            if ttft is not None:
                h_ttft.record(ttft)
                tel.observe("engine.ttft_s", ttft)
            if req.sla is not None:
                acc = sla_acc.setdefault(
                    req.sla, {"ttft": Histogram(), "queue": Histogram(),
                              "miss": 0, "n": 0})
                acc["n"] += 1
                acc["queue"].record(t_admit[i] - req.t_submit)
                if ttft is not None:
                    acc["ttft"].record(ttft)
                    tel.observe(f"engine.ttft_s.{req.sla}", ttft)
                if timed_out:
                    acc["miss"] += 1
                    tel.count(f"engine.deadline_miss.{req.sla}")
            if self.paged is not None and self._slot_blocks[i] is not None:
                pb = self._slot_blocks[i]
                self._alloc.free(pb["owned"] + pb["shared"])
                self._slot_blocks[i] = None
                tel.gauge("engine.pool_blocks_used", self._alloc.used_blocks)
            tel.count("engine.retired")
            tel.record_span("engine.request", req.t_due, now,
                            uid=req.uid, wave=slot_wave[i],
                            tokens=len(toks_i), domain=req.domain,
                            timed_out=timed_out)
            bufs[i] = []
            remaining[i] = 0
            slot_req[i] = None
            self.slot_table[i].recycle()

        drain = tel.span("engine.drain", slots=B, queued=len(self._queue))
        drain.__enter__()
        while self._queue or remaining.any() or self._arrivals:
            # host work between one sync and the next dispatch is
            # engine.schedule: admission, packing, the deadline sweep and
            # the bookkeeping of served tokens
            with tel.span("engine.schedule"):
                self._admit_due()
                idle = not self._queue and not remaining.any()
                packed = [] if idle else self._fill_slots()
                if packed:
                    stats.waves += 1
                    # a drain admitted entirely from a timed trace learns
                    # its tenancy from the first packed wave (submit()
                    # enforces the all-or-none invariant queue-wide)
                    tenant = packed[0][1].domain is not None
                    t_adm = time.perf_counter()  # queue wait ends: admitted
                    for i, req in packed:
                        slot_req[i], slot_wave[i] = req, stats.waves - 1
                        remaining[i] = req.max_new_tokens
                        cur_extras[i], cur_dom[i] = req.extras, req.domain
                        spec_rows[i] = req.speculative
                        t_admit[i], t_first[i] = t_adm, None
                        tel.observe("engine.queue_s", t_adm - req.t_submit)
                        if self.paged is not None:
                            pb = self._slot_blocks[i]
                            nshared = len(pb["shared"])
                            stats.pool_blocks_alloc += len(pb["owned"])
                            stats.cache_tokens += (len(req.tokens)
                                                   + req.max_new_tokens
                                                   - nshared * bs_)
                            if nshared:
                                stats.prefix_hits += 1
                                stats.prefix_hit_tokens += nshared * bs_
                                tel.count("engine.prefix_hits")
                    if self.paged is not None:
                        stats.pool_peak_blocks = max(stats.pool_peak_blocks,
                                                     self._alloc.used_blocks)
                        tel.gauge("engine.pool_blocks_used",
                                  self._alloc.used_blocks)
                        tel.gauge("engine.pool_blocks_shared",
                                  sum(1 for rc in self._alloc.refcount
                                      if rc > 1))
                    live = [i for i in range(B) if slot_req[i] is not None]
                    if tenant:                     # full-wave ids for segments
                        doms = [cur_dom[i] if cur_dom[i] is not None
                                else cur_dom[live[0]] for i in range(B)]
                        ids = self.bank.adapter_ids(doms)
                    wp = self._wave_params(params, tenant)
                    # right-pad the PACKED prompts to a pow2 width (jit-
                    # shape bucketing both dims keeps the compile cache
                    # O(log² cap))
                    S_pad = _pow2ceil(max(len(req.tokens)
                                          for _, req in packed))
            if idle:
                # arrival-driven drain, nothing live yet: sleep toward the
                # next arrival instead of spinning (capped so a deadline
                # sweep never starves)
                dt = self._trace_t0 + self._arrivals[0][0] \
                    - time.perf_counter()
                if dt > 0:
                    time.sleep(min(dt, 0.025))
                continue
            if packed:
                if self.paged is not None:
                    # paged waves: dense-prefill the packed rows, then
                    # commit their K/V into the block pool through the
                    # host-built tables. Prefix-HIT rows skip the main
                    # prefill entirely (1-token dummies, all-sentinel
                    # tables) and are admitted by a suffix-only chunk
                    # dispatch right after — the shared blocks are never
                    # re-prefilled (and never re-written: copy-on-write).
                    full_p = [(i, r) for i, r in packed
                              if not self._slot_blocks[i]["shared"]]
                    hit_p = [(i, r) for i, r in packed
                             if self._slot_blocks[i]["shared"]]

                    def table_row(i: int) -> np.ndarray:
                        pb = self._slot_blocks[i]
                        row = np.full(maxb, nb_, np.int32)
                        ids_b = pb["shared"] + pb["owned"]
                        row[:len(ids_b)] = ids_b
                        return row

                    if caches is None:
                        prompts = np.zeros((B, S_pad), np.int32)
                        lens = np.ones(B, np.int32)
                        tables = np.full((B, maxb), nb_, np.int32)
                        for i, req in full_p:
                            prompts[i, :len(req.tokens)] = req.tokens
                            lens[i] = len(req.tokens)
                            tables[i] = table_row(i)
                        batch = {"tokens": jnp.asarray(prompts),
                                 **self._stack_extras(
                                     [cur_extras[i] for i in range(B)],
                                     extras_keys, live)}
                        with tel.span("engine.prefill",
                                      wave=stats.waves - 1,
                                      rows=len(full_p), seq=S_pad,
                                      paged=True, uids=_uids(full_p)):
                            tok, caches, pos = M._paged_prefill_fn(
                                self.cfg, cap, bs_, self.mesh)(
                                wp, batch, jnp.asarray(lens),
                                jnp.asarray(tables), self._pool, ids)
                    elif full_p:
                        Br = min(_pow2ceil(len(full_p)), _pow2ceil(B))
                        prompts = np.zeros((Br, S_pad), np.int32)
                        lens = np.ones(Br, np.int32)
                        row_idx = np.full(Br, B, np.int32)
                        tables_r = np.full((Br, maxb), nb_, np.int32)
                        for r, (i, req) in enumerate(full_p):
                            prompts[r, :len(req.tokens)] = req.tokens
                            lens[r] = len(req.tokens)
                            row_idx[r] = i
                            tables_r[r] = table_row(i)
                        rex = [cur_extras[i] for i, _ in full_p]
                        rex += [rex[0]] * (Br - len(full_p))
                        batch = {"tokens": jnp.asarray(prompts),
                                 **self._stack_extras(rex, extras_keys,
                                                      [0])}
                        ids_rows = None
                        if tenant:
                            rdom = [req.domain for _, req in full_p]
                            rdom += [rdom[0]] * (Br - len(full_p))
                            ids_rows = self.bank.adapter_ids(rdom)
                        with tel.span("engine.refill",
                                      wave=stats.waves - 1,
                                      rows=len(full_p), seq=S_pad,
                                      paged=True, uids=_uids(full_p)):
                            tok, caches, pos = M._paged_refill_fn(
                                self.cfg, cap, bs_, self.mesh)(
                                wp, batch, jnp.asarray(lens),
                                jnp.asarray(row_idx),
                                jnp.asarray(tables_r),
                                tok, caches, pos, ids_rows)
                    if hit_p:
                        Br = min(_pow2ceil(len(hit_p)), _pow2ceil(B))
                        W = _pow2ceil(max(
                            len(r.tokens)
                            - len(self._slot_blocks[i]["shared"]) * bs_
                            for i, r in hit_p))
                        suf = np.zeros((Br, W), np.int32)
                        slens = np.zeros(Br, np.int32)
                        starts = np.zeros(Br, np.int32)
                        row_idx = np.full(Br, B, np.int32)
                        tables_r = np.full((Br, maxb), nb_, np.int32)
                        for r, (i, req) in enumerate(hit_p):
                            st = len(self._slot_blocks[i]["shared"]) * bs_
                            tail = req.tokens[st:]
                            suf[r, :len(tail)] = tail
                            slens[r], starts[r] = len(tail), st
                            row_idx[r] = i
                            tables_r[r] = table_row(i)
                        ids_rows = None
                        if tenant:
                            rdom = [req.domain for _, req in hit_p]
                            rdom += [rdom[0]] * (Br - len(hit_p))
                            ids_rows = self.bank.adapter_ids(rdom)
                        with tel.span("engine.suffix",
                                      wave=stats.waves - 1,
                                      rows=len(hit_p), seq=W,
                                      uids=_uids(hit_p)):
                            tok, caches, pos = M._paged_suffix_fn(
                                self.cfg, cap, bs_, self.mesh)(
                                wp, jnp.asarray(suf), jnp.asarray(slens),
                                jnp.asarray(starts), jnp.asarray(row_idx),
                                jnp.asarray(tables_r), tok, caches, pos,
                                ids_rows)
                elif caches is None:
                    # initial wave prefill: all B slots (empty slots carry
                    # 1-token dummies and retire immediately)
                    prompts = np.zeros((B, S_pad), np.int32)
                    lens = np.ones(B, np.int32)
                    for i, req in packed:
                        prompts[i, :len(req.tokens)] = req.tokens
                        lens[i] = len(req.tokens)
                    batch = {"tokens": jnp.asarray(prompts),
                             **self._stack_extras(
                                 [cur_extras[i] for i in range(B)],
                                 extras_keys, live)}
                    with tel.span("engine.prefill", wave=stats.waves - 1,
                                  rows=len(packed), seq=S_pad,
                                  uids=_uids(packed)):
                        tok, caches, pos = M._wave_prefill_fn(
                            self.cfg, cap, self.mesh)(
                            wp, batch, jnp.asarray(lens), ids)
                        if self.spec is not None:
                            # drafter rides the same wave: its own prefill
                            # builds the recurrent draft state per row (its
                            # next-token guess is discarded — the chunk
                            # carry is always the target's committed token)
                            dtok, dcaches, dpos = M._wave_prefill_fn(
                                self.spec.cfg, cap, self.mesh)(
                                self.spec.params,
                                {"tokens": batch["tokens"]},
                                jnp.asarray(lens), None)
                else:
                    # in-wave refill: prefill ONLY the admitted rows
                    # (pow2-padded row count) and scatter them into the
                    # live wave state at their slot indices
                    Br = min(_pow2ceil(len(packed)), _pow2ceil(B))
                    prompts = np.zeros((Br, S_pad), np.int32)
                    lens = np.ones(Br, np.int32)
                    row_idx = np.full(Br, B, np.int32)   # pad rows: dropped
                    for r, (i, req) in enumerate(packed):
                        prompts[r, :len(req.tokens)] = req.tokens
                        lens[r] = len(req.tokens)
                        row_idx[r] = i
                    rex = [cur_extras[i] for i, _ in packed]
                    rex += [rex[0]] * (Br - len(packed))
                    batch = {"tokens": jnp.asarray(prompts),
                             **self._stack_extras(rex, extras_keys, [0])}
                    ids_rows = None
                    if tenant:
                        rdom = [req.domain for _, req in packed]
                        rdom += [rdom[0]] * (Br - len(packed))
                        ids_rows = self.bank.adapter_ids(rdom)
                    with tel.span("engine.refill", wave=stats.waves - 1,
                                  rows=len(packed), seq=S_pad,
                                  uids=_uids(packed)):
                        tok, caches, pos = M._refill_fn(
                            self.cfg, cap, self.mesh)(
                            wp, batch, jnp.asarray(lens),
                            jnp.asarray(row_idx), tok, caches, pos, ids_rows)
                        if self.spec is not None:
                            dtok, dcaches, dpos = M._refill_fn(
                                self.spec.cfg, cap, self.mesh)(
                                self.spec.params, {"tokens": batch["tokens"]},
                                jnp.asarray(lens), jnp.asarray(row_idx),
                                dtok, dcaches, dpos, None)
            # deadline sweep: a live row past its monotonic budget is
            # retired HERE, mid-wave, as a timed-out completion with the
            # tokens it has so far — over-budget rows never stall the drain
            with tel.span("engine.schedule"):
                now = time.perf_counter()
                for i in range(B):
                    req = slot_req[i]
                    if req is None or req.deadline_s is None:
                        continue
                    if now - req.t_submit >= req.deadline_s:
                        retire(i, now, timed_out=True)
            if not remaining.any():
                continue                       # re-pack freed slots (or exit)
            # segment length: with queued work, the pow2 floor of the
            # smallest live budget — never longer than the next retirement,
            # so refills happen in-wave. With an empty queue there is
            # nothing to admit at a retirement, so run the longest pow2
            # segment that cannot overshoot the wave (per-row retirement
            # inside the scan idles finished rows either way; fewer
            # dispatches, identical padded_tokens).
            live_rem = remaining[remaining > 0]
            live_uids = [r.uid for r in slot_req if r is not None]
            t_seg0 = time.perf_counter()
            if self.spec is not None:
                # speculative segment: `chunks` draft->verify chunks, each
                # committing 1..k+1 tokens per row. The chunk count is the
                # pow2 floor of the budget in CHUNK units (worst case one
                # committed token per chunk keeps every chunk useful), so
                # the jit cache stays {1, 2, 4, ...} exactly like `seg`.
                Tc = self.spec.k + 1
                budget = int(live_rem.min() if self._queue
                             else live_rem.max())
                chunks = max(1, _pow2floor(max(1, budget // Tc)))
                with tel.span("engine.segment", chunks=chunks, k=self.spec.k,
                              live=len(live_uids), speculative=True,
                              uids=live_uids) as ssp:
                    with tel.span("engine.dispatch"):
                        (toks, counts, dr, ac, tok, caches, dcaches, pos,
                         _) = M._spec_segment_fn(
                            self.cfg, self.spec.cfg, chunks, self.spec.k,
                            self.mesh)(
                            self._wave_params(params, tenant),
                            self.spec.params, tok, caches, dcaches, pos,
                            jnp.asarray(remaining, jnp.int32),
                            jnp.asarray(spec_rows), ids)
                    with tel.span("engine.sync"):
                        toks = np.asarray(toks)      # tracelint: ignore[R2] the ONE deliberate sync: segment done
                        counts = np.asarray(counts)  # tracelint: ignore[R2] same fetch, already synced
                        ssp.set(drafted=int(dr), accepted=int(ac))
                stats.drafted += int(dr)
                stats.accepted += int(ac)
                executed = chunks * Tc * B     # verify slot-steps run
            else:
                seg = _pow2floor(int(live_rem.min() if self._queue
                                     else live_rem.max()))
                key = None
                if not self.greedy:
                    self._key, key = jax.random.split(self._key)
                with tel.span("engine.segment", seg=seg,
                              live=len(live_uids), speculative=False,
                              uids=live_uids):
                    with tel.span("engine.dispatch"):
                        toks, tok, caches, pos, _, key = M._segment_fn(
                            self.cfg, seg, self.greedy, self.mesh)(
                            self._wave_params(params, tenant), tok, caches,
                            pos, jnp.asarray(remaining, jnp.int32), key, ids)
                    with tel.span("engine.sync"):
                        toks = np.asarray(toks)    # tracelint: ignore[R2] the ONE deliberate sync: segment done
                if key is not None:
                    self._key = key            # carried per-step splits
                counts = np.minimum(seg, remaining)
                executed = seg * B
            t_seg1 = time.perf_counter()
            seg_wall = t_seg1 - t_seg0
            with tel.span("engine.schedule"):
                stats.segments += 1
                served_now = 0
                for i in range(B):
                    if remaining[i] <= 0:
                        continue
                    served = int(counts[i])
                    bufs[i].append(toks[i, :served])
                    remaining[i] -= served
                    served_now += served
                    if served > 0:
                        # per-token latency: this row's share of the
                        # segment wall, one observation per served token
                        h_tok.record(seg_wall / served, n=served)
                        tel.observe("engine.tok_latency_s",
                                    seg_wall / served, n=served)
                        if t_first[i] is None:  # first token host-visible
                            t_first[i] = t_seg1
                    if remaining[i] == 0:       # retire: complete + free slot
                        retire(i, t_seg1)
                stats.tokens += served_now
                stats.padded_tokens += executed - served_now
                tel.observe("engine.segment_s", seg_wall)
        if self.paged is not None and caches is not None:
            # persist the committed pool across drains: a freed block's
            # K/V stays addressable until its slot is actually reused,
            # which is what lets a later drain's matching prompt revive
            # it (LRU free list keeps the hash — core/paged.py)
            for g, s in self._psubs:
                c = caches[g][s]
                self._pool[g][s] = {"k": c["k"], "v": c["v"]}
        stats.wall_s = time.perf_counter() - t_all
        stats.ttft_hist = h_ttft.summary()
        stats.tok_latency_hist = h_tok.summary()
        if sla_acc:
            stats.sla_stats = {
                cls: {"ttft_hist": a["ttft"].summary(),
                      "queue_hist": a["queue"].summary(),
                      "deadline_miss": a["miss"], "requests": a["n"]}
                for cls, a in sla_acc.items()}
        tel.count("engine.tokens", stats.tokens)
        tel.count("engine.padded_tokens", stats.padded_tokens)
        drain.set(requests=stats.requests, tokens=stats.tokens,
                  waves=stats.waves, segments=stats.segments)
        drain.__exit__(None, None, None)
        return out, stats

    def _stack_extras(self, cur_extras, keys: frozenset, live) -> dict:
        """Stack each slot's extras row (empty slots replicate a live row)."""
        if not keys:
            return {}
        fallback = cur_extras[live[0]]
        rows = [e if e is not None else fallback for e in cur_extras]
        return {k: jnp.asarray(np.stack([np.asarray(r[k]) for r in rows]))
                for k in keys}

    def serve(self, params, prompts, *, gen: int,
              extra_batch: Optional[dict] = None,
              domains: Optional[list] = None
              ) -> tuple[np.ndarray, EngineStats]:
        """Serve an (N, S) prompt batch in one continuous-batching drain.

        One engine call per round: submits every row (with its
        ``extra_batch`` row, leading dim N, if given, and its ``domains[i]``
        adapter slot for multi-tenant rounds), drains the queue, and
        returns ((N, gen) tokens in submission order, stats)."""
        prompts = np.asarray(prompts)
        if domains is not None and len(domains) != len(prompts):
            raise ValueError(f"domains ({len(domains)}) must name one "
                             f"adapter slot per prompt ({len(prompts)})")
        # mirror the domains check for extra_batch: a short leading dim
        # would otherwise fail deep inside per-row indexing (or, worse,
        # silently truncate a longer one) instead of at the API boundary
        for k, v in (extra_batch or {}).items():
            n = np.shape(v)[0] if np.ndim(v) else 0
            if n != len(prompts):
                raise ValueError(
                    f"extra_batch[{k!r}] leading dim ({n}) must carry one "
                    f"row per prompt ({len(prompts)})")
        uids = [self.submit(p, gen,
                            extras=None if extra_batch is None else
                            {k: np.asarray(v[i]) for k, v in extra_batch.items()},
                            domain=None if domains is None else domains[i])
                for i, p in enumerate(prompts)]
        comps, stats = self.run(params)
        by_uid = {c.uid: c.tokens for c in comps}
        return np.stack([by_uid[u] for u in uids]), stats

    def serve_trace(self, params, trace
                    ) -> tuple[list[Completion], EngineStats]:
        """Serve a TIMED arrival trace with arrival-driven admission.

        ``trace`` is an iterable of ``(t_s, tokens, gen)`` or
        ``(t_s, tokens, gen, submit_kwargs)`` arrivals; ``t_s`` is the
        arrival offset in seconds from the drain start. Unlike
        :meth:`serve` (which front-loads the whole queue), requests are
        ``submit``-ted only when their timestamp comes due inside the
        running drain — queue wait and TTFT measure the engine under
        the OFFERED load (Poisson in benchmarks/latency_bench.py), and
        on a paged engine admission is additionally block-gated, so a
        burst beyond pool capacity queues head-of-line until blocks
        free. Returns (completions, stats) like :meth:`run`."""
        ev = sorted(((float(e[0]), np.asarray(e[1], np.int32), int(e[2]),
                      dict(e[3]) if len(e) > 3 else {}) for e in trace),
                    key=lambda e: e[0])
        self._arrivals = deque(ev)
        self._trace_t0 = time.perf_counter()
        try:
            return self.run(params)
        finally:
            self._arrivals = deque()
