"""Edge-drafted speculative decoding: recurrent drafter + batched verify.

GaisNet pairs small edge models with the big cloud model (PAPER.md §III);
the edge-cloud synergy framework of arXiv:2401.01666 makes that pairing
concrete — small models propose, the big model validates. That is exactly
speculative decoding, and it is the decode-bound throughput lever: plain
decode reads the whole cache + weights once PER TOKEN, speculative decode
reads them once per (k+1)-token chunk.

One speculative **chunk** per row:

1. **Draft** — a tiny recurrent drafter (ssm by default: O(1) state, no
   draft KV cache) runs ``k+1`` greedy steps over ``[carry, d1..dk]``
   (model._scan_steps with ``with_state=True``), proposing k tokens and
   snapshotting its per-step state — one snapshot per possible rollback
   point.
2. **Verify** — ONE pass of the target model over all k+1 chunk positions
   against the live caches (model.verify_step): greedy targets are the
   argmax at every offset.
3. **Accept** — greedy exact-match: the longest draft prefix that agrees
   with the targets. ``commit = min(accepted + 1, remaining)`` tokens
   land (the "+1" is the verify pass's own next token — progress is
   guaranteed even at 0% acceptance). Residual sampling for non-greedy
   serving is a recorded follow-up hook.
4. **Rollback** — per-row: attention caches restore the slots rejected
   drafts overwrote (exact for full and sliding-window layouts), and
   recurrent caches gather the snapshot at the committed step. Inactive
   (retired) rows keep their caches bitwise frozen, so a ragged wave
   mixes speculative, plain (``spec_rows=False`` forces commit=1, i.e.
   plain decoding THROUGH the verify pass), and retired rows freely.

Greedy speculative output is token-for-token identical to plain
``generate_scan`` — acceptance only changes how fast tokens commit, never
which tokens commit.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import model as M
from repro.models.transformer import attn_window, groups_for
from repro.sharding.rules import drafter_rules, use_rules


def _pow2floor(n: int) -> int:
    return 1 << (max(int(n), 1).bit_length() - 1)


# ---------------------------------------------------------------------------
# Drafter
# ---------------------------------------------------------------------------


def drafter_config(cfg: ModelConfig, *, d_model: int = 64,
                   n_layers: int = 2) -> ModelConfig:
    """A tiny recurrent drafter config for ``cfg``: ssm family (O(1) state,
    no draft KV cache), shared vocab, no PEFT modules. Quality comes from
    distilling the target into these weights (out of scope here — the
    mechanism is exact for ANY drafter weights, acceptance just varies)."""
    return cfg.with_(
        name=f"{cfg.name}-drafter", family="ssm", n_layers=n_layers,
        d_model=d_model, n_heads=1, n_kv_heads=1, head_dim=0,
        d_ff=2 * d_model, attn_variant="full",
        peft=dataclasses.replace(cfg.peft, n_prefix=0, lora_rank=0,
                                 state_prompt=False, head_dim_out=0))


def _min_window(cfg: ModelConfig) -> int:
    """Smallest nonzero attention window in the stack (0 = unwindowed)."""
    ws = [attn_window(cfg, kind) for _, kinds, _ in groups_for(cfg)
          for kind in kinds if kind in ("attn", "moe")]
    ws = [w for w in ws if w and w > 0]
    return min(ws) if ws else 0


@dataclasses.dataclass
class SpecDecoder:
    """Drafter bundle the engine / spec_generate consume.

    ``cfg``/``params`` are the drafter model (any non-audio/vlm family;
    :func:`drafter_config` builds the recommended recurrent one), ``k`` is
    the number of tokens proposed per chunk."""
    cfg: ModelConfig
    params: dict
    k: int = 4

    def __post_init__(self):
        if self.k < 1:
            raise ValueError(f"SpecDecoder.k={self.k} must be >= 1")

    @classmethod
    def init(cls, target: ModelConfig, key: jax.Array, *, k: int = 4,
             d_model: int = 64, n_layers: int = 2) -> "SpecDecoder":
        dcfg = drafter_config(target, d_model=d_model, n_layers=n_layers)
        return cls(dcfg, M.init(dcfg, key), k=k)

    def validate_target(self, cfg: ModelConfig) -> None:
        """Static compatibility checks, raised at construction/submit time
        rather than as silent corruption mid-wave."""
        for c, role in ((cfg, "target"), (self.cfg, "drafter")):
            if c.family in ("audio", "vlm"):
                raise NotImplementedError(
                    f"speculative decoding: {role} family {c.family!r} "
                    "not supported")
            w = _min_window(c)
            if w and self.k + 1 > w:
                raise ValueError(
                    f"speculative chunk k+1={self.k + 1} exceeds the "
                    f"{role}'s sliding window {w}: a chunk would wrap the "
                    "rolling cache buffer and rollback could not restore "
                    "the overwritten slots")
        if self.cfg.vocab_size != cfg.vocab_size:
            raise ValueError(
                f"drafter vocab {self.cfg.vocab_size} != target vocab "
                f"{cfg.vocab_size}")

    def place(self, mesh) -> "SpecDecoder":
        """Replicate the drafter params on ``mesh`` (sharding/rules.py::
        drafter_rules — tiny weights everywhere beats a collective per
        draft step)."""
        if mesh is None:
            return self
        params = M.place_params(self.params, self.cfg, mesh,
                                rules=drafter_rules())
        return dataclasses.replace(self, params=params)


def draft_chunk(dparams: dict, dcfg: ModelConfig, k: int, tok, dcaches,
                pos, active):
    """k+1 greedy drafter steps over ``[tok, d1..dk]``.

    One step MORE than the k proposals: the per-step snapshots then cover
    every rollback point a chunk can commit to (state after chunk offset
    c-1 for any commit c in 1..k+1). Returns (drafts (B, k), final drafter
    caches — chunk-advanced, rollback-mandatory — and per-step recurrent
    snapshots (L, B, k+1, ...))."""
    remaining = jnp.where(active, jnp.int32(k + 2), jnp.int32(0))
    toks, (_, caches, _, _, _), snaps = M._scan_steps(
        dparams, dcfg, k + 1, True, tok, dcaches, pos, remaining,
        jax.random.PRNGKey(0), None, with_state=True)
    return toks[:, 1:], caches, snaps


# ---------------------------------------------------------------------------
# Rollback
# ---------------------------------------------------------------------------


def _restore_attn(old: dict, new: dict, *, qpos, commit, active, window):
    """Exact attention-cache rollback: re-copy ``old``'s values into every
    slot the chunk wrote at a REJECTED offset (>= commit). For the full
    cache those slots were empty (restores the +1e9 sentinel); for the
    sliding-window rolling buffer they held live older entries the chunk
    overwrote — which post-rollback queries can still see, so value
    restore (not just sentinel-masking) is required for correctness."""
    S = old["pos"].shape[2]
    B, T = qpos.shape
    stale = jnp.arange(T)[None, :] >= commit[:, None]       # (B, T)
    slot = attn_mod.chunk_slots(qpos, window, S)
    slot = jnp.where(stale & active[:, None], slot, S)      # keep-slots OOB
    rows = jnp.arange(B)[:, None]
    gidx = jnp.clip(slot, 0, S - 1)

    def fix(o, n):
        if n.ndim == 3:                              # pos: (L, B, S)
            return n.at[:, rows, slot].set(o[:, rows, gidx], mode="drop")
        return n.at[:, rows, :, slot].set(o[:, rows, :, gidx],  # head-major
                                          mode="drop")

    return {key: fix(old[key], new[key]) for key in old}


def _restore_rec(old: dict, snaps: dict, *, commit, active):
    """Recurrent-cache rollback: gather the per-step snapshot at the last
    committed chunk offset (commit-1); inactive rows keep ``old``."""
    idx = jnp.maximum(commit - 1, 0)

    def fix(o, s):                                   # s: (L, B, T, ...)
        g = jnp.take_along_axis(
            s, idx.reshape((1, -1, 1) + (1,) * (s.ndim - 3)), axis=2)
        g = g[:, :, 0]
        return jnp.where(active.reshape((1, -1) + (1,) * (g.ndim - 2)),
                         g, o)

    return jax.tree.map(fix, old, snaps)


def rollback_caches(cfg: ModelConfig, old: dict, new: dict, snaps: dict, *,
                    pos, commit, active, k: int) -> dict:
    """Per-row cache rollback after a chunk: row b keeps exactly the state
    of having decoded its first ``commit[b]`` chunk tokens plainly
    (inactive rows keep ``old`` bitwise). ``old``/``new`` are the pre-/
    post-chunk cache trees, ``snaps`` the per-step recurrent snapshots."""
    qpos = pos[:, None] + jnp.arange(k + 1, dtype=jnp.int32)[None, :]
    out: dict = {}
    for name, kinds, _ in groups_for(cfg):
        grp: dict = {}
        for i, kind in enumerate(kinds):
            key = f"s{i}"
            if kind in ("attn", "moe"):
                grp[key] = _restore_attn(
                    old[name][key], new[name][key], qpos=qpos,
                    commit=commit, active=active,
                    window=attn_window(cfg, kind))
            else:
                grp[key] = _restore_rec(old[name][key], snaps[name][key],
                                        commit=commit, active=active)
        out[name] = grp
    return out


# ---------------------------------------------------------------------------
# Chunk + segment
# ---------------------------------------------------------------------------


def spec_chunk(params, dparams, cfg: ModelConfig, dcfg: ModelConfig, k: int,
               tok, caches, dcaches, pos, remaining, spec_rows, adapter_ids,
               mesh=None):
    """One draft -> verify -> accept -> rollback chunk for a ragged wave.

    Carry semantics: ``tok`` (B, 1) is the committed-but-unemitted next
    token at position ``pos`` (exactly _scan_steps's carry). The chunk
    emits ``commit`` tokens ``[tok, t1..t_{commit-1}]`` and carries the
    verify target at the last committed offset. ``spec_rows`` (B,) bool
    rows decode plainly through the verify pass when False (commit is
    forced to 1 and their drafts are never counted)."""
    active = remaining > 0
    with use_rules(mesh, drafter_rules() if mesh is not None else None):
        drafts, dnew, dsnaps = draft_chunk(dparams, dcfg, k, tok, dcaches,
                                           pos, active)
    tks = jnp.concatenate([tok, drafts], axis=1)            # (B, k+1)
    logits, vnew, vsnaps = M.verify_step(params, tks, caches, pos, cfg,
                                         adapter_ids=adapter_ids,
                                         active=active)
    tgt = jnp.argmax(logits, axis=-1).astype(jnp.int32)     # (B, k+1)
    match = (drafts == tgt[:, :k]) & spec_rows[:, None]
    acc = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(axis=1)
    commit = jnp.where(active, jnp.minimum(acc + 1, remaining),
                       jnp.int32(0))
    vals = jnp.concatenate([tok, tgt[:, :k]], axis=1)       # emitted stream
    carry = jnp.take_along_axis(tgt, jnp.maximum(commit - 1, 0)[:, None],
                                axis=1)
    tok = jnp.where(active[:, None], carry, tok).astype(jnp.int32)
    caches = rollback_caches(cfg, caches, vnew, vsnaps, pos=pos,
                             commit=commit, active=active, k=k)
    dcaches = rollback_caches(dcfg, dcaches, dnew, dsnaps, pos=pos,
                              commit=commit, active=active, k=k)
    pos = pos + commit
    remaining = remaining - commit
    drafted = jnp.where(active & spec_rows, jnp.int32(k), jnp.int32(0))
    accepted = jnp.maximum(commit - 1, 0)                   # accepted drafts
    return (tok, caches, dcaches, pos, remaining, vals, commit, drafted,
            accepted)


def spec_segment(params, dparams, cfg: ModelConfig, dcfg: ModelConfig,
                 chunks: int, k: int, tok, caches, dcaches, pos, remaining,
                 spec_rows, adapter_ids, mesh=None):
    """``chunks`` scanned speculative chunks in one dispatch (the engine's
    speculative counterpart of model._scan_steps).

    Emitted tokens scatter into a (B, chunks*(k+1)) buffer at per-row
    write offsets (rows commit at different rates); ``counts`` (B,) says
    how many of each row's buffer entries are real. Returns (buffer,
    counts, drafted, accepted, tok, caches, dcaches, pos, remaining)."""
    B = tok.shape[0]
    T = k + 1
    out0 = jnp.zeros((B, chunks * T), jnp.int32)
    off0 = jnp.zeros((B,), jnp.int32)
    rows = jnp.arange(B)[:, None]

    def body(carry, _):
        tok, caches, dcaches, pos, remaining, out, off = carry
        (tok, caches, dcaches, pos, remaining, vals, commit, drafted,
         accepted) = spec_chunk(params, dparams, cfg, dcfg, k, tok, caches,
                                dcaches, pos, remaining, spec_rows,
                                adapter_ids, mesh=mesh)
        idx = off[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
        idx = jnp.where(jnp.arange(T)[None, :] < commit[:, None], idx,
                        out.shape[1])                       # pad -> dropped
        out = out.at[rows, idx].set(vals, mode="drop")
        off = off + commit
        return (tok, caches, dcaches, pos, remaining, out, off), \
            (jnp.sum(drafted), jnp.sum(accepted))

    carry, (drafted, accepted) = jax.lax.scan(
        body, (tok, caches, dcaches, pos, remaining, out0, off0), None,
        length=chunks)
    tok, caches, dcaches, pos, remaining, out, off = carry
    return (out, off, jnp.sum(drafted), jnp.sum(accepted), tok, caches,
            dcaches, pos, remaining)


# ---------------------------------------------------------------------------
# One-call generation (generate_scan's speculative twin)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SpecStats:
    drafted: int = 0
    accepted: int = 0

    @property
    def acceptance_rate(self) -> float:
        return self.accepted / self.drafted if self.drafted else 0.0


def spec_generate(params: dict, cfg: ModelConfig, spec: SpecDecoder,
                  prompts, *, gen: int, prompt_lens=None,
                  adapter_ids=None, spec_rows=None, mesh=None):
    """Greedy speculative generation — token-for-token identical to
    ``generate_scan(..., greedy=True)``, just fewer target cache reads.

    prompts: (B, S) int32. Returns ((B, gen) tokens, SpecStats). The
    drafter prefills alongside the target (its prefill argmax is
    discarded — the carry token is the TARGET's), then pow2-bucketed
    speculative segments drain the per-row budgets."""
    spec.validate_target(cfg)
    prompts = jnp.asarray(prompts, jnp.int32)
    B, S = prompts.shape
    lens = None if prompt_lens is None else \
        jnp.asarray(prompt_lens, jnp.int32)
    ids = None if adapter_ids is None else \
        jnp.asarray(adapter_ids, jnp.int32)
    cap = S + gen
    batch = {"tokens": prompts}
    tok, caches, pos = M._wave_prefill_fn(cfg, cap, mesh)(
        params, batch, lens, ids)
    _, dcaches, _ = M._wave_prefill_fn(spec.cfg, cap, mesh)(
        spec.params, batch, lens, None)
    remaining = jnp.full((B,), gen, jnp.int32)
    rows = jnp.ones((B,), bool) if spec_rows is None else \
        jnp.asarray(spec_rows, bool)
    T = spec.k + 1
    out_np = np.zeros((B, gen), np.int32)
    write = np.zeros((B,), np.int64)
    rem_np = np.full((B,), gen, np.int64)
    stats = SpecStats()
    while rem_np.max() > 0:
        chunks = max(1, _pow2floor(max(1, int(rem_np.max()) // T)))
        (buf, counts, dr, ac, tok, caches, dcaches, pos, remaining) = \
            M._spec_segment_fn(cfg, spec.cfg, chunks, spec.k, mesh)(
                params, spec.params, tok, caches, dcaches, pos, remaining,
                rows, ids)
        counts_np = np.asarray(counts)
        buf_np = np.asarray(buf)
        for b in range(B):
            c = int(counts_np[b])
            out_np[b, write[b]:write[b] + c] = buf_np[b, :c]
            write[b] += c
        rem_np -= counts_np
        stats.drafted += int(dr)
        stats.accepted += int(ac)
    return jnp.asarray(out_np), stats
