"""Hybrid Federated Split Learning trainer (paper §III-C, Fig 4).

The paper's fine-tuning workflow maps onto the TPU mesh as follows:

- **FL inter-cluster parallelism**: every index along the (`pod`, `data`)
  mesh axes is one fine-tuning client cluster. The tunable adapters carry a
  leading ``cluster`` dim (sharded over those axes), so each cluster trains
  its *own* adapter replica on its *own* data shard — zero cross-cluster
  traffic during local steps. The frozen backbone is shared (FSDP-sharded).
- **FedAvg sync**: every ``sync_every`` steps the adapter replicas are
  averaged over the cluster dim (one all-reduce of adapter-sized bytes —
  the paper's "uploading and aggregation of end model"). Optimizer state
  stays cluster-local, as in standard FedAvg.
- **SL intra-cluster seriality** becomes tensor parallelism over `model`
  inside each cluster for production (see core/sl_pipeline.py for the
  faithful serial form).

With ``sync_every=1`` this degenerates to synchronous data-parallel PEFT;
with one cluster it degenerates to SL, matching §III-C.1's remark.

Two execution engines share one step body (:func:`_make_step_body`):

- :func:`make_hfsl_step` — ONE step per call (legacy; one jitted dispatch +
  host sync per step).
- :func:`make_hfsl_round` — K steps in ONE jitted ``lax.scan`` dispatch, the
  fine-tuning twin of models/model.py::generate_scan. FedAvg fires *inside*
  the scan at ``sync_every`` boundaries of the carried step counter; batches
  are gathered from a device-resident bank (data/pipeline.py::BatchBank) by
  the scanned step index, so no host transfer happens inside a round.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import telemetry
from repro.optim.optimizers import Optimizer, apply_updates, clip_by_global_norm
from repro.sharding.rules import (ParamSpec, dim_sharding, hfsl_round_rules,
                                  named_shardings, shard, use_rules)


# ---------------------------------------------------------------------------
# Specs / init
# ---------------------------------------------------------------------------

def _cluster_stack(tree, n: int):
    """Leading `cluster` dim on every adapter ParamSpec.

    Inner `fsdp` axes are dropped: `cluster` already consumes the
    (pod, data) mesh axes, and a spec may not map a mesh axis twice.
    """
    def f(s: ParamSpec) -> ParamSpec:
        inner = tuple(None if a == "fsdp" else a for a in s.axes) if s.axes \
            else tuple([None] * len(s.shape))
        return ParamSpec((n, *s.shape), s.dtype, ("cluster", *inner),
                         init=s.init, scale=s.scale)
    return jax.tree.map(f, tree, is_leaf=lambda x: isinstance(x, ParamSpec))


def hfsl_state_spec(cfg, n_clusters: int, optimizer: Optimizer,
                    model_spec_fn: Callable) -> dict:
    """ParamSpec tree of the full HFSL train state (dry-run compatible).

    Optimizer state is declared by structural analogy: AdamW keeps two f32
    moments per adapter leaf (+ step), SGD keeps zero or one.
    """
    ms = model_spec_fn(cfg)
    adapters_c = _cluster_stack(ms["adapters"], n_clusters)

    def f32_like(s: ParamSpec) -> ParamSpec:
        return ParamSpec(s.shape, jnp.float32, s.axes, init="zeros")

    opt = {
        "step": ParamSpec((n_clusters,), jnp.int32, ("cluster",), init="zeros"),
        "m": jax.tree.map(f32_like, adapters_c,
                          is_leaf=lambda x: isinstance(x, ParamSpec)),
        "v": jax.tree.map(f32_like, adapters_c,
                          is_leaf=lambda x: isinstance(x, ParamSpec)),
    }
    return {
        "backbone": ms["backbone"],
        "adapters_c": adapters_c,
        "opt": opt,
        "step": ParamSpec((), jnp.int32, (), init="zeros"),
    }


def hfsl_state_shardings(cfg, n_clusters: int, optimizer: Optimizer,
                         model_spec_fn: Callable, mesh,
                         rules: Optional[dict] = None) -> dict:
    """NamedSharding tree for the full HFSL train state on ``mesh``.

    Derived from :func:`hfsl_state_spec` via rules.partition_specs: the
    adapter replicas / optimizer moments put their leading ``cluster`` dim
    on the (`pod`, `data`) axes, the backbone FSDP-shards where dims
    divide. This is both what init-time ``jax.device_put`` should place
    (sharded jit inputs must already match the pinned in_shardings) and
    what make_hfsl_round(mesh=...) pins — the two agree by construction.
    """
    rules = rules or hfsl_round_rules(cfg.family)
    spec = hfsl_state_spec(cfg, n_clusters, optimizer, model_spec_fn)
    return named_shardings(spec, mesh, rules)


def init_hfsl_state(key: jax.Array, cfg, n_clusters: int,
                    optimizer: Optimizer, model_init_fn: Callable) -> dict:
    params = model_init_fn(cfg, key)
    adapters_c = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n_clusters, *x.shape)),
        params["adapters"])
    # cluster replicas start identical (edge model delivery, Fig 4 step 1)
    return {
        "backbone": params["backbone"],
        "adapters_c": adapters_c,
        "opt": jax.vmap(optimizer.init)(adapters_c),
        "step": jnp.zeros((), jnp.int32),
    }


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------

def fedavg(adapters_c):
    """FedAvg over the cluster dim: mean, broadcast back to every cluster."""
    return jax.tree.map(
        lambda x: jnp.broadcast_to(
            jnp.mean(x.astype(jnp.float32), axis=0, keepdims=True),
            x.shape).astype(x.dtype),
        adapters_c)


def _make_cluster_update(cfg, optimizer: Optimizer, loss_fn: Callable,
                         clip_norm: float, microbatches: int) -> Callable:
    """Per-cluster local step: grads (optionally accumulated over
    ``microbatches`` splits of the cluster batch) -> one optimizer update."""

    def one_cluster(backbone, adapters, opt_state, batch):
        def inner(a, mb):
            return loss_fn({"backbone": backbone, "adapters": a}, mb, cfg)

        vg = jax.value_and_grad(inner, has_aux=True)
        if microbatches <= 1:
            (loss, aux), grads = vg(adapters, batch)
        else:
            def split(x):
                if x.shape[0] % microbatches:
                    raise ValueError(
                        f"cluster batch {x.shape[0]} not divisible by "
                        f"microbatches={microbatches}")
                return x.reshape(microbatches, x.shape[0] // microbatches,
                                 *x.shape[1:])

            mbs = jax.tree.map(split, batch)
            mb0 = jax.tree.map(lambda x: x[0], mbs)
            (l_av, aux_av), g_av = jax.eval_shape(vg, adapters, mb0)
            zeros = lambda t: jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), t)

            def mb_body(carry, mb):
                gs, ls, axs = carry
                (l, ax), g = vg(adapters, mb)
                return (jax.tree.map(jnp.add, gs, g), ls + l,
                        jax.tree.map(jnp.add, axs, ax)), None

            (gs, ls, axs), _ = jax.lax.scan(
                mb_body, (zeros(g_av), jnp.zeros(l_av.shape, l_av.dtype),
                          zeros(aux_av)), mbs)
            inv = 1.0 / microbatches
            # mean-of-means == full-batch mean for equal splits
            grads = jax.tree.map(lambda g: (g * inv).astype(g.dtype), gs)
            loss = ls * inv
            aux = jax.tree.map(lambda v: v * inv, axs)
        with jax.named_scope("optimizer"):
            if clip_norm:
                grads, _ = clip_by_global_norm(grads, clip_norm)
            updates, opt_state = optimizer.update(grads, opt_state, adapters)
            adapters = apply_updates(adapters, updates)
        return adapters, opt_state, loss, aux

    return one_cluster


def fedavg_masked(adapters_c, mask):
    """Partial-participation FedAvg: mean over the clusters ``mask`` keeps,
    broadcast back to those clusters ONLY — a masked-out (dropped or
    straggling) cluster's replica passes through bit-unchanged. With an
    all-ones mask this is bitwise :func:`fedavg`: the weighted sum·/cnt
    form compiles to (ulp-level) different arithmetic than ``jnp.mean``
    once fused into a round's scan, so the full-participation case runtime-
    selects the plain-mean graph instead of trusting float identities."""
    m = mask.astype(jnp.float32)
    cnt = jnp.maximum(jnp.sum(m), 1.0)      # 0 survivors -> no-op round
    full = jnp.all(m > 0)

    def f(x):
        mm = m.reshape((-1,) + (1,) * (x.ndim - 1))
        xf = x.astype(jnp.float32)
        plain = jnp.mean(xf, axis=0, keepdims=True)
        masked = jnp.sum(xf * mm, axis=0, keepdims=True) / cnt
        avg = jnp.broadcast_to(jnp.where(full, plain, masked),
                               x.shape).astype(x.dtype)
        return jnp.where(mm > 0, avg, x)

    return jax.tree.map(f, adapters_c)


def _clusters_finite(tree) -> jax.Array:
    """Per-cluster all-leaves-finite flag (n_clusters,) for cluster-leading
    trees — the in-scan guard's verdict on each cluster's update."""
    oks = [jnp.all(jnp.isfinite(x.astype(jnp.float32))
                   .reshape(x.shape[0], -1), axis=1)
           for x in jax.tree.leaves(tree)]
    return functools.reduce(jnp.logical_and, oks)


@jax.named_scope("fedavg")
def _sync_at_boundary(adapters_c, new_step, *, sync_every: int,
                      always_sync: bool, mask=None):
    """FedAvg at ``sync_every`` multiples of the (possibly traced) counter.
    With ``mask`` (participation, (n,)), the masked FedAvg aggregates only
    surviving clusters and leaves the rest untouched."""
    avg = fedavg if mask is None else functools.partial(fedavg_masked,
                                                        mask=mask)
    if always_sync or sync_every == 1:
        return avg(adapters_c)
    do_sync = (new_step % sync_every) == 0
    synced = avg(adapters_c)
    return jax.tree.map(
        lambda s, a: jnp.where(do_sync, s, a), synced, adapters_c)


def _make_step_body(cfg, optimizer: Optimizer, loss_fn: Callable, *,
                    sync_every: int, clip_norm: float, always_sync: bool,
                    microbatches: int, spmd_axes=None,
                    faulted: bool = False) -> Callable:
    """``spmd_axes`` names the mesh axes carrying the cluster dim (mesh-
    native rounds): the cluster vmap runs with ``spmd_axis_name`` so the
    activation shard() constraints inside the per-cluster forward stay
    aligned — vmap inserts the mapped cluster dim into every inner spec
    instead of letting it shift the constraint onto the wrong dims.

    ``faulted=True`` returns the fault-tolerant step body
    ``step(state, batch, mask, corrupt)`` instead: a per-cluster
    participation ``mask`` (float (n,), >0 = present) gates both the local
    update and the FedAvg, a per-cluster ``corrupt`` flag NaN-poisons that
    cluster's computed update (core/faults.py), and an in-scan non-finite
    guard ``jnp.where``-skips any cluster whose update went NaN/inf — no
    host sync; the skip just keeps the pre-step replica. The differentiated
    per-cluster step is the SAME graph as the plain body (corruption is
    injected into the update epilogue, never into the grad computation), so
    with an all-ones mask and all-false corrupt the outputs are bitwise
    identical to the plain body (every guard reduces to a select of the
    updated branch)."""
    one_cluster = _make_cluster_update(cfg, optimizer, loss_fn, clip_norm,
                                       microbatches)

    def vstep(state, batch):
        return jax.vmap(one_cluster, in_axes=(None, 0, 0, 0),
                        spmd_axis_name=spmd_axes)(
            state["backbone"], state["adapters_c"], state["opt"], batch)

    def step(state: dict, batch: dict) -> tuple[dict, dict]:
        adapters_c, opt_c, loss_c, aux_c = vstep(state, batch)
        new_step = state["step"] + 1
        adapters_c = _sync_at_boundary(adapters_c, new_step,
                                       sync_every=sync_every,
                                       always_sync=always_sync)
        metrics = {"loss": jnp.mean(loss_c), "loss_per_cluster": loss_c}
        for k in (aux_c or {}):
            metrics[k] = jnp.mean(aux_c[k])
        return {**state, "adapters_c": adapters_c, "opt": opt_c,
                "step": new_step}, metrics

    def step_faulted(state: dict, batch: dict, mask, corrupt
                     ) -> tuple[dict, dict]:
        new_a, new_opt, loss_c, aux_c = vstep(state, batch)
        # gradient-corruption injection: a flagged cluster's update (and
        # loss) is NaN-poisoned AFTER the differentiated step, so the
        # unflagged clusters run the plain body's exact graph while the
        # guard below sees a genuinely non-finite update
        new_a = jax.tree.map(
            lambda x: jnp.where(
                corrupt.reshape((-1,) + (1,) * (x.ndim - 1)),
                jnp.asarray(jnp.nan, x.dtype), x)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, new_a)
        loss_c = jnp.where(corrupt, jnp.asarray(jnp.nan, loss_c.dtype),
                           loss_c)
        part = mask > 0
        # non-finite guard: a cluster whose update (or loss) went NaN/inf
        # keeps its pre-step replica — computed in-scan, surfaced as counts
        ok = (_clusters_finite(new_a) & _clusters_finite(new_opt)
              & jnp.isfinite(loss_c))
        eff = part & ok

        def sel(new, old):
            return jax.tree.map(
                lambda n, o: jnp.where(
                    eff.reshape((-1,) + (1,) * (n.ndim - 1)), n, o), new, old)

        adapters_c = sel(new_a, state["adapters_c"])
        opt_c = sel(new_opt, state["opt"])
        new_step = state["step"] + 1
        adapters_c = _sync_at_boundary(adapters_c, new_step,
                                       sync_every=sync_every,
                                       always_sync=always_sync,
                                       mask=part.astype(jnp.float32))
        # metric means use where-masking (not multiply): a guarded cluster's
        # loss is literally NaN, and NaN * 0 would poison the mean. The
        # all-effective case selects the plain jnp.mean graph so fault-free
        # metrics match the plain body bitwise (same trick as fedavg_masked)
        denom = jnp.maximum(jnp.sum(eff.astype(jnp.float32)), 1.0)
        all_eff = jnp.all(eff)
        mmean = lambda v: jnp.where(
            all_eff, jnp.mean(v), jnp.sum(jnp.where(eff, v, 0.0)) / denom)
        n = part.shape[0]
        metrics = {"loss": mmean(loss_c),
                   "loss_per_cluster": loss_c,
                   "participating": jnp.sum(part.astype(jnp.int32)),
                   "skipped": jnp.sum((part & ~ok).astype(jnp.int32)),
                   "dropped": jnp.asarray(n, jnp.int32)
                   - jnp.sum(part.astype(jnp.int32))}
        for k in (aux_c or {}):
            metrics[k] = mmean(aux_c[k])
        return {**state, "adapters_c": adapters_c, "opt": opt_c,
                "step": new_step}, metrics

    return step_faulted if faulted else step


def make_hfsl_step(cfg, optimizer: Optimizer, loss_fn: Callable, *,
                   sync_every: int = 1, clip_norm: float = 0.0,
                   always_sync: bool = False,
                   microbatches: int = 1) -> Callable:
    """Build the jittable single HFSL train step (one dispatch per step).

    loss_fn(params, batch, cfg) -> (loss, aux). Batch leaves carry a leading
    cluster dim (see data/pipeline.cluster_batches). Prefer
    :func:`make_hfsl_round` on the hot path — it runs K of these per
    dispatch.
    """
    return _make_step_body(cfg, optimizer, loss_fn, sync_every=sync_every,
                           clip_norm=clip_norm, always_sync=always_sync,
                           microbatches=microbatches)


_TRAIN_KEYS = ("adapters_c", "opt", "step")    # donated; backbone never is


def make_hfsl_round(cfg, optimizer: Optimizer, loss_fn: Callable, *,
                    steps: int, sync_every: int = 1, clip_norm: float = 0.0,
                    always_sync: bool = False, microbatches: int = 1,
                    remat: Optional[bool] = None, jit: bool = True,
                    donate: bool = False, mesh=None,
                    rules: Optional[dict] = None,
                    state_spec: Optional[dict] = None) -> Callable:
    """Fused fine-tuning round: ``steps`` HFSL steps in ONE jitted dispatch.

    Returned ``round_fn(state, bank, offset=0) -> (state, metrics)``:

    - ``state`` — the init_hfsl_state dict; the carried ``state['step']``
      counter enters and leaves the scan, so FedAvg phase is preserved
      across rounds (pass the previous round's counter back in).
    - ``bank`` — device-resident batch bank: every leaf shaped
      ``(E, n_clusters, batch, ...)`` (data/pipeline.py::BatchBank.arrays).
      Step ``i`` trains on epoch row ``(offset + i) % E`` — the gather is
      indexed by the scanned step, so the whole round runs without a single
      host->device transfer.
    - ``metrics`` — the per-step metric dicts stacked to leading ``(steps,)``.

    ``microbatches`` accumulates gradients over that many equal splits of
    each cluster batch before the optimizer update (activation memory drops
    by the same factor; the update is numerically the full-batch one).
    ``remat`` is forwarded to ``loss_fn`` (e.g. model.lm_loss re-materializes
    the per-layer forward under ``jax.checkpoint``) for long-sequence LM
    fine-tuning; None leaves the loss untouched for losses without the knob.

    ``donate=True`` donates the round's *train-state* input buffers
    (adapters_c / opt / step — never the frozen backbone) to the jit, so
    XLA reuses them for the round's outputs instead of allocating a second
    full train state. Only enable it when the caller replaces its state
    with the returned one (e.g. ``integrated.upgrade``) — the input
    arrays are invalidated by the call. Parity/baseline harnesses that
    rerun from a kept initial state must leave it off.

    Numerics match ``steps`` sequential :func:`make_hfsl_step` calls on the
    same batches exactly — the two engines share one step body.

    ``mesh`` makes the round mesh-native: the jit's in/out shardings are
    pinned from rules.partition_specs over ``state_spec`` (the
    :func:`hfsl_state_spec` tree — required with ``mesh``), so the adapter
    replicas, optimizer moments, and the bank's batches keep their
    ``cluster`` dim resident on the (`pod`, `data`) axes across rounds (no
    per-round resharding, donation reuses the sharded buffers in place),
    and :func:`~repro.sharding.rules.use_rules` is active inside the
    dispatch so the loss forward's activation constraints resolve against
    ``rules`` (default: per-family hfsl_round_rules). Callers must place
    state and bank to match — :func:`hfsl_state_shardings` /
    ``BatchBank.pack(mesh=...)`` produce exactly these placements.
    """
    if remat is not None:
        loss_fn = functools.partial(loss_fn, remat=remat)
    if mesh is not None and state_spec is None:
        raise ValueError("make_hfsl_round(mesh=...) requires state_spec= "
                         "(the hfsl_state_spec tree) to derive the pinned "
                         "jit in/out shardings")
    rules = rules or (hfsl_round_rules(cfg.family) if mesh is not None
                      else None)
    spmd_axes = None
    if mesh is not None:
        # the mesh axes the cluster dim actually lands on (post
        # divisibility): threaded into the cluster vmap as spmd_axis_name
        n_clusters = state_spec["opt"]["step"].shape[0]
        cluster_spec = dim_sharding(mesh, n_clusters, "cluster",
                                    rules=rules).spec
        ax = cluster_spec[0] if len(cluster_spec) else None
        spmd_axes = ax if ax is None or isinstance(ax, tuple) else (ax,)
    def build_core(faulted: bool) -> Callable:
        step = _make_step_body(cfg, optimizer, loss_fn,
                               sync_every=sync_every, clip_norm=clip_norm,
                               always_sync=always_sync,
                               microbatches=microbatches,
                               spmd_axes=spmd_axes, faulted=faulted)

        def hfsl_round(train: dict, backbone, bank: dict, offset,
                       mask=None, corrupt=None) -> tuple[dict, dict]:
            epoch = jax.tree.leaves(bank)[0].shape[0]
            off = jnp.asarray(offset, jnp.int32)

            def body(carry, i):
                batch = jax.tree.map(lambda x: x[(off + i) % epoch], bank)
                state = {**carry, "backbone": backbone}
                out, metrics = (step(state, batch, mask, corrupt) if faulted
                                else step(state, batch))
                return {k: out[k] for k in _TRAIN_KEYS}, metrics

            with use_rules(mesh, rules):
                return jax.lax.scan(body, train,
                                    jnp.arange(steps, dtype=jnp.int32))

        if not jit:
            return hfsl_round
        # donate only the train state (argnum 0): the backbone rides as its
        # own argument precisely so it is excluded from donation — callers
        # keep serving from the same frozen backbone buffers.
        donate_argnums = (0,) if donate else ()
        if mesh is None:
            return jax.jit(hfsl_round, donate_argnums=donate_argnums)
        state_sh = named_shardings(state_spec, mesh, rules)
        train_sh = {k: state_sh[k] for k in _TRAIN_KEYS}
        # the bank in_sharding is a pytree prefix: one sharding covers
        # every (steps, cluster, batch, ...) leaf — identical to what
        # BatchBank.pack(mesh=...) placed
        bank_sh = dim_sharding(mesh, n_clusters, "cluster", index=1,
                               rules=rules)
        in_sh = (train_sh, state_sh["backbone"], bank_sh, None) \
            + ((None, None) if faulted else ())
        return jax.jit(hfsl_round, in_shardings=in_sh,
                       out_shardings=(train_sh, None),
                       donate_argnums=donate_argnums)

    # the plain core is the only one most callers ever touch; the faulted
    # core (participation mask + corruption flags + non-finite guard) is
    # built on first faulted call so the happy path stays byte-identical
    cores: dict[bool, Callable] = {False: build_core(False)}

    def round_fn(state: dict, bank: dict, offset=0, *, mask=None,
                 corrupt=None) -> tuple[dict, dict]:
        # clean-round fast path, decided on the HOST (mask/corrupt are
        # concrete FaultPlan schedules): a round where no fault fires runs
        # the plain compiled core — bitwise-identical by construction, not
        # by trusting float identities across two different XLA graphs
        clean = ((mask is None or bool((np.asarray(mask) > 0).all()))
                 and (corrupt is None or not bool(np.asarray(corrupt).any())))
        train = {k: state[k] for k in _TRAIN_KEYS}
        # scan-dispatch span (module singleton, resolved per call): the jit
        # returns as soon as the round is ENQUEUED, so the duration is the
        # host-side dispatch share (plus compile on the first call) — the
        # blocked end-to-end round time is the caller's span
        # (integrated.upgrade) or the wall clock around block_until_ready
        tel = telemetry.get()
        with tel.span("hfsl.round_dispatch", steps=steps, clean=clean):
            if clean:
                out, metrics = cores[False](train, state["backbone"], bank,
                                            offset)
            else:
                if True not in cores:
                    cores[True] = build_core(True)
                n = jax.tree.leaves(train["adapters_c"])[0].shape[0]
                mask = (jnp.ones((n,), jnp.float32) if mask is None
                        else jnp.asarray(mask, jnp.float32))
                corrupt = (jnp.zeros((n,), bool) if corrupt is None
                           else jnp.asarray(corrupt, bool))
                out, metrics = cores[True](train, state["backbone"], bank,
                                           offset, mask, corrupt)
        tel.count("hfsl.rounds")
        tel.count("hfsl.steps", steps)
        if not clean:
            tel.count("hfsl.faulted_rounds")
        return {**out, "backbone": state["backbone"]}, metrics

    return round_fn


def consensus_params(state: dict) -> dict:
    """Aggregated model (edge view after FedAvg): cluster-mean adapters."""
    return {"backbone": state["backbone"],
            "adapters": jax.tree.map(
                lambda x: jnp.mean(x.astype(jnp.float32), 0).astype(x.dtype),
                state["adapters_c"])}


# ---------------------------------------------------------------------------
# Communication accounting (per §III-C.2)
# ---------------------------------------------------------------------------

def sync_bytes(adapters_c) -> int:
    """Bytes moved per FedAvg round: each cluster uploads + downloads its
    adapter replica (the parameter-efficient flow; compare a full-model
    FedAvg in benchmarks/fig2_comm.py)."""
    import numpy as np
    one = jax.tree.map(lambda x: x[0], adapters_c)
    per_replica = sum(int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
                      for x in jax.tree.leaves(one))
    n = jax.tree.leaves(adapters_c)[0].shape[0]
    return 2 * n * per_replica
