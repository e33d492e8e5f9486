"""The comparison that decides ``correct``: what the timed path produced,
against the configuration's plain reference (``references/<name>.py``).

Serving: a sample of the finished requests drawn from the seed, the one
with the most served tokens always in it. The reference runs once over
each prompt and its served tokens; the number compared is the widest gap
by which a served token's logit lies below the reference's best
(``max_gap``, in standard deviations of the reference's logits at that
position). The tokens are greedy, so a sound program's gap is rounding
only.

Training: the set-up round (the first ``steps`` steps from the seed, run
by the window's own round call). The reference runs the same round in
float32 from the same adapters and rows. Numbers compared, each the worst
over steps or adapter leaves:

- ``loss_gap``: |program loss - reference loss| / reference loss, per step;
- ``grad_gap``: the optimizer's first moment after the round (the
  gradients as AdamW holds them) - the gap between the program's and the
  reference's norm of each leaf, over the larger of that leaf's and the
  median leaf's reference norm;
- ``update_gap``: the same for the change of each adapter leaf over the
  round.

Leaves whose reference first moment is under a thousandth of the median
leaf's are left out of the two leaf numbers (none is, at the adapters'
initial values; the rule is there for a layout that has such leaves).

Each number's limit is in ``limits/<workload>.json`` with the readings it
was set from.
"""
from __future__ import annotations

import importlib
import json
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent


def limits(workload: str) -> dict:
    return json.loads((HERE / "limits" / f"{workload}.json").read_text())


def reference(conf: dict):
    return importlib.import_module(f"bench.references.{conf['reference']}")


def sample(rec: list, k: int, seed: int) -> list:
    """k served requests: the one with most served tokens, and k-1 drawn
    from the seed among the rest."""
    served = [r for r in rec if r["n"] > 0]
    longest = max(served, key=lambda r: (r["n"], len(r["prompt"])))
    rest = [r for r in served if r is not longest]
    rng = np.random.default_rng(seed + 1)
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def _row_inputs(r: dict, width: int, gmax: int):
    P, n = len(r["prompt"]), r["n"]
    seq = np.zeros(width, np.int32)
    seq[:P] = r["prompt"]
    seq[P:P + n - 1] = r["tokens"][:n - 1]
    served = np.zeros(gmax, np.int32)
    served[:n] = r["tokens"][:n]
    return seq, P - 1, served, n


def serving_gaps(conf, traffic, backbone, adapters, rows, *,
                 control: bool = False):
    """Per sampled row: the widest gap of its served tokens (and, with
    ``control``, of the fp8 control's first tokens)."""
    import jax
    import jax.numpy as jnp
    from bench import weights as W
    ref = reference(conf)
    gmax = traffic["output_len"]["max"]
    width = traffic["prompt_len"]["max"] + gmax
    width += (-width) % 128
    items = ref.freeze(conf)
    fn = ref.control_gaps if control else ref.served_gaps
    out = []
    for r in rows:
        seq, start, served, n = _row_inputs(r, width, gmax)
        i = int(r["domain"][1:]) if r["domain"] else 0
        g = fn(items, backbone, W.adapter(adapters, i), jnp.asarray(seq),
               jnp.asarray(start, jnp.int32), jnp.asarray(served), gmax)
        out.append(float(np.max(np.asarray(jax.device_get(g))[:n])))
    return out


def serving(ctx, backbone, adapters, rec) -> dict:
    rows = sample(rec, ctx.traffic["check_requests"], ctx.seed)
    gaps = serving_gaps(ctx.conf, ctx.traffic, backbone, adapters, rows)
    lim = limits(ctx.workload)
    return {"max_gap": {"value": max(gaps),
                        "limit": lim["max_gap"]["limit"]},
            "_info": {"check_rows": len(rows),
                      "check_tokens": int(sum(r["n"] for r in rows))}}


def _norms(tree) -> dict:
    import jax
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): float(np.linalg.norm(
        np.asarray(x, np.float64).ravel())) for p, x in flat}


def leaf_gap(prog: dict, ref: dict, keep: set) -> float:
    """Worst leaf: |norm_prog - norm_ref| / max(norm_ref, median norm_ref)."""
    med = float(np.median([ref[k] for k in keep]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep)


def reference_round(conf, traffic, backbone, before, bank_np,
                    precision: str = "f32") -> dict:
    """The set-up round by the reference: per-step losses, adapters after
    the round and AdamW's first moments."""
    import jax
    import jax.numpy as jnp
    ref = reference(conf)
    hp = tuple(sorted({"lr": traffic["lr"], "b1": traffic["b1"],
                       "b2": traffic["b2"], "eps": traffic["eps"],
                       "precision": precision}.items()))
    bank = {k: jnp.asarray(v) for k, v in bank_np.items()}
    loss, ad, m = (jax.device_get(x) for x in ref.hfsl_round(
        ref.freeze(conf), backbone, before, bank, 0, traffic["steps"],
        traffic["sync_every"], hp))
    return {"loss": loss, "adapters": ad, "m": m}


def compare_rounds(got: dict, ref: dict, before) -> dict:
    """The three numbers of a round ``got`` against the reference's."""
    import jax
    f64 = lambda t: jax.tree.map(lambda x: np.asarray(x, np.float64), t)
    before = f64(before)
    d_ref = _norms(jax.tree.map(lambda a, b: a - b, f64(ref["adapters"]),
                                before))
    d_got = _norms(jax.tree.map(lambda a, b: a - b, f64(got["adapters"]),
                                before))
    m_ref, m_got = _norms(ref["m"]), _norms(got["m"])
    med = float(np.median(list(m_ref.values())))
    keep = {k for k, v in m_ref.items() if v >= 1e-3 * med}
    loss_g = np.asarray(got["loss"], np.float64)
    loss_r = np.asarray(ref["loss"], np.float64)
    return {"loss_gap": float(np.max(np.abs(loss_g - loss_r) / loss_r)),
            "grad_gap": leaf_gap(m_got, m_ref, keep),
            "update_gap": leaf_gap(d_got, d_ref, keep),
            "_leaves": len(keep)}


def training(ctx, backbone, before, bank_np, first) -> dict:
    nums = compare_rounds(first, reference_round(
        ctx.conf, ctx.traffic, backbone, before, bank_np), before)
    lim = limits(ctx.workload)
    out = {k: {"value": nums[k], "limit": lim[k]["limit"]}
           for k in ("loss_gap", "grad_gap", "update_gap")}
    out["_info"] = {"leaves": nums["_leaves"]}
    return out
